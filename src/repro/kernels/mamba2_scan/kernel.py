"""Pallas TPU kernel: chunked Mamba2 (SSD) scan.

One (batch x head) stream per grid row; the chunk dim is sequential
("arbitrary") so the [N, P] SSM state lives in VMEM scratch across chunks.
Within a chunk the SSD form turns the recurrence into two MXU matmuls
(intra-chunk "attention" + state readout), which is exactly how the XLA
reference in ``repro.models.mamba2`` is structured — the kernel removes the
HBM round-trips between those steps.

Shapes (prepared by ops.py):
    x  [BH, S, P]   dt [BH, S]    (softplus'd, >0)
    b  [BH, S, N]   c  [BH, S, N]
    a  [BH]         (negative per-head decay, -exp(A_log))
Returns y [BH, S, P] and final state [BH, N, P].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu




def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, hout_ref, state_ref,
            *, q: int, nc: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)               # [Q, P]
    dt = dt_ref[0].astype(jnp.float32)             # [Q]
    b = b_ref[0].astype(jnp.float32)               # [Q, N]
    c = c_ref[0].astype(jnp.float32)               # [Q, N]
    a = a_ref[0].astype(jnp.float32)               # scalar (negative)

    da = dt * a                                    # [Q] log decays
    cum = jnp.cumsum(da)                           # [Q] inclusive
    cum_end = cum[q - 1]

    # intra-chunk: y[i] += sum_{j<=i} exp(cum_i - cum_j) (c_i.b_j) dt_j x_j
    lmat = cum[:, None] - cum[None, :]             # [Q, Q]
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lower = cols <= rows
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, lmat, -60.0)), 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    mt = scores * decay * dt[None, :]              # [Q, Q]
    y = jax.lax.dot_general(mt, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y[i] += exp(cum_i) * c_i . state
    y += jnp.exp(cum)[:, None] * jax.lax.dot_general(
        c, state_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: h = exp(cum_end) h + sum_j exp(cum_end - cum_j) dt_j b_j x_j^T
    kdec = b * (jnp.exp(cum_end - cum) * dt)[:, None]   # [Q, N]
    state_ref[...] = state_ref[...] * jnp.exp(cum_end) + jax.lax.dot_general(
        kdec, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _flush():
        hout_ref[0] = state_ref[...].astype(hout_ref.dtype)


def mamba2_scan(x: jax.Array, dt: jax.Array, b: jax.Array, c: jax.Array,
                a: jax.Array, *, chunk: int = 256,
                interpret: bool = False):
    """See module docstring.  Returns (y [BH,S,P], h_final [BH,N,P])."""
    bh, s, p = x.shape
    n = b.shape[-1]
    q = min(chunk, s)
    while s % q:
        q //= 2
    nc = s // q
    grid = (bh, nc)

    kern = functools.partial(_kernel, q=q, nc=nc)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, p), lambda i, ic: (i, ic, 0)),
            pl.BlockSpec((1, q), lambda i, ic: (i, ic)),
            pl.BlockSpec((1, q, n), lambda i, ic: (i, ic, 0)),
            pl.BlockSpec((1, q, n), lambda i, ic: (i, ic, 0)),
            pl.BlockSpec((1,), lambda i, ic: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, p), lambda i, ic: (i, ic, 0)),
            pl.BlockSpec((1, n, p), lambda i, ic: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), x.dtype),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, b, c, a)
