"""Pallas TPU kernel: chunked RWKV6 wkv with data-dependent decay.

One (batch x head) stream per grid row, chunk dim sequential, state [K, V]
in VMEM scratch.  The chunk math matches ``repro.models.rwkv6.wkv_chunked``:
the factored decay products are normalized so every exponent is bounded by
|LOG_W_MIN| * chunk (log decays are pre-clamped by the caller).

Shapes (prepared by ops.py):
    r,k,v [BH, S, K]   lw [BH, S, K] (log decays, <= 0)   u [BH, K]
Returns y [BH, S, K] and final state [BH, K, V].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu




def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, hout_ref, state_ref,
            *, q: int, nc: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)              # [Q, K]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)              # [K]

    cw = jnp.cumsum(lw, axis=0)                   # [Q, K] inclusive
    cwx = cw - lw                                 # exclusive
    cw_end = cw[q - 1]                            # [K]

    r_tilde = r * jnp.exp(cwx)
    k_tilde = k * jnp.exp(-cw)
    amat = jax.lax.dot_general(r_tilde, k_tilde, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    amat = jnp.where(cols < rows, amat, 0.0)      # strictly lower
    y = jax.lax.dot_general(amat, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # diagonal bonus: (r . (u * k)) v
    diag = jnp.sum(r * k * u[None, :], axis=1, keepdims=True)
    y += diag * v
    # inter-chunk: r_tilde . state
    y += jax.lax.dot_general(r_tilde, state_ref[...], (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)

    kdec = k * jnp.exp(cw_end[None, :] - cw)
    state_ref[...] = state_ref[...] * jnp.exp(cw_end)[:, None] + \
        jax.lax.dot_general(kdec, v, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _flush():
        hout_ref[0] = state_ref[...].astype(hout_ref.dtype)


def rwkv6_wkv(r, k, v, lw, u, *, chunk: int = 16, interpret: bool = False):
    """See module docstring."""
    bh, s, kk = r.shape
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
            pl.BlockSpec((1, q, kk), lambda i, ic: (i, ic, 0)),
            pl.BlockSpec((1, q, kk), lambda i, ic: (i, ic, 0)),
            pl.BlockSpec((1, q, kk), lambda i, ic: (i, ic, 0)),
            pl.BlockSpec((1, q, kk), lambda i, ic: (i, ic, 0)),
            pl.BlockSpec((1, kk), lambda i, ic: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, kk), lambda i, ic: (i, ic, 0)),
            pl.BlockSpec((1, kk, kk), lambda i, ic: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, kk), r.dtype),
            jax.ShapeDtypeStruct((bh, kk, kk), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((kk, kk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, lw, u)
