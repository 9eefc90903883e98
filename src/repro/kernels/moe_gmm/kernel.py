"""Pallas TPU kernel: grouped (per-expert) matmul for MoE FFNs.

x [E, C, D] (capacity-dispatched tokens) x w [E, D, F] -> [E, C, F].
Grid (E, C/bm, F/bn, D/bk); k-dim sequential with an f32 VMEM accumulator.
``row_counts`` [E] (actual tokens per expert) lets the kernel skip output
tiles that contain only padding — the dominant saving under imbalanced
routing (paper §4's workload-imbalance story at the kernel level).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu




def _kernel(counts_ref, x_ref, w_ref, o_ref, acc_ref, *, bm: int, nsteps: int,
            use_counts: bool):
    e = pl.program_id(0)
    im = pl.program_id(1)
    kk = pl.program_id(3)

    needed = jnp.bool_(True)
    if use_counts:
        needed = im * bm < counts_ref[e]

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(needed)
    def _step():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kk == nsteps - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def moe_gmm(x: jax.Array, w: jax.Array,
            row_counts: Optional[jax.Array] = None, *,
            block_m: int = 128, block_n: int = 256, block_k: int = 256,
            interpret: bool = False) -> jax.Array:
    """x [E, C, D] x w [E, D, F] -> [E, C, F]."""
    e, c, d = x.shape
    _, _, f = w.shape
    bm = min(block_m, c)
    bn = min(block_n, f)
    bk = min(block_k, d)
    while c % bm:
        bm //= 2
    while f % bn:
        bn //= 2
    while d % bk:
        bk //= 2
    grid = (e, c // bm, f // bn, d // bk)
    use_counts = row_counts is not None
    if row_counts is None:
        row_counts = jnp.full((e,), c, jnp.int32)

    kern = functools.partial(_kernel, bm=bm, nsteps=grid[3],
                             use_counts=use_counts)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # row_counts, whole array
            pl.BlockSpec((1, bm, bk), lambda e_, im, jn, kk: (e_, im, kk)),
            pl.BlockSpec((1, bk, bn), lambda e_, im, jn, kk: (e_, kk, jn)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e_, im, jn, kk: (e_, im, jn)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(row_counts.astype(jnp.int32), x, w)
