"""Pallas TPU kernel: matmul with *fused data preparation*.

Paper §5.2 (``MatMul2``): the winning CPU operator design parallelizes the
data preparation (layout/dtype massaging) and overlaps it with the FMA-bound
library kernel via hyperthreading.  The TPU-native translation: do the prep
*per tile in VMEM on the VPU* — dtype cast + per-row dequant scaling — inside
the same kernel whose MXU matmul consumes the tile.  The Pallas pipeline
double-buffers HBM->VMEM copies, so prep of tile k+1 overlaps the MXU work
of tile k: the hyperthreading win, re-created with the TPU memory hierarchy.

The reference implementation (``ref.py``) is the ``MatMul1`` shape: prep as
a separate materialized op (one extra HBM round-trip), then a plain dot.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu




def _kernel(x_ref, w_ref, xscale_ref, o_ref, acc_ref, *, nsteps: int,
            out_dtype):
    """One (bm x bn) output tile; k-loop is the last grid dim.

    x tile [bm, bk] (possibly low precision + per-row scale), w tile
    [bk, bn].  Data prep = upcast + scale, done in VMEM right before the
    MXU dot.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ---- fused data preparation (VPU work, overlapped by the pipeline)
    x = x_ref[...].astype(jnp.float32)
    if xscale_ref is not None:
        x = x * xscale_ref[...].astype(jnp.float32)  # [bm, 1] row scales
    w = w_ref[...].astype(jnp.float32)

    # ---- MXU contraction
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == nsteps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def fused_matmul(x: jax.Array, w: jax.Array,
                 x_scale: Optional[jax.Array] = None, *,
                 block_m: int = 256, block_n: int = 256, block_k: int = 512,
                 out_dtype=None, interpret: bool = False) -> jax.Array:
    """x [M, K] (any dtype, e.g. int8/bf16) x w [K, N] -> [M, N].

    ``x_scale`` [M, 1] applies per-row dequantization as the fused prep.
    Block sizes are MXU-aligned (multiples of 128 on the minor dims).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    out_dtype = out_dtype or w.dtype
    bm = min(block_m, m)
    bn = min(block_n, n)
    bk = min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (x.shape, w.shape,
                                                         (bm, bn, bk))
    grid = (m // bm, n // bn, k // bk)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    args = [x, w]
    if x_scale is not None:
        assert x_scale.shape == (m, 1), x_scale.shape
        in_specs.append(pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)))
        args.append(x_scale)
        kern = functools.partial(_kernel, nsteps=grid[2], out_dtype=out_dtype)
    else:
        def kern(x_ref, w_ref, o_ref, acc_ref):
            _kernel(x_ref, w_ref, None, o_ref, acc_ref, nsteps=grid[2],
                    out_dtype=out_dtype)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
