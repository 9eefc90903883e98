"""Pallas TPU flash attention (forward): blockwise online softmax.

Layout [B, H, S, dh]; grid (B*H, Sq/bq, Skv/bk) with the KV dim sequential
("arbitrary") so running max / denominator / accumulator live in VMEM
scratch across KV steps.  Supports causal masking, sliding windows, logit
softcap (gemma2/grok) and GQA (kv-head index derived from the q-head grid
index).  Fully-masked KV blocks are skipped with ``pl.when`` — the Pallas
analogue of flash attention's block-sparsity on the causal structure.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, nkv: int, causal: bool,
            window: Optional[int], softcap: Optional[float], scale: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = iq * bq
    k_lo = ik * bk
    needed = jnp.bool_(True)
    if causal:
        needed = k_lo <= q_lo + bq - 1
    if window is not None:
        needed = jnp.logical_and(needed, k_lo + bk - 1 > q_lo - window)

    @pl.when(needed)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)         # [bq, dh]
        k = k_ref[0, 0].astype(jnp.float32)         # [bk, dh]
        v = v_ref[0, 0].astype(jnp.float32)         # [bk, dh]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        rows = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = cols <= rows
        if window is not None:
            mask = jnp.logical_and(mask, cols > rows - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nkv - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q [B,H,Sq,dh]; k,v [B,Hkv,Skv,dh] -> [B,H,Sq,dh]."""
    b, h, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    g = h // hkv
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    while sq % bq:
        bq //= 2
    while skv % bk:
        bk //= 2
    grid = (b * h, sq // bq, skv // bk)
    scale = dh ** -0.5

    kern = functools.partial(
        _kernel, bq=bq, bk=bk, nkv=grid[2], causal=causal, window=window,
        softcap=softcap, scale=scale)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh),
                         lambda i, iq, ik: (i // h, i % h, iq, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda i, iq, ik: (i // h, (i % h) // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda i, iq, ik: (i // h, (i % h) // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda i, iq, ik: (i // h, i % h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running denominator
            pltpu.VMEM((bq, dh), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
