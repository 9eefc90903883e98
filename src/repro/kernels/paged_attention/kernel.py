"""Pallas TPU paged decode attention: gather-free, double-buffered pool
reads with in-kernel dequantization.

Up to ``K+1`` decode tokens per slot (one for plain decode, several for a
speculative verify step) attend to the slot's block-paged KV ring
(``serve/cache.py`` pool layout ``[num_pages+1, page_size, kv_heads,
dh]`` behind a per-slot page table) *without* ever materializing the
gathered ``[slots, ring, kv_heads, dh]`` buffer the XLA path builds.
The page table and cache lengths ride in as **scalar prefetch** operands
(``pltpu.PrefetchScalarGridSpec``); the pools stay in HBM/ANY memory
and the kernel issues its own page DMAs (``pltpu.make_async_copy``) into
a 2-deep VMEM ring: while page ``j`` is being scored, page ``j+1``'s
copy is already in flight (double buffering), so the DMA latency hides
behind the flash-style online-softmax compute.

**Quantized pools** (``k_scale``/``v_scale`` given): K/V pages are
stored 8-bit (int8 / fp8_e4m3) with per-page, per-kv-head fp32 scales in
a parallel scale pool.  The wrapper gathers each slot's scales through
its page table (``[slots, nb * kv_heads]``, 1/(page_size * dh) of the
page bytes) and hands the kernel one slot's row per grid step as an
SMEM block; the kernel reads them as scalars and folds them in — the K
scale into the scores before the softcap, the V scale into the PV
accumulation — so the *dequantized* page never exists anywhere: HBM
holds 8-bit, VMEM holds one 8-bit page block, and dequantization is two
scalar multiplies per kv head.  (Scale rows are ``kv_heads`` lanes wide,
below the 128-lane VMEM tiling, so they never travel through VMEM.)

Per page the kernel recomputes the ring-validity mask from the same
formula the XLA path uses (``models/attention.ring_token_positions``):
ring offset ``r`` holds absolute token ``u = t - ((t - r) mod R)``,
valid iff ``u >= 0`` (ever written) and, for sliding windows, ``u > t -
window``.  With ``q_len > 1`` query rows (speculative verify), query
row ``i`` sits at absolute position ``t - (q_len-1) + i`` and the mask
is evaluated *per row*, so a drafted query can attend to the drafted
tokens before it but never to the ones after it.  The **trash page**
(last pool row, where unreserved table entries point) contributes -inf
scores: a table entry equal to the trash id masks its whole page, so a
slot whose reservation ran out can never attend to the write-discard
garbage.  A slot with *no* valid page (unadmitted / warmup rows)
produces exactly 0 output — the denominator is clamped, matching
``ref.paged_attention_ref``.

Grouped-query attention needs no KV repeat: queries arrive grouped
``[slots, kv_heads, q_len * group, dh]`` and each kv head's page block
is shared by its ``q_len * group`` query rows inside the kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(pt_ref, cl_ref, q_ref, kp_ref, vp_ref, *rest, page_size: int,
            nb: int, hkv: int, g: int, q_len: int, trash: int,
            window: Optional[int], softcap: Optional[float], scale: float,
            quantized: bool):
    if quantized:
        ks_ref, vs_ref, *rest = rest     # [1, 1, nb * hkv] SMEM scales
    o_ref, kbuf, vbuf, sem_k, sem_v, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    t = cl_ref[b] - 1                    # newest query's absolute position
    ring = nb * page_size
    rows = q_len * g                     # query rows per kv head

    def page_copies(j, slot):
        """The async copies that stream page ``pt[b, j]`` into VMEM ring
        slot ``slot``.  Reconstructed identically at start and wait time
        (the descriptors are pure functions of their arguments)."""
        p = pt_ref[b, j]
        return [pltpu.make_async_copy(kp_ref.at[p], kbuf.at[slot],
                                      sem_k.at[slot]),
                pltpu.make_async_copy(vp_ref.at[p], vbuf.at[slot],
                                      sem_v.at[slot])]

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for c in page_copies(0, 0):          # warm the pipeline: page 0
        c.start()

    def body(j, _):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < nb)
        def _prefetch():                 # overlap: start page j+1 now
            for c in page_copies(j + 1, jax.lax.rem(j + 1, 2)):
                c.start()

        for c in page_copies(j, slot):   # land page j
            c.wait()

        phys = pt_ref[b, j]
        r = (j * page_size
             + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1))
        u = t - ((t - r) % ring)         # latest token at each ring offset
        valid = u >= 0
        if window is not None:
            valid = jnp.logical_and(valid, u > t - window)
        if q_len > 1:
            # per-row causal mask: row i (of any kv head) is query q = i//g
            # at absolute position t - (q_len - 1) + (i // g)
            qpos = (t - (q_len - 1)
                    + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // g)
            valid = jnp.logical_and(u >= 0, u <= qpos)       # [rows, P]
            if window is not None:
                valid = jnp.logical_and(valid, u > qpos - window)
        # page-skip predicate AFTER the per-row recompute: a page whose
        # tokens are stale for the newest row can still be in-window for an
        # earlier draft row (its window starts q_len-1 positions earlier)
        live = jnp.logical_and(phys != trash, jnp.any(valid))

        @pl.when(live)
        def _step():
            q = q_ref[0].astype(jnp.float32)            # [Hkv*q_len*G, dh]
            kb = kbuf[slot]                             # [P, Hkv, dh]
            vb = vbuf[slot]
            for kh in range(hkv):   # static loop: one dot per kv head
                k = kb[:, kh].astype(jnp.float32)       # [P, dh]
                v = vb[:, kh].astype(jnp.float32)
                sl = slice(kh * rows, (kh + 1) * rows)
                s = jax.lax.dot_general(
                    q[sl], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # [rows, P]
                if quantized:       # dequant K: fold the page's scale in
                    s = s * ks_ref[0, 0, j * hkv + kh]
                if softcap is not None:
                    s = jnp.tanh(s / softcap) * softcap
                s = jnp.where(valid, s, NEG_INF)
                m_prev = m_ref[sl]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                # masked-accumulate: a row with NO valid position anywhere
                # (cl < q_len pad/draft rows) must flush to exactly 0, not
                # exp(NEG_INF - NEG_INF) == 1 garbage weights
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                corr = jnp.exp(m_prev - m_new)
                l_ref[sl] = (l_ref[sl] * corr
                             + jnp.sum(p, axis=1, keepdims=True))
                pv = jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if quantized:       # dequant V: fold into the PV update
                    pv = pv * vs_ref[0, 0, j * hkv + kh]
                acc_ref[sl] = acc_ref[sl] * corr + pv
                m_ref[sl] = m_new

        return _

    jax.lax.fori_loop(0, nb, body, None)
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, pool_k: jax.Array,
                           pool_v: jax.Array, page_table: jax.Array,
                           cache_len: jax.Array, *,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           interpret: bool = False) -> jax.Array:
    """q [B,H,dh] (single decode token) or [B,S,H,dh] (S <= K+1 verify
    rows, newest last); pools [num_pages+1,P,Hkv,dh]; page_table [B,nb]
    int32; cache_len [B] int32 (valid tokens *including* the newest query
    token, whose KV must already be written through the table);
    ``k_scale``/``v_scale`` [num_pages+1,Hkv] fp32 per-page scales when
    the pools are 8-bit quantized -> output shaped like ``q``."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, s, h, dh = q.shape
    npg, page_size, hkv, _ = pool_k.shape
    nb = page_table.shape[1]
    g = h // hkv
    quantized = k_scale is not None
    # rows grouped by kv head: [B, Hkv, S, G, dh] -> [B, Hkv*S*G, dh]
    qr = q.reshape(b, s, hkv, g, dh).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(b, hkv * s * g, dh)
    kern = functools.partial(
        _kernel, page_size=page_size, nb=nb, hkv=hkv, g=g, q_len=s,
        trash=npg - 1, window=window, softcap=softcap, scale=dh ** -0.5,
        quantized=quantized)
    rows = h * s
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        pl.BlockSpec((1, rows, dh), lambda i, pt, cl: (i, 0, 0)),
        any_spec,                  # pool_k stays in HBM; kernel DMAs pages
        any_spec,                  # pool_v
    ]
    operands = [qr, pool_k, pool_v]
    scratch = [
        pltpu.VMEM((2, page_size, hkv, dh), pool_k.dtype),  # K page ring
        pltpu.VMEM((2, page_size, hkv, dh), pool_v.dtype),  # V page ring
    ]
    sems = [
        pltpu.SemaphoreType.DMA((2,)),                      # K page DMA
        pltpu.SemaphoreType.DMA((2,)),                      # V page DMA
    ]
    if quantized:
        # each slot's per-page scales, gathered through its table row:
        # grid step i reads row i from SMEM as scalars
        scale_spec = pl.BlockSpec((1, 1, nb * hkv),
                                  lambda i, pt, cl: (i, 0, 0),
                                  memory_space=pltpu.SMEM)
        in_specs += [scale_spec, scale_spec]
        operands += [
            sc.astype(jnp.float32)[page_table].reshape(b, 1, nb * hkv)
            for sc in (k_scale, v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # page_table + cache_len feed the DMAs
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, dh), lambda i, pt, cl: (i, 0, 0)),
        scratch_shapes=scratch + sems + [
            pltpu.VMEM((rows, 1), jnp.float32),    # running max
            pltpu.VMEM((rows, 1), jnp.float32),    # running denominator
            pltpu.VMEM((rows, dh), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table.astype(jnp.int32), cache_len.astype(jnp.int32), *operands)
    out = out.reshape(b, hkv, s, g, dh).transpose(0, 2, 1, 3, 4)
    out = out.reshape(b, s, h, dh)
    return out[:, 0] if squeeze else out
