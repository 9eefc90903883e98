"""Public paged decode-attention op: pool-direct reads on every backend.

``paged_attention`` is what ``models/attention.paged_decode_step`` calls
on the serving fast path when the engine's ``paged_kernel`` flag is on.
Dispatch:

* **TPU** — the compiled Pallas kernel (``kernel.paged_decode_attention``):
  scalar-prefetch page tables, one K/V page DMA'd per grid step, online
  softmax in VMEM scratch.  No gathered ring buffer exists at any point.
* **other backends** — ``pool_attention_xla`` below: score the query
  against the *entire* pool and mask by a scattered table-membership
  mask.  Still gather-free (the only scatter is a tiny ``[B, num_pages+1,
  P]`` boolean mask; KV bytes are read in place), and on CPU it lowers to
  two large einsums, which XLA runs faster than the per-page interpret
  emulation of the kernel.  Its cost scales with the *physical pool*, not
  the worst-case table width — cheaper than gather-then-attend whenever
  the pool is oversubscribed (``num_pages < slots * ring_blocks``), which
  is the configuration paging exists for.
* ``interpret=True`` — force the Pallas kernel through interpret mode on
  any backend: the parity-debugging path the kernel tests use on CPU.

Under sharding rules with a multi-device mesh (``Engine(rules=...)``)
the kernel runs inside ``jax.shard_map``: a Mosaic kernel cannot be
partitioned automatically.  Each device attends for its share of the
slots (split the way the ``BATCH`` rule splits them) against whole
pools.

Correctness requires the scheduler invariant that already holds for the
gather path: within one slot's table row every non-trash entry is a
distinct physical page (pages shared *across* slots are fine — that is
prefix sharing).  There is no capability probe and no fallback: on TPU
a kernel that fails to compile raises.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.paged_attention.kernel import paged_decode_attention
from repro.parallel import sharding as sh

NEG_INF = -1e30


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pool_attention_xla(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                       page_table: jax.Array, cache_len: jax.Array, *,
                       window: Optional[int] = None,
                       softcap: Optional[float] = None,
                       k_scale: Optional[jax.Array] = None,
                       v_scale: Optional[jax.Array] = None) -> jax.Array:
    """Gather-free XLA lowering: attend to the whole pool under a
    scattered per-slot validity mask.

    Ring validity (``u = t - ((t - r) mod R)``, window mask) is computed
    in table space ``[B, nb, P]`` and scattered to pool space ``[B,
    num_pages+1, P]`` through the page table; the trash row is then
    force-masked, so duplicate trash entries cannot resurrect it.  Rows
    with no valid position (unadmitted slots) return exactly 0, matching
    the kernel's clamped denominator.  ``q`` may carry ``S`` query rows
    per slot ([B,S,H,dh], the speculative verify step); query row ``i``
    sits at absolute position ``cache_len - S + i`` and the mask is
    evaluated per row, so a drafted query never attends past itself.

    ``k_scale``/``v_scale`` [num_pages+1, Hkv]: 8-bit quantized pools.
    Dequantization is *folded*, pool-wide — the K scale multiplies the
    scores (before the softcap), the V scale multiplies the softmax
    weights — so no fp32 copy of the pool is ever stored (the 8-bit→f32
    cast is a transient XLA fuses into the einsum)."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, sq, h, dh = q.shape
    npg, page_size, hkv, _ = pool_k.shape
    nb = page_table.shape[1]
    ring = nb * page_size
    g = h // hkv
    scale = dh ** -0.5
    quant = k_scale is not None
    if quant:
        pool_k = pool_k.astype(jnp.float32)
        pool_v = pool_v.astype(jnp.float32)
    t = (cache_len - 1)[:, None, None]                         # [B,1,1]
    r = (jnp.arange(nb)[:, None] * page_size
         + jnp.arange(page_size)[None, :])[None]               # [1,nb,P]
    u = t - ((t - r) % ring)                                   # [B,nb,P]
    if sq == 1:              # plain decode: keep the PR-4 lowering exactly
        valid = u >= 0
        if window is not None:
            valid &= u > t - window
        mask = jnp.zeros((b, npg, page_size), bool)
        mask = mask.at[jnp.arange(b)[:, None], page_table].set(valid)
        mask = mask.at[:, npg - 1].set(False)                  # trash row
        q2 = q[:, 0].reshape(b, hkv, g, dh)
        s = jnp.einsum("bkgd,npkd->bkgnp", q2, pool_k)
        s = s.astype(jnp.float32) * scale
        if quant:        # dequant K: fold per-page scales into the scores
            s = s * jnp.transpose(k_scale)[None, :, None, :, None]
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        w = jnp.exp(s - jnp.max(s, axis=(-2, -1), keepdims=True))
        w = jnp.where(mask[:, None, None], w, 0.0)
        l = jnp.maximum(jnp.sum(w, axis=(-2, -1), keepdims=True), 1e-30)
        w = w / l
        if quant:        # dequant V: fold into the softmax weights
            w = w * jnp.transpose(v_scale)[None, :, None, :, None]
        else:
            w = w.astype(pool_v.dtype)
        out = jnp.einsum("bkgnp,npkd->bkgd", w, pool_v)
        out = out.reshape(b, 1, h, dh)
        return out[:, 0] if squeeze else out
    qpos = (cache_len - sq)[:, None] + jnp.arange(sq)[None, :]  # [B,S]
    valid = (u >= 0)[:, None] & (u[:, None] <= qpos[:, :, None, None])
    if window is not None:
        valid &= u[:, None] > qpos[:, :, None, None] - window
    mask = jnp.zeros((b, npg, page_size, sq), bool)
    mask = mask.at[jnp.arange(b)[:, None], page_table].set(
        jnp.moveaxis(valid, 1, -1))
    mask = mask.at[:, npg - 1].set(False)                      # trash row
    mask = jnp.moveaxis(mask, 3, 1)[:, None, None]             # [B,1,1,S,n,P]
    q2 = q.reshape(b, sq, hkv, g, dh)
    s = jnp.einsum("bqkgd,npkd->bkgqnp", q2, pool_k)
    s = s.astype(jnp.float32) * scale
    if quant:                # [1,k,1,1,n,1] — scale per (page, kv head)
        s = s * jnp.transpose(k_scale)[None, :, None, None, :, None]
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(mask, s, NEG_INF)
    w = jnp.exp(s - jnp.max(s, axis=(-2, -1), keepdims=True))
    w = jnp.where(mask, w, 0.0)
    l = jnp.maximum(jnp.sum(w, axis=(-2, -1), keepdims=True), 1e-30)
    w = w / l
    if quant:
        w = w * jnp.transpose(v_scale)[None, :, None, None, :, None]
    else:
        w = w.astype(pool_v.dtype)
    out = jnp.einsum("bkgqnp,npkd->bqkgd", w, pool_v)
    return out.reshape(b, sq, h, dh)


def paged_attention(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                    page_table: jax.Array, cache_len: jax.Array, *,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None,
                    interpret: bool = False) -> jax.Array:
    """Pool-direct decode attention for 1..K+1 query rows per slot
    (``q`` [B,H,dh] or [B,S,H,dh]); ``k_scale``/``v_scale``
    [num_pages+1, Hkv] when the pools are 8-bit quantized (dequant
    happens inside whichever lowering runs); see module docstring for
    dispatch."""
    if interpret or _on_tpu():
        def kern(q, pool_k, pool_v, page_table, cache_len, k_scale=None,
                 v_scale=None):
            return paged_decode_attention(
                q, pool_k, pool_v, page_table, cache_len, window=window,
                softcap=softcap, k_scale=k_scale, v_scale=v_scale,
                interpret=interpret or not _on_tpu())

        args = [q, pool_k, pool_v, page_table, cache_len]
        if k_scale is not None:
            args += [k_scale, v_scale]
        rules = sh.current_rules()
        if rules is None or rules.mesh is None or rules.mesh.size == 1:
            return kern(*args)
        rows = rules.spec_for((sh.BATCH,), (q.shape[0],))  # P() if ragged
        whole = P()
        return jax.shard_map(
            kern, mesh=rules.mesh,
            in_specs=(rows, whole, whole, rows, rows) + (whole,) * (
                len(args) - 5),
            out_specs=rows, check_vma=False)(*args)
    return pool_attention_xla(q, pool_k, pool_v, page_table, cache_len,
                              window=window, softcap=softcap,
                              k_scale=k_scale, v_scale=v_scale)
