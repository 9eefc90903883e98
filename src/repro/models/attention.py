"""GQA attention: chunked (flash-style) train/prefill path, seq-sharded
decode path, KV caches, sliding windows, softcaps, cross-attention.

Two XLA-level implementations (the Pallas flash kernel in
``repro.kernels.flash_attention`` is the TPU hot path; these are the
lower-&-compile-friendly references that the dry-run uses):

* ``chunked_attention`` — query-chunked online attention.  The chunk loop is
  a *python* loop (static), so HLO FLOPs are exact and peak memory is one
  chunk of scores, not the full S x S matrix.  Sliding windows slice the KV
  statically per chunk.
* ``decode_attention`` — one-token attention against a KV cache laid out
  ``[B, Hkv, S, dh]`` with S sharded over the *model* mesh axis.  Softmax
  and the PV contraction reduce over the sharded S dim; GSPMD turns those
  into the flash-decode all-reduce pattern automatically.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import module as m
from repro.models.layers import rope
from repro.parallel import sharding as sh

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Param defs
# ---------------------------------------------------------------------------

def attn_defs(cfg: ModelConfig, d_in: Optional[int] = None) -> Dict:
    d = d_in or cfg.d_model
    dh = cfg.resolved_head_dim
    return {
        "wq": m.ParamDef((d, cfg.num_heads, dh), (m.EMBED, m.HEADS, m.HEAD_DIM)),
        "wk": m.ParamDef((d, cfg.num_kv_heads, dh), (m.EMBED, m.KV_HEADS, m.HEAD_DIM)),
        "wv": m.ParamDef((d, cfg.num_kv_heads, dh), (m.EMBED, m.KV_HEADS, m.HEAD_DIM)),
        "wo": m.ParamDef((cfg.num_heads, dh, d), (m.HEADS, m.HEAD_DIM, m.EMBED)),
    }


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------

def _softcap(scores: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return scores
    return jnp.tanh(scores / cap) * cap


def _pick_q_chunk(sq: int, q_chunk: Optional[int]) -> int:
    if q_chunk is None:
        q_chunk = 2048
    q_chunk = min(q_chunk, sq)
    while sq % q_chunk:
        q_chunk //= 2
    return max(q_chunk, 1)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, window: Optional[int] = None,
                      softcap: Optional[float] = None,
                      q_chunk: Optional[int] = None) -> jax.Array:
    """q [B,Sq,H,dh]; k,v [B,Skv,Hkv,dh] -> [B,Sq,H,dh].

    For causal self-attention we assume query i sits at absolute position i
    with Skv == Sq (train / prefill).  ``causal=False, window=None`` is the
    encoder / cross-attention case.
    """
    b, sq, h, dh = q.shape
    _, skv, hkv, _ = k.shape
    g = h // hkv
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        k = sh.shard(k, sh.BATCH, None, sh.HEADS, None)
        v = sh.shard(v, sh.BATCH, None, sh.HEADS, None)
    scale = dh ** -0.5
    cq = _pick_q_chunk(sq, q_chunk)
    outs = []
    for qs in range(0, sq, cq):  # static python loop: exact HLO flops
        qe = qs + cq
        qc = q[:, qs:qe]
        if causal:
            klo = 0 if window is None else max(0, qs - window + 1)
            khi = min(qe, skv)
        else:
            klo, khi = 0, skv
        ks, vs = k[:, klo:khi], v[:, klo:khi]
        # bf16-out dot (f32 MXU accumulation); upcast for the softmax only so
        # the *cotangent* of qc/ks stays bf16 (f32 cotangents would double
        # every backward activation and collective, see EXPERIMENTS.md §Perf)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qc, ks)
        scores = scores.astype(jnp.float32) * scale
        scores = _softcap(scores, softcap)
        if causal:
            qpos = jnp.arange(qs, qe)[:, None]
            kpos = jnp.arange(klo, khi)[None, :]
            mask = kpos <= qpos
            if window is not None:
                mask &= kpos > qpos - window
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, vs))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def cp_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 causal: bool, window: Optional[int] = None,
                 softcap: Optional[float] = None) -> jax.Array:
    """Context-parallel attention: q stays seq-sharded on the model axis
    (explicit shard_map so XLA cannot replicate it), KV is gathered once.
    Windowed layers dynamic-slice only ``window + s_loc`` keys, so gemma3's
    5:1 local layers keep their flops savings under CP."""
    import functools

    from jax.sharding import PartitionSpec as P

    rules = sh.current_rules()
    mesh = rules.mesh
    ax = rules.table[sh.SEQ]
    b, sq, h, dh = q.shape
    _, skv, hkv, _ = k.shape
    g = h // hkv
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    scale = dh ** -0.5
    nshards = rules.mesh_size(ax)
    s_loc = sq // nshards
    klen = min((window or skv) + s_loc, skv) if causal else skv
    axname = ax if isinstance(ax, str) else ax[0]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, ax, None, None), P(), P()),
        out_specs=P(None, ax, None, None), check_vma=False)
    def f(q_loc, k_full, v_full):
        idx = jax.lax.axis_index(axname)
        offset = idx * s_loc
        if causal and klen < skv:
            start = jnp.clip(offset + s_loc - klen, 0, skv - klen)
            k_sl = jax.lax.dynamic_slice_in_dim(k_full, start, klen, 1)
            v_sl = jax.lax.dynamic_slice_in_dim(v_full, start, klen, 1)
            kpos0 = start
        else:
            k_sl, v_sl, kpos0 = k_full, v_full, 0
        s_ = jnp.einsum("bqhd,bkhd->bhqk", q_loc, k_sl)
        s_ = s_.astype(jnp.float32) * scale
        s_ = _softcap(s_, softcap)
        if causal:
            qpos = offset + jnp.arange(s_loc)[:, None]
            kpos = kpos0 + jnp.arange(k_sl.shape[1])[None, :]
            mask = kpos <= qpos
            if window is not None:
                mask &= kpos > qpos - window
            s_ = jnp.where(mask[None, None], s_, NEG_INF)
        p = jax.nn.softmax(s_, axis=-1).astype(v_full.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v_sl)

    return f(q, k, v)


def decode_attention(q: jax.Array, ck: jax.Array, cv: jax.Array,
                     cache_len: jax.Array, *,
                     window: Optional[int] = None,
                     softcap: Optional[float] = None,
                     valid: Optional[jax.Array] = None) -> jax.Array:
    """q [B,Sq,H,dh]; cache [B,Hkv,S,dh] (S model-sharded); cache_len counts
    valid entries *including* the newest query token.

    ``valid`` [B,S] overrides the default position-order mask — the paged
    path passes ``ring_valid`` because its KV rows are in ring order, not
    absolute order.  ``Sq > 1`` is the speculative-verify case (several
    drafted query rows per slot in one dispatch); there ``valid`` must be
    given per query row as [B,Sq,S], because each drafted query may only
    attend to cache entries at or before its own position."""
    b, sq, h, dh = q.shape
    _, hkv, s, _ = ck.shape
    g = h // hkv
    scale = dh ** -0.5
    if sq == 1:
        q2 = q[:, 0].reshape(b, hkv, g, dh)
        scores = jnp.einsum("bkgd,bksd->bkgs", q2,
                            ck).astype(jnp.float32) * scale
        scores = _softcap(scores, softcap)
        if valid is None:
            pos = jnp.arange(s)
            valid = pos[None, :] < cache_len[:, None]      # [B, S]
            if window is not None:
                valid &= pos[None, :] >= cache_len[:, None] - window
        scores = jnp.where(valid[:, None, None], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)  # GSPMD reduces
        out = jnp.einsum("bkgs,bksd->bkgd", p, cv)
        return out.reshape(b, 1, h, dh)
    assert valid is not None and valid.ndim == 3, \
        "multi-query decode attention needs a per-query [B,Sq,S] mask"
    q2 = q.reshape(b, sq, hkv, g, dh)
    scores = jnp.einsum("bqkgd,bksd->bkgqs", q2, ck).astype(jnp.float32)
    scores = _softcap(scores * scale, softcap)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
    out = jnp.einsum("bkgqs,bksd->bqkgd", p, cv)
    return out.reshape(b, sq, h, dh)


# ---------------------------------------------------------------------------
# Paged KV decode (serve/cache.py block-paged pools)
# ---------------------------------------------------------------------------

def ring_token_positions(cache_len: jax.Array, ring: int) -> jax.Array:
    """Absolute token position held by each ring slot.

    The paged/ring write rule puts token ``t`` at ring index ``t % ring``,
    so slot ``r`` holds the *latest* token ``u <= t_cur`` with ``u === r
    (mod ring)``; a negative ``u`` means the slot was never written.
    ``cache_len`` [B] counts tokens *including* the current one.
    Returns [B, ring] int32."""
    t = (cache_len - 1)[:, None]                       # [B,1] current token
    r = jnp.arange(ring)[None, :]                      # [1,R]
    return t - ((t - r) % ring)


def ring_valid(cache_len: jax.Array, ring: int,
               window: Optional[int]) -> jax.Array:
    """[B, ring] attention validity for a ring-ordered KV layout: written
    slots only, window-masked by *absolute* position (a ring rounded up to
    page granularity may physically retain a few tokens older than the
    window — they must not be attended)."""
    u = ring_token_positions(cache_len, ring)
    valid = u >= 0
    if window is not None:
        valid &= u > (cache_len - 1)[:, None] - window
    return valid


def paged_ring_blocks(window: Optional[int], max_blocks: int,
                      page_size: int, spec_slack: int = 0) -> int:
    """Logical ring width in pages for a paged attention layer — must match
    ``serve/cache.CacheSpec``'s per-layer ``ring_blocks`` (it does:
    ``ceil(min(max_len, window)/P) == min(ceil(max_len/P), ceil(window/P))``
    and ``max_blocks == ceil(max_len/P)``).

    ``spec_slack`` widens *windowed* rings by the speculative draft length
    ``K`` (``serve/spec``): a verify step writes up to ``K`` tokens past
    the newest committed one, and without the slack those writes would
    ring-wrap onto tokens still inside the window of the earliest query
    row.  Full-attention rings never wrap, so they take no slack."""
    if window is None:
        return max_blocks
    return min(max_blocks, -(-(window + spec_slack) // page_size))


def page_group_key(ring_blocks: int) -> str:
    """Stable pytree key of the pool group with the given ring width.

    Paged layers are grouped by ring width into independently-budgeted
    pools (``serve/cache.PoolGroup``); the decode path recovers each
    layer's group from its ring width alone, so the key must be a pure
    function of it."""
    return f"ring{ring_blocks}"


def kv_pool_qmax(pool_dtype) -> Optional[float]:
    """Symmetric quantization range of an 8-bit pool dtype.

    ``None`` means the pool is not quantized (fp32/bf16 pools store K/V
    directly and carry no scale pool)."""
    dt = jnp.dtype(pool_dtype)
    if dt == jnp.dtype(jnp.int8):
        return 127.0
    if dt == jnp.dtype(jnp.float8_e4m3fn):
        return 448.0
    return None


def quantize_pages(x: jax.Array, pool_dtype) -> Tuple[jax.Array, jax.Array]:
    """Quantize full pages to an 8-bit pool dtype with per-(page, kv-head)
    symmetric amax scales.

    x [..., P, Hkv, dh] fp32 -> (q [..., P, Hkv, dh] ``pool_dtype``,
    scale [..., Hkv] fp32) with ``x ~= q * scale``.  The scale floor keeps
    all-zero pages (and the trash page) at a finite, tiny scale so the
    dequantized pool never produces inf/nan — zero pages round-trip to
    exact zeros."""
    qmax = kv_pool_qmax(pool_dtype)
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(-3, -1))
    scale = jnp.maximum(amax, 1e-30) / qmax
    y = x.astype(jnp.float32) / scale[..., None, :, None]
    y = jnp.clip(y, -qmax, qmax)     # pre-clip: round(127.49) must not hit 128
    if jnp.dtype(pool_dtype) == jnp.dtype(jnp.int8):
        y = jnp.round(y)
    return y.astype(pool_dtype), scale


def dequantize_pages(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_pages`: q [..., P, Hkv, dh] 8-bit,
    scale [..., Hkv] -> fp32 [..., P, Hkv, dh]."""
    return q.astype(jnp.float32) * scale[..., None, :, None]


def rmw_quantized_pages(pool: jax.Array, scales: jax.Array,
                        phys: jax.Array, new_vals: jax.Array,
                        wrote: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Re-quantizing read-modify-write of whole pages.

    Gathers the ``phys`` pages [...] from ``pool`` [npg+1, P, Hkv, dh],
    dequantizes them with ``scales`` [npg+1, Hkv], overlays ``new_vals``
    [..., P, Hkv, dh] where ``wrote`` [..., P] is set, recomputes each
    page's amax scale and scatters pages + scales back.  Partial-page
    writes therefore re-quantize the whole page — the only correct RMW
    when the page's amax may have changed.

    Distinct non-trash entries of ``phys`` must name distinct pages (the
    scheduler's exclusive-write invariant; shared pages go copy-on-write
    at admission).  Duplicate *trash* entries race benignly: the trash
    page's contents are never attended (every consumer masks table
    entries equal to the trash id) and its scale stays finite."""
    ex = dequantize_pages(pool[phys], scales[phys])
    merged = jnp.where(wrote[..., None, None], new_vals.astype(jnp.float32),
                       ex)
    q, s = quantize_pages(merged, pool.dtype)
    return pool.at[phys].set(q), scales.at[phys].set(s)


def prefix_prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             ck: jax.Array, cv: jax.Array, off: jax.Array,
                             *, softcap: Optional[float] = None
                             ) -> jax.Array:
    """Suffix-prefill attention against a shared-prefix KV context.

    q/k/v [B,S,H(kv),dh] carry the *suffix* tokens at absolute positions
    ``off + i`` (rope already applied); ck/cv [B,C,Hkv,dh] are the prefix
    KV gathered from the paged pool in block order, so ctx token ``j``
    sits at absolute position ``j`` and is valid iff ``j < off`` (the
    tail of the gathered ctx is trash-page padding).  Used by the prefix-
    sharing admission path: prefill runs only on the suffix, attending to
    the prefix through pages it never recomputes."""
    b, s, h, dh = q.shape
    c = ck.shape[1]
    hkv = k.shape[2]
    g = h // hkv
    if g > 1:
        # constrain once after each repeat, like chunked_attention: GSPMD
        # otherwise reshards the partially-sharded kv on every constraint
        k = sh.shard(jnp.repeat(k, g, axis=2), sh.BATCH, None, sh.HEADS, None)
        v = sh.shard(jnp.repeat(v, g, axis=2), sh.BATCH, None, sh.HEADS, None)
        ck = sh.shard(jnp.repeat(ck, g, axis=2),
                      sh.BATCH, None, sh.HEADS, None)
        cv = sh.shard(jnp.repeat(cv, g, axis=2),
                      sh.BATCH, None, sh.HEADS, None)
    kall = jnp.concatenate([ck.astype(q.dtype), k], axis=1)
    vall = jnp.concatenate([cv.astype(q.dtype), v], axis=1)
    scale = dh ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kall)
    scores = scores.astype(jnp.float32) * scale
    scores = _softcap(scores, softcap)
    qpos = off + jnp.arange(s)[:, None]                     # [S,1]
    kpos = jnp.concatenate([jnp.arange(c), off + jnp.arange(s)])
    kvalid = jnp.concatenate([jnp.arange(c) < off,
                              jnp.ones((s,), bool)])
    mask = (kpos[None, :] <= qpos) & kvalid[None, :]        # [S,C+S]
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(vall.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vall)


def paged_decode_step(q: jax.Array, kk: jax.Array, vv: jax.Array,
                      cache: Dict, cache_len: jax.Array, *,
                      window: Optional[int],
                      softcap: Optional[float],
                      paged_kernel: bool = False
                      ) -> Tuple[jax.Array, Dict]:
    """``S``-token attention against a block-paged KV pool (``S == 1`` is
    the plain decode step; ``S == K+1`` is the speculative verify step).

    cache: {"pk","pv": [num_pages+1, P, Hkv, dh], "pt": [B, ring_blocks],
    optional "wm": [B] bool write mask, optional "ks","vs":
    [num_pages+1, Hkv] per-page per-kv-head scales when the pool is
    8-bit quantized — writes then re-quantize whole pages (RMW) and the
    read side dequantizes, so fp32 K/V never exists at pool width}.
    Writes the new KV through the
    page table (write-then-attend, so every query token attends to
    itself and the drafted tokens before it), then either gathers the
    slot's logical ring and masks by ring validity (default), or — with
    ``paged_kernel=True`` — reads the pool *directly* through
    ``kernels/paged_attention`` (Pallas page streaming on TPU, pool-wide
    masked attention elsewhere) so the gathered ``[B, ring, Hkv, dh]``
    buffer never exists.  All shapes are static: the compiled decode
    chunk only indexes the table the host populated at admission.

    ``wm`` (the engine passes its ``active`` slot mask) redirects the
    writes of finished/idle slots to the trash page.  A slot that
    finishes mid-chunk keeps "decoding" until the next drain with its
    position still advancing — without the mask those dead writes would
    ring-wrap past the table into real pages, which under prefix sharing
    may be pages other slots (or the radix index) still read.

    Multi-token steps (``S > 1``) additionally trash-redirect any write
    whose absolute position falls outside the ring *unless the ring
    legitimately wraps* (``ring >= window + S - 1`` — the spec-slack
    sizing from ``serve/cache.CacheSpec``): a full-attention ring never
    wraps, so a draft written past the table must be discarded rather
    than alias block 0 (which may be a shared prefix page), and an
    under-sized windowed ring must likewise refuse the wrap because it
    would overwrite tokens still inside an earlier query's window.
    Rollback of rejected drafts is free: the accept step simply does not
    advance ``len`` past them, the ring-validity mask hides positions
    beyond ``len``, and the next step's writes land on the same (page,
    offset) cells."""
    pool_k, pool_v, pt = cache["pk"], cache["pv"], cache["pt"]
    ks, vs = cache.get("ks"), cache.get("vs")    # per-page scales (8-bit pool)
    quant = ks is not None
    b, s = q.shape[0], q.shape[1]
    page_size = pool_k.shape[1]

    def _result(out, pool_k, pool_v, ks, vs):
        new = {"pk": pool_k, "pv": pool_v}
        if quant:
            new["ks"], new["vs"] = ks, vs
        return out, new

    if s == 1:
        blocks = paged_ring_blocks(window, pt.shape[1], page_size)
        ring = blocks * page_size
        t = cache_len - 1                               # [B] current position
        lb = (t // page_size) % blocks                  # logical block
        phys = jnp.take_along_axis(pt[:, :blocks], lb[:, None], axis=1)[:, 0]
        wm = cache.get("wm")
        if wm is not None:
            phys = jnp.where(wm, phys, pool_k.shape[0] - 1)   # dead -> trash
        off = t % page_size
        k_new = kk[:, 0]                                # [B, Hkv, dh]
        v_new = vv[:, 0]
        # distinct live slots own every page they write (host invariant:
        # shared pages go copy-on-write at admission); idle/dead slots map
        # to the shared trash page where last-write-wins races are harmless
        if quant:
            bi = jnp.arange(b)
            wrote = jnp.zeros((b, page_size), bool).at[bi, off].set(True)
            shape = (b, page_size) + k_new.shape[1:]
            nk = jnp.zeros(shape, jnp.float32).at[bi, off].set(
                k_new.astype(jnp.float32))
            nv = jnp.zeros(shape, jnp.float32).at[bi, off].set(
                v_new.astype(jnp.float32))
            pool_k, ks = rmw_quantized_pages(pool_k, ks, phys, nk, wrote)
            pool_v, vs = rmw_quantized_pages(pool_v, vs, phys, nv, wrote)
        else:
            pool_k = pool_k.at[phys, off].set(k_new.astype(pool_k.dtype))
            pool_v = pool_v.at[phys, off].set(v_new.astype(pool_v.dtype))
        if paged_kernel:
            from repro.kernels.paged_attention import paged_attention
            out = paged_attention(q[:, 0], pool_k, pool_v, pt[:, :blocks],
                                  cache_len, window=window, softcap=softcap,
                                  k_scale=ks, v_scale=vs)
            return _result(out[:, None], pool_k, pool_v, ks, vs)
        gk = pool_k[pt[:, :blocks]]        # [B, blocks, P, Hkv, dh]
        gv = pool_v[pt[:, :blocks]]
        if quant:
            gk = dequantize_pages(gk, ks[pt[:, :blocks]])
            gv = dequantize_pages(gv, vs[pt[:, :blocks]])
        ck = jnp.moveaxis(gk.reshape(b, ring, *gk.shape[3:]), 1, 2)
        cv = jnp.moveaxis(gv.reshape(b, ring, *gv.shape[3:]), 1, 2)
        valid = ring_valid(cache_len, ring, window)
        out = decode_attention(q, ck, cv, cache_len, softcap=softcap,
                               valid=valid)
        return _result(out, pool_k, pool_v, ks, vs)
    # ---- multi-token verify step (speculative decoding); the table is
    # the layer's own group table, so its width IS the ring width
    blocks = pt.shape[1]
    ring = blocks * page_size
    trash = pool_k.shape[0] - 1
    g_pos = (cache_len - s)[:, None] + jnp.arange(s)[None, :]   # [B,S] abs
    lb = (g_pos // page_size) % blocks
    phys = jnp.take_along_axis(pt, lb, axis=1)                  # [B,S]
    ok = jnp.ones(g_pos.shape, bool)
    if not (window is not None and ring >= window + s - 1):
        ok &= g_pos < ring              # non-wrapping ring: no write aliasing
    wm = cache.get("wm")
    if wm is not None:
        # [B] slot mask (verify step) or [B,S] per-row mask (fused mixed
        # prefill+decode chunk: leading pad rows write to trash)
        ok &= wm if wm.ndim == 2 else wm[:, None]
    off = g_pos % page_size
    if quant:
        # page-granular RMW: the S tokens of a row touch at most
        # J = ceil((S-1)/P) + 1 consecutive logical pages starting at the
        # page of the earliest token.  Scatter tokens into per-page
        # overlays, then re-quantize each touched page once.
        J = (s - 1) // page_size + 2
        base = g_pos[:, :1] // page_size                 # [B,1] earliest page
        jtok = g_pos // page_size - base                 # [B,S] in [0, J)
        lp = base + jnp.arange(J)[None, :]               # [B,J] logical pages
        bi = jnp.arange(b)[:, None]
        page_live = jnp.zeros((b, J), bool).at[bi, jtok].max(ok)
        if J > blocks:
            # a ring narrower than the touched span aliases: of logical
            # pages congruent mod `blocks`, only the newest may be written
            page_live &= jnp.arange(J)[None, :] + blocks >= J
        pphys = jnp.take_along_axis(pt, lp % blocks, axis=1)     # [B,J]
        pphys = jnp.where(page_live, pphys, trash)
        wrote = jnp.zeros((b, J, page_size), bool).at[bi, jtok, off].max(ok)
        shape = (b, J, page_size) + kk.shape[2:]
        nk = jnp.zeros(shape, jnp.float32).at[bi, jtok, off].set(
            kk.astype(jnp.float32))
        nv = jnp.zeros(shape, jnp.float32).at[bi, jtok, off].set(
            vv.astype(jnp.float32))
        pool_k, ks = rmw_quantized_pages(pool_k, ks, pphys, nk, wrote)
        pool_v, vs = rmw_quantized_pages(pool_v, vs, pphys, nv, wrote)
    else:
        phys = jnp.where(ok, phys, trash)
        pool_k = pool_k.at[phys, off].set(kk.astype(pool_k.dtype))
        pool_v = pool_v.at[phys, off].set(vv.astype(pool_v.dtype))
    if paged_kernel:
        from repro.kernels.paged_attention import paged_attention
        out = paged_attention(q, pool_k, pool_v, pt, cache_len,
                              window=window, softcap=softcap,
                              k_scale=ks, v_scale=vs)
        return _result(out, pool_k, pool_v, ks, vs)
    gk = pool_k[pt]                    # [B, blocks, P, Hkv, dh]
    gv = pool_v[pt]
    if quant:
        gk = dequantize_pages(gk, ks[pt])
        gv = dequantize_pages(gv, vs[pt])
    ck = jnp.moveaxis(gk.reshape(b, ring, *gk.shape[3:]), 1, 2)
    cv = jnp.moveaxis(gv.reshape(b, ring, *gv.shape[3:]), 1, 2)
    u = ring_token_positions(cache_len, ring)                   # [B, ring]
    valid = (u >= 0)[:, None, :] & (u[:, None, :] <= g_pos[:, :, None])
    if window is not None:
        valid &= u[:, None, :] > g_pos[:, :, None] - window
    out = decode_attention(q, ck, cv, cache_len, softcap=softcap,
                           valid=valid)
    return _result(out, pool_k, pool_v, ks, vs)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + cache plumbing)
# ---------------------------------------------------------------------------

def init_cache_shape(cfg: ModelConfig, batch: int, max_len: int
                     ) -> Tuple[Tuple[int, int, int, int], Tuple]:
    shape = (batch, cfg.num_kv_heads, max_len, cfg.resolved_head_dim)
    axes = (sh.BATCH, None, sh.KV_SEQ, None)
    return shape, axes


def apply(params: Dict, x: jax.Array, *, cfg: ModelConfig,
          window: Optional[int], positions: jax.Array,
          mode: str, cache: Optional[Dict] = None,
          cache_len: Optional[jax.Array] = None,
          causal: bool = True,
          q_chunk: Optional[int] = None,
          ctx: Optional[Dict] = None,
          paged_kernel: bool = False
          ) -> Tuple[jax.Array, Optional[Dict]]:
    """x [B,S,d] -> (y [B,S,d], new_cache | None).

    mode: "dense" (train / encoder: no cache), "prefill" (returns cache),
    "decode" (S==1; reads+updates cache; cache_len includes current token).

    ``paged_kernel`` (paged decode only): read KV straight from the page
    pool via ``kernels/paged_attention`` instead of gather-then-attend.

    ``ctx`` (prefill only): shared-prefix context for a *suffix* prefill —
    ``{"pk","pv": pool, "row": [Cb] page ids, "off": scalar}``.  The
    layer's queries sit at absolute positions ``off + i`` (``positions``
    must already carry the offset) and attend to the ``off`` prefix
    tokens gathered from the paged pool without recomputing them.
    """
    dt = x.dtype
    rules = sh.current_rules()
    cp = bool(rules and rules.context_parallel)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    kk = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    vv = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if cp and mode != "decode":
        # context parallelism: q stays seq-sharded (chunk slices align with
        # the shards); the narrow GQA kv is gathered across the model axis
        q = sh.shard(q, sh.BATCH, sh.SEQ, None, None)
        kk = sh.shard(kk, sh.BATCH, None, None, None)
        vv = sh.shard(vv, sh.BATCH, None, None, None)
    else:
        q = sh.shard(q, sh.BATCH, None, sh.HEADS, None)
        if mode != "decode" and cfg.num_kv_heads < cfg.num_heads:
            # GQA: replicate the narrow kv ONCE here; otherwise GSPMD
            # reshards the partially-sharded kv on every repeat/constraint
            # (4 gathers/layer measured on gemma3 — EXPERIMENTS.md §Perf)
            kk = sh.shard(kk, sh.BATCH, None, None, None)
            vv = sh.shard(vv, sh.BATCH, None, None, None)
    q = rope(q, positions, cfg.rope_theta)
    kk = rope(kk, positions, cfg.rope_theta)

    new_cache = None
    if mode == "prefill" and ctx is not None:
        # prefix sharing: gather the matched prefix KV from the paged pool
        # (block order == position order for the non-wrapping full-
        # attention group) and prefill only the suffix against it.
        gk = ctx["pk"][ctx["row"]]              # [Cb, P, Hkv, dh]
        gv = ctx["pv"][ctx["row"]]
        if ctx.get("ks") is not None:           # quantized pool: dequant the
            gk = dequantize_pages(gk, ctx["ks"][ctx["row"]])  # gathered pages
            gv = dequantize_pages(gv, ctx["vs"][ctx["row"]])
        cb, psz = gk.shape[0], gk.shape[1]
        ck = gk.reshape(1, cb * psz, *gk.shape[2:])
        cv = gv.reshape(1, cb * psz, *gv.shape[2:])
        out = prefix_prefill_attention(q, kk, vv, ck, cv, ctx["off"],
                                       softcap=cfg.attn_softcap)
        ck_new = sh.shard(jnp.swapaxes(kk, 1, 2),
                          sh.BATCH, None, sh.KV_SEQ, None)
        cv_new = sh.shard(jnp.swapaxes(vv, 1, 2),
                          sh.BATCH, None, sh.KV_SEQ, None)
        new_cache = {"k": ck_new, "v": cv_new}
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
        return sh.shard(y, sh.BATCH, sh.SEQ, sh.EMBED), new_cache
    if mode == "decode" and cache is not None and "pk" in cache:
        # block-paged KV (serve/cache.py): pool + page-table indirection
        out, new_cache = paged_decode_step(
            q, kk, vv, cache, cache_len, window=window,
            softcap=cfg.attn_softcap, paged_kernel=paged_kernel)
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
        return sh.shard(y, sh.BATCH, sh.SEQ, sh.EMBED), new_cache
    if mode == "decode":
        assert cache is not None and cache_len is not None
        if x.shape[1] != 1:
            raise NotImplementedError(
                "multi-token decode (speculative verify) needs a paged "
                "cache; the dense ring-buffer path is single-token only")
        k_new = jnp.swapaxes(kk, 1, 2)  # [B,Hkv,1,dh]
        v_new = jnp.swapaxes(vv, 1, 2)
        size = cache["k"].shape[2]
        # windowed layers keep a ring buffer of `window` slots; keys carry
        # absolute rope positions, so slot order does not matter and ring
        # occupancy enforces the window mask for free.
        idx = ((cache_len - 1) % size).astype(jnp.int32)
        ck = _update_cache(cache["k"], k_new, idx)
        cv = _update_cache(cache["v"], v_new, idx)
        ring = window is not None and size <= window
        out = decode_attention(q, ck, cv, cache_len,
                               window=None if ring else window,
                               softcap=cfg.attn_softcap)
        new_cache = {"k": ck, "v": cv}
    elif cp and x.shape[1] % max(rules.mesh_size(rules.table.get(sh.SEQ)), 1) == 0 \
            and rules.mesh_size(rules.table.get(sh.SEQ)) > 1:
        out = cp_attention(q, kk, vv, causal=causal, window=window,
                           softcap=cfg.attn_softcap)
        if mode == "prefill":
            ck = sh.shard(jnp.swapaxes(kk, 1, 2), sh.BATCH, None, sh.KV_SEQ, None)
            cv = sh.shard(jnp.swapaxes(vv, 1, 2), sh.BATCH, None, sh.KV_SEQ, None)
            new_cache = {"k": ck, "v": cv}
    else:
        out = chunked_attention(q, kk, vv, causal=causal, window=window,
                                softcap=cfg.attn_softcap, q_chunk=q_chunk)
        if mode == "prefill":
            ck = sh.shard(jnp.swapaxes(kk, 1, 2), sh.BATCH, None, sh.KV_SEQ, None)
            cv = sh.shard(jnp.swapaxes(vv, 1, 2), sh.BATCH, None, sh.KV_SEQ, None)
            new_cache = {"k": ck, "v": cv}
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return sh.shard(y, sh.BATCH, sh.SEQ, sh.EMBED), new_cache


def _update_cache(cache: jax.Array, new: jax.Array, idx: jax.Array) -> jax.Array:
    """Write ``new`` [B,Hkv,1,dh] at sequence position ``idx`` [B]."""
    # one-hot masked update keeps the S dim sharded (no gather/scatter resharding)
    s = cache.shape[2]
    onehot = (jnp.arange(s)[None, :] == idx[:, None])  # [B,S]
    onehot = onehot[:, None, :, None]
    return jnp.where(onehot, new.astype(cache.dtype), cache)


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_attn_defs(cfg: ModelConfig) -> Dict:
    return attn_defs(cfg)


def cross_apply(params: Dict, x: jax.Array, enc_kv: Dict, *,
                cfg: ModelConfig) -> jax.Array:
    """x [B,S,d]; enc_kv {"k","v": [B,Henc_kv,Senc,dh]} precomputed."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    q = sh.shard(q, sh.BATCH, None, sh.HEADS, None)
    k = jnp.swapaxes(enc_kv["k"], 1, 2)  # [B,Senc,Hkv,dh]
    v = jnp.swapaxes(enc_kv["v"], 1, 2)
    out = chunked_attention(q, k.astype(dt), v.astype(dt), causal=False)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return sh.shard(y, sh.BATCH, sh.SEQ, sh.EMBED)


def encode_kv(params: Dict, enc_out: jax.Array, *, cfg: ModelConfig) -> Dict:
    dt = enc_out.dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"].astype(dt))
    return {"k": jnp.swapaxes(k, 1, 2), "v": jnp.swapaxes(v, 1, 2)}
