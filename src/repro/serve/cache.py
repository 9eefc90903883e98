"""Unified decode-cache subsystem: CacheSpec + refcounted block-paged pools.

Before this module, every serving slot preallocated a dense ``max_len`` KV
row per attention layer (``models/transformer.cache_structure``), so total
capacity was ``slots x max_len`` tokens no matter how long the actual
sequences were — the static-worst-case allocation the paper flags as a
naive-setting trap (§2.2.3).  ``CacheSpec`` replaces that plumbing with a
per-layer *kind* derived from ``ModelConfig``:

* ``PAGED_KV`` (attention / zamba2 shared-attention layers): keys and
  values live in block-paged pools.  Layers are grouped into **pool
  groups** by their logical ring width (``ceil(min(max_len, window) /
  page_size)`` pages); each group owns an independent pool
  ``[group.num_pages + 1, page_size, kv_heads, head_dim]``, an
  independent page budget, and an independent per-slot page table
  ``[slots, ring_blocks]``.  Sliding-window layers therefore size their
  pool to the *window* (``slots x ring_blocks`` pages) instead of the
  shared ``num_pages`` budget — the per-layer page-id remapping that
  removes the old flat-pool byte overhead for windowed archs.  Windowed
  groups ring over their table (token ``t`` lives at ring index ``t %
  ring``), so one mapping serves full attention, sliding windows, and
  wrap-around.  The last pool row of each group is a **trash page**:
  unreserved table entries point at it, so a slot whose budget ran out
  (or that finished mid-chunk) writes garbage there instead of into a
  neighbour's pages.
* ``STATE`` (mamba2 / rwkv6 layers): O(1) recurrent state stays dense
  ``[slots, ...]`` exactly as before — paging constant-size state buys
  nothing.

Pages are **refcounted** (``serve/scheduler.PagePool``): a physical page
may back the same logical block of several slots at once (prefix sharing
across requests with a common prompt, indexed by the scheduler's radix
tree) and stays allocated until every table reference *and* the radix
index drop it.  A slot that would write into a shared page gets a private
copy first (``copy_shared_page`` — the jitted copy-on-write path); the
compiled decode chunk itself never needs to know, because the host
guarantees at admission time that every page a slot will write is
exclusively owned or trash.

Total tokens per slot are bounded by the widest group's page budget, not
a per-slot preallocation, which lifts the ``max_len`` ceiling: one
request can run past the old dense per-slot limit as long as pages are
free.

Physical page ids are allocated host-side (``serve/scheduler``) at
admission, so the fused decode chunk stays a single shape-stable
executable with zero host synchronization: the compiled code only ever
*indexes* the tables, never grows them.

Sharding: the spec carries logical axes for every buffer (slot-batched
state on ``sh.BATCH``, every group's page pool on ``sh.PAGES``), so a
``parallel/sharding.Rules`` table mapping both to the data mesh axis
shards the serving state over the data axis of ``launch/mesh.py`` meshes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, MAMBA2, RWKV6, SHARED_ATTN, ModelConfig
from repro.models import attention, mamba2, rwkv6
from repro.models.attention import page_group_key
from repro.parallel import sharding as sh

PAGED_KV = "paged_kv"    # block-paged KV ring (attention mixers)
STATE = "state"          # constant-size recurrent state (mamba2 / rwkv6)

# ---- pool precision: K/V pages may be stored 8-bit with per-page,
# per-kv-head fp32 scales in a parallel scale pool ("ks"/"vs"); every
# producer re-quantizes whole pages (attention.rmw_quantized_pages) and
# every consumer dequantizes in the attention path, so fp32 K/V never
# materializes at pool width.
KV_DTYPES = ("fp32", "int8", "fp8_e4m3")


def kv_pool_dtype(kv_dtype: str):
    """jnp dtype the K/V pools are stored in for ``kv_dtype``."""
    if kv_dtype == "int8":
        return jnp.int8
    if kv_dtype == "fp8_e4m3":
        return jnp.float8_e4m3fn
    return jnp.float32


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@dataclasses.dataclass(frozen=True)
class PoolGroup:
    """One independently-budgeted page pool shared by every paged layer
    with the same logical ring width."""

    key: str            # "ring{R}" — stable pytree key for tables/pools
    ring_blocks: int    # page-table width (pages per slot)
    num_pages: int      # pool budget (physical pages, excl. trash)
    windowed: bool      # True when every member layer is sliding-window

    @property
    def trash_page(self) -> int:
        """Physical id of this group's write-discard page."""
        return self.num_pages


@dataclasses.dataclass(frozen=True)
class LayerCacheSpec:
    """Cache layout of one decoder layer."""

    kind: str
    # PAGED_KV: logical ring width in pages (ceil(min(max_len, window)/P))
    ring_blocks: int = 0
    window: Optional[int] = None
    group: int = -1     # index into CacheSpec.groups
    # STATE: {name: (shape, logical_axes)} at batch == slots
    state: Optional[Dict[str, Tuple]] = None


@dataclasses.dataclass
class CacheSpec:
    """Shapes + logical sharding axes + kinds for a slot-batched decode
    cache, derived per-layer from ``ModelConfig``."""

    cfg: ModelConfig
    slots: int
    max_len: int          # logical per-slot token cap (widest table * P)
    page_size: int
    num_pages: int        # widest (full-attention) group's page budget
    layers: List[Optional[LayerCacheSpec]]
    groups: List[PoolGroup]
    # speculative draft length K: windowed rings carry K tokens of slack
    # so an in-flight verify step can never wrap a draft write onto a
    # token still inside an earlier query's window (serve/spec)
    spec_tokens: int = 0
    # pool storage precision: "fp32" | "int8" | "fp8_e4m3" (KV_DTYPES)
    kv_dtype: str = "fp32"

    # ------------------------------------------------------------ factory
    @classmethod
    def from_config(cls, cfg: ModelConfig, slots: int, max_len: int, *,
                    page_size: int = 8,
                    num_pages: Optional[int] = None,
                    spec_tokens: int = 0,
                    kv_dtype: str = "fp32") -> "CacheSpec":
        if cfg.cross_attention:
            raise ValueError(
                f"{cfg.name}: cross-attention cache structures (enc_kv) are "
                "not representable as slot-batched decode caches; the "
                "serving cache subsystem is decoder-only.  Whisper decodes "
                "via examples/whisper_transcribe.py's direct loop.")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}")
        if page_size & (page_size - 1):
            # fail HERE with an actionable message: a non-power-of-two
            # page used to survive until the paged-attention kernel's
            # block spec tried to tile it and died inside Pallas at
            # trace time (and the bucketed splice assumed pow2 rings)
            raise ValueError(
                f"page_size must be a power of two (kernel block specs "
                f"tile pages into the VMEM grid), got {page_size}")
        layers: List[Optional[LayerCacheSpec]] = []
        for block in cfg.blocks:
            if block.mixer in (ATTN, SHARED_ATTN):
                cap = min(max_len, block.window or max_len)
                if block.window is not None and spec_tokens:
                    # speculative slack: a verify step writes up to K
                    # drafted tokens past the newest committed one; the
                    # ring must keep window + K tokens so those writes
                    # never clobber in-window history (capped at max_len
                    # — a ring that large never wraps within budget and
                    # overlong draft writes are trash-redirected instead)
                    cap = min(max_len, block.window + spec_tokens)
                if page_size > cap:
                    raise ValueError(
                        f"page_size={page_size} exceeds a paged layer's "
                        f"ring width {cap} (min(max_len={max_len}, "
                        f"window={block.window})): one page would span "
                        "more tokens than the layer can ever hold and "
                        "the kernel block spec cannot tile it; lower "
                        "page_size or raise max_len")
                layers.append(LayerCacheSpec(
                    PAGED_KV, ring_blocks=_ceil_div(cap, page_size),
                    window=block.window))
            elif block.mixer == MAMBA2:
                layers.append(LayerCacheSpec(
                    STATE, state=mamba2.state_shapes(cfg, slots)))
            elif block.mixer == RWKV6:
                layers.append(LayerCacheSpec(
                    STATE, state=rwkv6.state_shapes(cfg, slots)))
            else:  # pragma: no cover - config validation forbids this
                raise ValueError(block.mixer)
        # ---- pool groups: one per distinct ring width.  The widest group
        # takes the shared ``num_pages`` budget knob (default: the old
        # dense layout's slots x max_len token capacity); every narrower
        # (windowed) group is sized to its window — slots x ring pages —
        # because a slot can never reference more than ``ring`` pages of
        # it.  This is what removes the old flat-pool byte overhead for
        # sliding-window layers.
        rings = sorted({ls.ring_blocks for ls in layers
                        if ls is not None and ls.kind == PAGED_KV})
        widest = rings[-1] if rings else 1
        if num_pages is None:
            num_pages = slots * widest
        groups: List[PoolGroup] = []
        for r in rings:
            windowed = all(ls.window is not None for ls in layers
                           if ls is not None and ls.kind == PAGED_KV
                           and ls.ring_blocks == r)
            budget = num_pages if r == widest else slots * r
            groups.append(PoolGroup(key=page_group_key(r), ring_blocks=r,
                                    num_pages=budget, windowed=windowed))
        gidx = {g.ring_blocks: i for i, g in enumerate(groups)}
        layers = [dataclasses.replace(ls, group=gidx[ls.ring_blocks])
                  if ls is not None and ls.kind == PAGED_KV else ls
                  for ls in layers]
        spec = cls(cfg=cfg, slots=slots, max_len=max_len,
                   page_size=page_size, num_pages=num_pages, layers=layers,
                   groups=groups, spec_tokens=spec_tokens,
                   kv_dtype=kv_dtype)
        # the compiled decode path re-derives each layer's ring width from
        # (window, widest table width, page size, spec slack) — attention.
        # paged_ring_blocks.  Verify the two formulas agree HERE so any
        # future layout change fails loudly at spec construction instead
        # of silently spliced and decoded with different ring widths
        # (wrong attention output).
        for block, ls in zip(cfg.blocks, spec.layers):
            if ls is not None and ls.kind == PAGED_KV:
                derived = attention.paged_ring_blocks(
                    block.window, spec.max_blocks, page_size, spec_tokens)
                assert derived == ls.ring_blocks, (
                    block.window, derived, ls.ring_blocks)
        return spec

    # --------------------------------------------------------- properties
    @property
    def has_paged(self) -> bool:
        return any(ls is not None and ls.kind == PAGED_KV
                   for ls in self.layers)

    @property
    def max_blocks(self) -> int:
        """Widest page-table: the widest logical ring of any paged layer."""
        widths = [ls.ring_blocks for ls in self.layers
                  if ls is not None and ls.kind == PAGED_KV]
        return max(widths) if widths else 1

    def group_of(self, key: str) -> PoolGroup:
        for g in self.groups:
            if g.key == key:
                return g
        raise KeyError(key)

    @property
    def widest_group(self) -> PoolGroup:
        return max(self.groups, key=lambda g: g.ring_blocks)

    @property
    def share_group_key(self) -> Optional[str]:
        """Pool group eligible for cross-request prefix sharing, or None.

        Sharing reuses prompt-prefix KV pages across slots, which is only
        sound when (a) every layer's prefix state lives in pages (no
        recurrent STATE layers — their prefix state is a dense per-slot
        tensor) and (b) no layer ever ring-wraps a decode write back into
        a prefix page (no sliding windows), and (c) token KV depends only
        on the token prefix (no modality frontend prefix, no zamba2
        shared-block h0 concat).  Under those conditions there is exactly
        one pool group and prompt pages are immutable once prefilled."""
        if not self.has_paged or self.cfg.frontend \
                or self.cfg.num_shared_groups:
            return None
        for ls in self.layers:
            if ls is None or ls.kind != PAGED_KV or ls.window is not None:
                return None
        assert len(self.groups) == 1
        return self.groups[0].key

    @property
    def prefix_sharing_capable(self) -> bool:
        return self.share_group_key is not None

    @property
    def trash_page(self) -> int:
        """Physical id of the widest group's write-discard page."""
        return self.widest_group.trash_page

    @property
    def quantized(self) -> bool:
        """True when K/V pages are stored 8-bit with a parallel scale pool."""
        return self.kv_dtype != "fp32"

    @property
    def pool_dtype(self):
        return kv_pool_dtype(self.kv_dtype)

    @property
    def kv_dtype_bytes(self) -> int:
        """Bytes per stored pool element (scales accounted separately)."""
        return 1 if self.quantized else 4

    def pool_shape_for(self, group: PoolGroup) -> Tuple[int, int, int, int]:
        return (group.num_pages + 1, self.page_size,
                self.cfg.num_kv_heads, self.cfg.resolved_head_dim)

    def scale_shape_for(self, group: PoolGroup) -> Tuple[int, int]:
        """Per-page, per-kv-head scale pool parallel to the page pool."""
        return (group.num_pages + 1, self.cfg.num_kv_heads)

    @property
    def pool_shape(self) -> Tuple[int, int, int, int]:
        return self.pool_shape_for(self.widest_group)

    POOL_AXES = (sh.PAGES, None, None, None)
    SCALE_AXES = (sh.PAGES, None)
    TABLE_AXES = (sh.BATCH, None)

    def blocks_needed(self, plen: int, max_new: int) -> Dict[str, int]:
        """Worst-case page-table entries a request ever touches, per pool
        group: tokens 0..plen+max_new-1, ring-wrapped at each group's
        table width.  Reserving this up-front at admission makes mid-run
        pool exhaustion impossible for admitted requests."""
        if not self.has_paged:
            return {}
        total = max(plen + max_new, 1)
        blocks = _ceil_div(total, self.page_size)
        return {g.key: min(blocks, g.ring_blocks) for g in self.groups}

    # -------------------------------------------------------------- inits
    def init_paged_cache(self, dtype=jnp.float32) -> Dict[str, Any]:
        """Zeroed paged decode cache.  Page-table entries start at each
        group's trash page, so an unadmitted slot's decode writes are
        discarded.  Quantized specs store the pools in ``pool_dtype`` and
        add per-page scale pools "ks"/"vs" (fp32; ``dtype`` still governs
        the dense STATE leaves)."""
        pool_dt = self.pool_dtype if self.quantized else dtype
        layer_caches: List[Optional[Dict]] = []
        for ls in self.layers:
            if ls is None:
                layer_caches.append(None)
            elif ls.kind == PAGED_KV:
                group = self.groups[ls.group]
                shape = self.pool_shape_for(group)
                entry = {
                    "pk": jnp.zeros(shape, pool_dt),
                    "pv": jnp.zeros(shape, pool_dt),
                }
                if self.quantized:
                    sshape = self.scale_shape_for(group)
                    # scale floor, not zero: an unwritten page dequantizes
                    # to exact zeros and never divides by zero on RMW
                    entry["ks"] = jnp.full(sshape, 1e-30, jnp.float32)
                    entry["vs"] = jnp.full(sshape, 1e-30, jnp.float32)
                layer_caches.append(entry)
            else:
                layer_caches.append({
                    k: jnp.zeros(shp, dtype)
                    for k, (shp, _axes) in ls.state.items()})
        return {
            "layers": layer_caches,
            "page_tables": {
                g.key: jnp.full((self.slots, g.ring_blocks),
                                g.trash_page, jnp.int32)
                for g in self.groups} if self.has_paged else {},
            "len": jnp.zeros((self.slots,), jnp.int32),
        }

    def init_dense_cache(self, dtype=jnp.float32) -> Dict[str, Any]:
        """Zeroed dense (pre-paging) cache: one ``max_len``-or-ring row per
        slot per attention layer.  Kept for ``ReferenceEngine`` so the
        equivalence oracle can never diverge structurally."""
        layer_caches: List[Optional[Dict]] = []
        for block, ls in zip(self.cfg.blocks, self.layers):
            if ls is None:
                layer_caches.append(None)
            elif ls.kind == PAGED_KV:
                shape, _axes = attention.init_cache_shape(
                    self.cfg, self.slots,
                    min(self.max_len, block.window or self.max_len))
                layer_caches.append({"k": jnp.zeros(shape, dtype),
                                     "v": jnp.zeros(shape, dtype)})
            else:
                layer_caches.append({
                    k: jnp.zeros(shp, dtype)
                    for k, (shp, _axes) in ls.state.items()})
        return {"layers": layer_caches,
                "len": jnp.zeros((self.slots,), jnp.int32)}

    # ---------------------------------------------------------- structure
    def structure(self) -> Dict[str, Any]:
        """Nested ``{name: (shape, logical_axes)}`` mirroring the paged
        runtime cache — the paged analogue of
        ``models/transformer.cache_structure``."""
        per_layer: List[Optional[Dict]] = []
        for ls in self.layers:
            if ls is None:
                per_layer.append(None)
            elif ls.kind == PAGED_KV:
                group = self.groups[ls.group]
                shape = self.pool_shape_for(group)
                entry = {"pk": (shape, self.POOL_AXES),
                         "pv": (shape, self.POOL_AXES)}
                if self.quantized:
                    sshape = self.scale_shape_for(group)
                    entry["ks"] = (sshape, self.SCALE_AXES)
                    entry["vs"] = (sshape, self.SCALE_AXES)
                per_layer.append(entry)
            else:
                per_layer.append(dict(ls.state))
        return {
            "layers": per_layer,
            "page_tables": {
                g.key: ((self.slots, g.ring_blocks), self.TABLE_AXES)
                for g in self.groups} if self.has_paged else {},
            "len": ((self.slots,), (sh.BATCH,)),
        }

    def shardings(self, rules: sh.Rules) -> Any:
        """NamedShardings (or None without a mesh) for the paged cache."""
        def is_leaf(x):
            return (isinstance(x, tuple) and len(x) == 2
                    and isinstance(x[0], tuple))

        return jax.tree.map(
            lambda leaf: rules.sharding_for(leaf[1], leaf[0]),
            self.structure(), is_leaf=is_leaf)

    # ------------------------------------------------------- memory stats
    def group_page_bytes(self, group: PoolGroup,
                         dtype_bytes: Optional[int] = None) -> int:
        """HBM bytes one physical page of ``group`` costs across every
        member layer (each page id backs a K and a V block per layer).
        Defaults to the spec's own pool precision; quantized pools also
        pay the per-page fp32 scale rows (one per kv head, K and V)."""
        if dtype_bytes is None:
            dtype_bytes = self.kv_dtype_bytes
        n = sum(1 for ls in self.layers
                if ls is not None and ls.kind == PAGED_KV
                and self.groups[ls.group] is group)
        per_layer = (2 * self.page_size * self.cfg.num_kv_heads
                     * self.cfg.resolved_head_dim * dtype_bytes)
        if self.quantized and dtype_bytes == self.kv_dtype_bytes:
            per_layer += 2 * self.cfg.num_kv_heads * 4   # ks/vs scale rows
        return n * per_layer

    def dense_kv_bytes(self, dtype_bytes: int = 4) -> int:
        """What the old dense layout preallocated for attention KV."""
        total = 0
        for block, ls in zip(self.cfg.blocks, self.layers):
            if ls is None or ls.kind != PAGED_KV:
                continue
            ring = min(self.max_len, block.window or self.max_len)
            total += (2 * self.slots * ring * self.cfg.num_kv_heads
                      * self.cfg.resolved_head_dim * dtype_bytes)
        return total

    def paged_kv_bytes(self, dtype_bytes: Optional[int] = None) -> int:
        return sum(g.num_pages * self.group_page_bytes(g, dtype_bytes)
                   for g in self.groups)

    def total_pages(self) -> int:
        return sum(g.num_pages for g in self.groups)

    def memory_stats(self, pages_in_use: Dict[str, int],
                     live_tokens: int) -> Dict[str, Any]:
        """Paged-cache memory telemetry for the BENCH_serve.json schema.

        ``pages_in_use`` maps group key -> leased pages (``{}`` for
        stateless archs)."""
        in_use_bytes = sum(pages_in_use.get(g.key, 0)
                           * self.group_page_bytes(g) for g in self.groups)
        dense = self.dense_kv_bytes()
        paged = self.paged_kv_bytes()
        return {
            "page_size": self.page_size,
            "num_pages": self.total_pages(),
            "pages_in_use": sum(pages_in_use.values()),
            "kv_dtype": self.kv_dtype,
            "hbm_bytes_per_live_token": (
                in_use_bytes / live_tokens if live_tokens else 0.0),
            # the trajectory metric the quantized-pool capacity claim is
            # tracked by: leased pool bytes (at stored precision, scales
            # included) per live token
            "pool_bytes_per_live_token": (
                in_use_bytes / live_tokens if live_tokens else 0.0),
            "dense_vs_paged_capacity_ratio": (
                dense / paged if paged else 1.0),
            "paged_kv_bytes": paged,
            "dense_kv_bytes": dense,
            "pool_groups": {
                g.key: {
                    "ring_blocks": g.ring_blocks,
                    "num_pages": g.num_pages,
                    "windowed": g.windowed,
                    "pages_in_use": pages_in_use.get(g.key, 0),
                } for g in self.groups},
        }


# ---------------------------------------------------------------------------
# Jit-traceable cache ops (called inside the Executor's compiled functions)
# ---------------------------------------------------------------------------

def splice_paged_layer(pool_k: jax.Array, pool_v: jax.Array,
                       pre_k: jax.Array, pre_v: jax.Array,
                       pages_row: jax.Array, start: jax.Array,
                       valid_len: jax.Array, ring_blocks: int,
                       page_size: int, trash_page: int,
                       scale_k: Optional[jax.Array] = None,
                       scale_v: Optional[jax.Array] = None) -> Tuple:
    """Write a batch-1 prefill KV ``[1, Hkv, bucket, dh]`` into the pool
    as one token-granular scatter.

    Local token ``i`` holds global position ``g = start + i``; it lands at
    page ``pages_row[(g // P) % ring_blocks]``, offset ``g % P`` — the
    same write rule decode uses.  ``start`` is 0 for a full prefill and
    the prefix-match length for a suffix prefill (prefix sharing), and
    need not be page-aligned: the scatter touches exactly the written
    offsets, so a copy-on-write page keeps its earlier tokens.  Masked
    tokens are redirected to the trash page instead of merged: pad
    positions (``i >= valid_len``, bucketed prefill) and — for windowed
    rings that wrap *within* one prefill — every token that is not the
    newest occupant of its ring slot, which keeps the scatter free of
    conflicting valid writes.

    With ``scale_k``/``scale_v`` (quantized pools, [num_pages+1, Hkv])
    the splice becomes page-granular: tokens are grouped into the logical
    pages they touch, each touched page is dequantized, overlaid, and
    re-quantized with a fresh amax scale (partial-page copy-on-write
    keeps its earlier tokens through the read-modify-write), and a
    4-tuple ``(pool_k, pool_v, scale_k, scale_v)`` is returned."""
    k = jnp.swapaxes(pre_k[0], 0, 1)   # [bucket, Hkv, dh]
    v = jnp.swapaxes(pre_v[0], 0, 1)
    bucket = k.shape[0]
    idx = jnp.arange(bucket)
    g = start + idx
    keep = idx < valid_len
    ring = ring_blocks * page_size
    if bucket > ring:   # static: only wrap-capable shapes pay the mask
        keep &= g >= start + valid_len - ring
    off = g % page_size
    if scale_k is not None:
        # page-granular quantizing RMW (see attention.rmw_quantized_pages):
        # the bucket spans at most ceil((bucket-1)/P)+1 consecutive
        # logical pages starting at start's page
        J = (bucket - 1) // page_size + 2
        base = start // page_size
        jtok = g // page_size - base                    # [bucket] in [0, J)
        lp = base + jnp.arange(J)
        page_live = jnp.zeros((J,), bool).at[jtok].max(keep)
        if J > ring_blocks:
            # ring narrower than the span: of logical pages congruent mod
            # ring_blocks only the newest occupant may be written
            page_live &= jnp.arange(J) + ring_blocks >= J
        phys = jnp.where(page_live, pages_row[lp % ring_blocks], trash_page)
        wrote = jnp.zeros((J, page_size), bool).at[jtok, off].max(keep)
        shape = (J, page_size) + k.shape[1:]
        nk = jnp.zeros(shape, jnp.float32).at[jtok, off].set(
            k.astype(jnp.float32))
        nv = jnp.zeros(shape, jnp.float32).at[jtok, off].set(
            v.astype(jnp.float32))
        pool_k, scale_k = attention.rmw_quantized_pages(
            pool_k, scale_k, phys, nk, wrote)
        pool_v, scale_v = attention.rmw_quantized_pages(
            pool_v, scale_v, phys, nv, wrote)
        return pool_k, pool_v, scale_k, scale_v
    phys = jnp.where(keep, pages_row[(g // page_size) % ring_blocks],
                     trash_page)
    pool_k = pool_k.at[phys, off].set(k.astype(pool_k.dtype))
    pool_v = pool_v.at[phys, off].set(v.astype(pool_v.dtype))
    return pool_k, pool_v


def _splice_state_leaf(big: Optional[jax.Array], small: Optional[jax.Array],
                       slot: jax.Array) -> Optional[jax.Array]:
    """Write a batch-1 recurrent-state leaf into row ``slot``."""
    if big is None or small is None:
        return big
    return jax.lax.dynamic_update_slice_in_dim(
        big, small.astype(big.dtype), slot, axis=0)


def admit_cache(spec: CacheSpec, cache: Dict, one_cache: Dict,
                slot: jax.Array, start: jax.Array, plen: jax.Array,
                rows: Dict[str, jax.Array],
                enabled: Optional[jax.Array] = None) -> Dict:
    """Jit-traceable admission: splice a batch-1 prefill cache into
    ``slot`` starting at global token position ``start`` (0 for a full
    prefill; the prefix-match length for a suffix prefill whose first
    ``start`` tokens ride on shared pages) and install its page-table
    rows (one per pool group; reserved pages padded with the trash id, so
    writes past the reservation are discarded, never aliased into a
    neighbour's pages).  ``plen`` is the request's full logical prompt
    length — the slot's ``len`` after admission regardless of how much
    prefill was skipped.

    ``enabled`` (scalar bool, optional) no-ops the whole admission when
    False: pool writes are redirected to the trash pages (``valid_len``
    forced to 0) and the table/len/state updates keep their prior values.
    The batched multi-slot admission path uses it to pad a chunk
    boundary's admissions to a fixed count, so ONE splice executable
    serves any number of simultaneous admissions.  Extra cache keys (the
    speculative draft cache) pass through untouched."""
    valid = plen - start
    if enabled is not None:
        valid = jnp.where(enabled, valid, 0)
    new_layers: List[Optional[Dict]] = []
    for ls, big, small in zip(spec.layers, cache["layers"],
                              one_cache["layers"]):
        if ls is None:
            new_layers.append(big)
        elif ls.kind == PAGED_KV:
            group = spec.groups[ls.group]
            if "ks" in big:     # quantized pool: re-quantizing splice
                pk, pv, sk, sv = splice_paged_layer(
                    big["pk"], big["pv"], small["k"], small["v"],
                    rows[group.key], start, valid, ls.ring_blocks,
                    spec.page_size, group.trash_page,
                    scale_k=big["ks"], scale_v=big["vs"])
                new_layers.append({"pk": pk, "pv": pv, "ks": sk, "vs": sv})
            else:
                pk, pv = splice_paged_layer(
                    big["pk"], big["pv"], small["k"], small["v"],
                    rows[group.key], start, valid, ls.ring_blocks,
                    spec.page_size, group.trash_page)
                new_layers.append({"pk": pk, "pv": pv})
        else:
            entry = {}
            for k in big:
                small_k = small[k]
                if enabled is not None and big[k] is not None \
                        and small_k is not None:
                    cur = jax.lax.dynamic_slice_in_dim(big[k], slot, 1, 0)
                    small_k = jnp.where(enabled,
                                        small_k.astype(big[k].dtype), cur)
                entry[k] = _splice_state_leaf(big[k], small_k, slot)
            new_layers.append(entry)
    page_tables = {}
    for k in cache["page_tables"]:
        row = rows[k][None].astype(jnp.int32)
        if enabled is not None:
            cur = jax.lax.dynamic_slice(
                cache["page_tables"][k], (slot, 0), (1, row.shape[1]))
            row = jnp.where(enabled, row, cur)
        page_tables[k] = jax.lax.dynamic_update_slice(
            cache["page_tables"][k], row, (slot, 0))
    new_len = plen[None].astype(jnp.int32)
    if enabled is not None:
        cur = jax.lax.dynamic_slice_in_dim(cache["len"], slot, 1, 0)
        new_len = jnp.where(enabled, new_len, cur)
    length = jax.lax.dynamic_update_slice_in_dim(
        cache["len"], new_len, slot, axis=0)
    return dict(cache, layers=new_layers, page_tables=page_tables,
                len=length)


def install_slot_rows(spec: CacheSpec, cache: Dict, slot: jax.Array,
                      start: jax.Array, rows: Dict[str, jax.Array],
                      enabled: Optional[jax.Array] = None) -> Dict:
    """Jit-traceable table-only admission for fused chunked prefill:
    install ``slot``'s page-table rows and rewind its ``len`` to the
    prefill cursor ``start`` (0 for a fresh prompt; the shared-prefix or
    resume boundary otherwise).  No KV is spliced — the fused chunk step
    writes prompt KV through these rows itself — so this stays a cheap
    bookkeeping dispatch.  ``enabled`` masks a padding entry exactly as
    in :func:`admit_cache`."""
    page_tables = {}
    for k in cache["page_tables"]:
        row = rows[k][None].astype(jnp.int32)
        if enabled is not None:
            cur = jax.lax.dynamic_slice(
                cache["page_tables"][k], (slot, 0), (1, row.shape[1]))
            row = jnp.where(enabled, row, cur)
        page_tables[k] = jax.lax.dynamic_update_slice(
            cache["page_tables"][k], row, (slot, 0))
    new_len = start[None].astype(jnp.int32)
    if enabled is not None:
        cur = jax.lax.dynamic_slice_in_dim(cache["len"], slot, 1, 0)
        new_len = jnp.where(enabled, new_len, cur)
    length = jax.lax.dynamic_update_slice_in_dim(
        cache["len"], new_len, slot, axis=0)
    return dict(cache, page_tables=page_tables, len=length)


def copy_shared_page(spec: CacheSpec, cache: Dict, group_key: str,
                     src: jax.Array, dst: jax.Array) -> Dict:
    """Jit-traceable copy-on-write: duplicate physical page ``src`` into
    ``dst`` in every layer pool of ``group_key``.  The scheduler invokes
    this at admission for a slot about to write into a shared page (e.g.
    a partially-matched prefix page, or the final page of a fully-matched
    prompt); the slot's table then points at the private copy, so the
    compiled decode path never observes sharing."""
    new_layers: List[Optional[Dict]] = []
    for ls, big in zip(spec.layers, cache["layers"]):
        if (ls is not None and ls.kind == PAGED_KV
                and spec.groups[ls.group].key == group_key):
            entry = {
                "pk": big["pk"].at[dst].set(big["pk"][src]),
                "pv": big["pv"].at[dst].set(big["pv"][src]),
            }
            if "ks" in big:     # quantized pool: the copy carries scales
                entry["ks"] = big["ks"].at[dst].set(big["ks"][src])
                entry["vs"] = big["vs"].at[dst].set(big["vs"][src])
            new_layers.append(entry)
        else:
            new_layers.append(big)
    return dict(cache, layers=new_layers)


def free_slot_cache(spec: CacheSpec, cache: Dict, slot: jax.Array) -> Dict:
    """Jit-traceable eviction: point the freed slot's page-table rows at
    each group's trash page and zero its length.  Its physical pages go
    back to the host-side refcounted pools (``serve/scheduler``); after
    this update the idle slot's dead decode writes land on trash pages,
    so exclusively-owned pages can be re-leased immediately without
    corruption — and shared pages stay valid for their other referents."""
    page_tables = {}
    for g in spec.groups:
        row = jnp.full((1, g.ring_blocks), g.trash_page, jnp.int32)
        page_tables[g.key] = jax.lax.dynamic_update_slice(
            cache["page_tables"][g.key], row, (slot, 0))
    length = jax.lax.dynamic_update_slice_in_dim(
        cache["len"], jnp.zeros((1,), jnp.int32), slot, axis=0)
    return dict(cache, page_tables=page_tables, len=length)


def empty_batch_cache(cfg: ModelConfig, slots: int, max_len: int):
    """Zeroed dense slot-batched decode cache (``ReferenceEngine``'s
    layout).  Cross-attention structures are rejected by ``CacheSpec``
    construction with a clear error — previously this silently
    ``pop``-ed the ``enc_kv`` entry and served garbage cross-attention."""
    return CacheSpec.from_config(cfg, slots, max_len).init_dense_cache()
