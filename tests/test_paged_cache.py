"""Paged decode-cache subsystem: CacheSpec layout, page-table splice,
slot lifecycle (eviction / re-admission / FIFO fairness), page-pool
backpressure, long-output capacity beyond the dense max_len ceiling, and
data-axis sharding specs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, reduced
from repro.launch.mesh import make_serving_mesh
from repro.models import forward_dense_logits, model_defs
from repro.models import module as m
from repro.models.attention import ring_token_positions, ring_valid
from repro.parallel import sharding as sh
from repro.serve.cache import PAGED_KV, STATE, CacheSpec
from repro.serve.engine import Engine, Request
from repro.serve.scheduler import (PagePool, PagePoolExhausted,  # noqa: F401
                                   RequestStatus, Scheduler)


def _model(arch, **kw):
    cfg = reduced(get_config(arch), **kw)
    params = m.init_params(model_defs(cfg), jax.random.PRNGKey(0),
                           jnp.float32)
    return cfg, params


# ---------------------------------------------------------------------------
# CacheSpec construction
# ---------------------------------------------------------------------------

def test_cachespec_kinds_per_layer():
    cfg, _ = _model("zamba2-7b")    # mamba2 backbone + shared attention
    spec = CacheSpec.from_config(cfg, slots=2, max_len=64, page_size=8)
    kinds = [ls.kind for ls in spec.layers]
    assert PAGED_KV in kinds and STATE in kinds
    assert spec.has_paged
    # the widest group's default budget covers slots x its ring tokens
    # (zamba2's shared attention is windowed, so its only paged group is
    # window-sized — no longer inflated to slots x max_len)
    widest = spec.widest_group
    assert spec.num_pages == 2 * widest.ring_blocks
    assert spec.trash_page == spec.num_pages
    assert spec.pool_shape[0] == spec.num_pages + 1


def test_cachespec_windowed_ring_blocks():
    cfg, _ = _model("gemma2-2b")    # alternating window=16 / global layers
    spec = CacheSpec.from_config(cfg, slots=1, max_len=64, page_size=8)
    rings = {ls.window: ls.ring_blocks for ls in spec.layers
             if ls is not None and ls.kind == PAGED_KV}
    assert rings[16] == 2           # ceil(16/8): windowed layers ring early
    assert rings[None] == 8         # ceil(64/8): full layers span max_len
    assert spec.max_blocks == 8


def test_cachespec_per_layer_pool_budgets():
    """Windowed layers get their own window-sized pool (per-layer page-id
    remapping) instead of paying the full-attention group's budget; the
    dense-vs-paged byte ratio is therefore 1.0 for windowed archs."""
    cfg, _ = _model("gemma2-2b")
    spec = CacheSpec.from_config(cfg, slots=2, max_len=64, page_size=8)
    by_key = {g.key: g for g in spec.groups}
    assert set(by_key) == {"ring2", "ring8"}
    assert by_key["ring2"].windowed and not by_key["ring8"].windowed
    assert by_key["ring2"].num_pages == 2 * 2       # slots x ring (window)
    assert by_key["ring8"].num_pages == 2 * 8       # slots x max_len / P
    # every paged layer points at the group matching its ring
    for ls in spec.layers:
        if ls is not None and ls.kind == PAGED_KV:
            assert spec.groups[ls.group].ring_blocks == ls.ring_blocks
    stats = spec.memory_stats({k: 0 for k in by_key}, 0)
    assert stats["dense_vs_paged_capacity_ratio"] == 1.0
    assert stats["num_pages"] == 4 + 16
    # tables are per group, trash ids are per group
    cache = spec.init_paged_cache()
    assert cache["page_tables"]["ring2"].shape == (2, 2)
    assert cache["page_tables"]["ring8"].shape == (2, 8)
    assert int(cache["page_tables"]["ring2"][0, 0]) == by_key["ring2"].num_pages
    assert int(cache["page_tables"]["ring8"][0, 0]) == by_key["ring8"].num_pages


def test_cachespec_rejects_cross_attention():
    """The old empty_batch_cache silently pop()-ed enc_kv; now the spec
    refuses the structure outright with an actionable error."""
    cfg, _ = _model("whisper-medium")
    with pytest.raises(ValueError, match="cross-attention"):
        CacheSpec.from_config(cfg, slots=2, max_len=32)


def test_cachespec_blocks_needed_caps_at_table_width():
    cfg, _ = _model("rwkv6-7b")
    spec = CacheSpec.from_config(cfg, 2, 64)
    assert not spec.has_paged and spec.blocks_needed(100, 100) == {}
    cfg2, _ = _model("internlm2-1.8b")
    spec2 = CacheSpec.from_config(cfg2, 2, 64, page_size=8)
    key = spec2.widest_group.key
    assert spec2.blocks_needed(3, 4) == {key: 1}
    assert spec2.blocks_needed(0, 1) == {key: 1}   # empty prompt still pages
    assert spec2.blocks_needed(60, 1000) == {key: spec2.max_blocks}
    # per-group caps: windowed groups reserve at most their ring
    cfg3, _ = _model("gemma2-2b")
    spec3 = CacheSpec.from_config(cfg3, 2, 64, page_size=8)
    assert spec3.blocks_needed(60, 1000) == {"ring2": 2, "ring8": 8}


# ---------------------------------------------------------------------------
# Ring position math (shared by splice and paged decode attention)
# ---------------------------------------------------------------------------

def test_ring_token_positions_and_validity():
    # ring of 8, current token t=10 (cache_len=11): slots hold tokens 3..10
    u = np.asarray(ring_token_positions(jnp.asarray([11]), 8))[0]
    assert sorted(u.tolist()) == list(range(3, 11))
    assert u[10 % 8] == 10
    # before wrap (t=2): slots 3.. were never written -> negative
    u2 = np.asarray(ring_token_positions(jnp.asarray([3]), 8))[0]
    assert (u2[:3] == [0, 1, 2]).all() and (u2[3:] < 0).all()
    # window mask hides ring-retained tokens older than the window
    v = np.asarray(ring_valid(jnp.asarray([11]), 8, window=4))[0]
    assert v.sum() == 4
    u = np.asarray(ring_token_positions(jnp.asarray([11]), 8))[0]
    assert (u[v] > 10 - 4).all()


# ---------------------------------------------------------------------------
# Slot lifecycle
# ---------------------------------------------------------------------------

def test_eviction_returns_pages_to_pool():
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=2, max_len=64, page_size=8)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=4))
    assert eng.scheduler.pages_in_use == 0
    done = eng.run()
    assert len(done) == 3
    # every lease was released on finish; peak shows the pool was used
    assert eng.scheduler.pages_in_use == 0
    assert eng.scheduler.peak_pages_in_use >= 2
    stats = eng.memory_stats()
    assert stats["pages_in_use"] == 0 and stats["num_pages"] == 16


def test_readmission_into_freed_slot_mid_run():
    """A short request finishes, its slot and pages are re-leased to a
    queued request mid-run, and the long-running neighbour is unaffected
    (its tokens match a solo run) — freed pages were not corrupted."""
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=2, max_len=64, page_size=8)
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=20))  # long
    eng.submit(Request(rid=1, prompt=[4, 5], max_new_tokens=3))      # short
    eng.submit(Request(rid=2, prompt=[6, 7], max_new_tokens=3))      # reuses
    done = {r.rid: r for r in eng.run()}
    assert len(done) == 3
    solo = Engine(cfg, params, slots=2, max_len=64, page_size=8)
    solo.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=20))
    (s,) = solo.run()
    assert done[0].out_tokens == s.out_tokens


def test_fifo_queue_fairness_no_jumping():
    """Head-of-line backpressure: when the queue head's page reservation
    does not fit, a smaller later request must NOT be admitted around it."""
    cfg, _ = _model("internlm2-1.8b")
    spec = CacheSpec.from_config(cfg, slots=2, max_len=64, page_size=8,
                                 num_pages=8)
    sched = Scheduler(spec)
    r0 = Request(rid=0, prompt=[1] * 8, max_new_tokens=24)    # 4 pages
    r1 = Request(rid=1, prompt=[1] * 8, max_new_tokens=40)    # 6 pages
    r2 = Request(rid=2, prompt=[1], max_new_tokens=2)         # 1 page
    for r in (r0, r1, r2):
        sched.submit(r)
    admitted = list(sched.admissions([0, 1]))
    # r0 fits (4 <= 8); r1 needs 6 > 4 free -> head-of-line blocks r2 too
    assert [a.req.rid for a in admitted] == [0]
    assert [r.rid for r in sched.queue] == [1, 2]
    sched.release(admitted[0].slot)
    admitted2 = list(sched.admissions([0, 1]))
    assert [a.req.rid for a in admitted2] == [1, 2]


def test_fifo_completion_order_end_to_end():
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=1, max_len=64, page_size=8,
                 num_pages=8)
    eng.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=40))  # 6 pages
    eng.submit(Request(rid=1, prompt=[3], max_new_tokens=2))
    eng.submit(Request(rid=2, prompt=[4], max_new_tokens=2))
    done = eng.run()
    assert [r.rid for r in done] == [0, 1, 2]


def test_page_pool_exhaustion_is_clean_backpressure():
    """A request that can never fit is shed with a typed "infeasible"
    RequestRejected at submit() (no exception leaks to the caller);
    nothing is admitted and in-flight neighbours are unharmed."""
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=2, max_len=64, page_size=8,
                 num_pages=4)   # 32-token pool
    assert eng.submit(
        Request(rid=0, prompt=[1, 2, 3], max_new_tokens=8)) is None
    doomed = Request(rid=1, prompt=[1] * 30, max_new_tokens=16)
    rej = eng.submit(doomed)
    assert rej is not None and rej.kind == "infeasible"
    assert "pages" in rej.reason
    assert doomed.status == RequestStatus.REJECTED and doomed.done
    assert eng.fault_stats()["rejected_infeasible"] == 1
    assert len(eng.queue) == 1
    (r,) = eng.run()
    assert r.rid == 0 and len(r.out_tokens) == 8
    solo = Engine(cfg, params, slots=2, max_len=64, page_size=8)
    solo.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=8))
    (s,) = solo.run()
    assert r.out_tokens == s.out_tokens


def test_page_pool_allocator_invariants():
    pool = PagePool(4)
    a = pool.alloc(3)
    assert sorted(a) == [0, 1, 2] and pool.in_use == 3
    assert pool.alloc(2) is None           # backpressure, not partial
    assert pool.in_use == 3                # failed alloc leaks nothing
    pool.free(a)
    assert pool.free_pages == 4 and pool.peak_in_use == 3


# ---------------------------------------------------------------------------
# Multi-token cache appends (speculative verify writes K+1 tokens/step)
# ---------------------------------------------------------------------------

def _multi_vs_sequential(page_size, nb, num_pages, cache_len0, s, window):
    """Oracle: an S-token paged_decode_step must equal S sequential
    single-token steps — same pool contents, same per-position attention
    outputs."""
    from repro.models.attention import paged_decode_step

    b, h, hkv, dh = 2, 4, 2, 16
    key = jax.random.PRNGKey(nb + s + (window or 0))
    q = jax.random.normal(key, (b, s, h, dh)) * 0.5
    kk = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, dh)) * 0.5
    vv = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, dh))
    pool = jax.random.normal(jax.random.fold_in(key, 3),
                             (num_pages + 1, page_size, hkv, dh)) * 0.3
    rs = np.random.RandomState(3)
    pt = jnp.asarray(np.stack([rs.permutation(num_pages)[:nb]
                               for _ in range(b)]), jnp.int32)
    cl0 = jnp.asarray(cache_len0, jnp.int32)

    multi, mnew = paged_decode_step(
        q, kk, vv, {"pk": pool, "pv": pool, "pt": pt}, cl0 + s,
        window=window, softcap=None)
    seq_out = []
    cur = {"pk": pool, "pv": pool}
    for i in range(s):
        o, cur = paged_decode_step(
            q[:, i:i + 1], kk[:, i:i + 1], vv[:, i:i + 1],
            {"pk": cur["pk"], "pv": cur["pv"], "pt": pt}, cl0 + i + 1,
            window=window, softcap=None)
        seq_out.append(o[:, 0])
    np.testing.assert_allclose(np.asarray(mnew["pk"]),
                               np.asarray(cur["pk"]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(mnew["pv"]),
                               np.asarray(cur["pv"]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(multi),
                               np.asarray(jnp.stack(seq_out, 1)),
                               rtol=2e-4, atol=2e-4)


def test_multi_token_scatter_across_page_boundary():
    """K-token append straddling a page boundary: positions 6..10 with
    page_size 8 span pages 0 and 1."""
    _multi_vs_sequential(page_size=8, nb=4, num_pages=12,
                         cache_len0=[6, 13], s=5, window=None)


def test_multi_token_scatter_into_ring_wrapped_window():
    """K-token append into a windowed ring that wraps mid-append.  The
    ring carries spec slack (ring tokens >= window + S - 1, the
    CacheSpec sizing), so wrapped writes only land on ring slots whose
    tokens are already outside every query's window.  Oracles: a numpy
    emulation of the token-position write rule for the pool contents,
    and the gather-then-attend ``paged_attention_ref`` for the output."""
    from repro.kernels.paged_attention import paged_attention_ref
    from repro.models.attention import paged_decode_step

    b, h, hkv, dh, P, nb, window, s = 2, 4, 2, 16, 4, 4, 12, 5
    ring = P * nb                                  # 16 >= window + s - 1
    key = jax.random.PRNGKey(9)
    q = jax.random.normal(key, (b, s, h, dh)) * 0.5
    kk = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, dh))
    vv = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, dh))
    pool = jax.random.normal(jax.random.fold_in(key, 3),
                             (11, P, hkv, dh)) * 0.3
    rs = np.random.RandomState(5)
    pt = jnp.asarray(np.stack([rs.permutation(10)[:nb]
                               for _ in range(b)]), jnp.int32)
    cl = jnp.asarray([14 + s, 29 + s], jnp.int32)  # second slot wraps
    out, new = paged_decode_step(
        q, kk, vv, {"pk": pool, "pv": pool, "pt": pt}, cl,
        window=window, softcap=None)
    want_k = np.asarray(pool).copy()
    for bi in range(b):
        for i in range(s):
            g = int(cl[bi]) - s + i                # absolute position
            want_k[int(pt[bi, (g // P) % nb]), g % P] = np.asarray(kk)[bi, i]
    np.testing.assert_allclose(np.asarray(new["pk"]), want_k, atol=1e-6)
    want = paged_attention_ref(q, new["pk"], new["pv"], pt, cl,
                               window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_multi_token_write_clamped_outside_nonwrapping_ring():
    """A verify step whose draft positions run past a NON-wrapping ring
    (full attention) must discard those writes instead of mod-wrapping
    them onto page 0 — under prefix sharing that page may belong to
    other slots."""
    from repro.models.attention import paged_decode_step

    b, h, hkv, dh, P, nb = 1, 2, 1, 8, 4, 2       # ring = 8 tokens
    q = jnp.zeros((b, 3, h, dh))
    kk = jnp.ones((b, 3, hkv, dh))
    vv = jnp.ones((b, 3, hkv, dh))
    pool = jnp.zeros((4, P, hkv, dh))
    pt = jnp.asarray([[0, 1]], jnp.int32)
    # positions 6, 7, 8: the last is beyond the 8-token ring
    _, new = paged_decode_step(
        q, kk, vv, {"pk": pool, "pv": pool, "pt": pt},
        jnp.asarray([9], jnp.int32), window=None, softcap=None)
    pk = np.asarray(new["pk"])
    assert pk[1, 2:].sum() == 2 * hkv * dh        # positions 6,7 written
    assert pk[0].sum() == 0                       # page 0 NOT wrapped into
    assert pk[3].sum() > 0                        # overflow went to trash


def test_multi_token_append_into_cow_shared_page_rolls_back():
    """Engine-level: a slot admitted onto a partially-matched shared page
    copies it exactly once (CoW), drafted writes then land in the
    private copy, and rejected-draft rollback leaves refcounts intact —
    after the run every page reference is the radix tree's own."""
    from repro.serve.spec import SpecConfig

    cfg, params = _model("internlm2-1.8b")
    prefix = [(3 * j) % 200 + 1 for j in range(16)]
    tail = [50, 51, 52, 53, 54, 55, 56, 57]
    # legacy prefill path: same-wave sharing (rid=1 attaches rid=0's
    # pages while both admit together) needs eager radix indexing, which
    # fused chunked prefill defers to prefill completion
    eng = Engine(cfg, params, slots=2, max_len=64, page_size=8,
                 spec=SpecConfig(draft="ngram", k=4),
                 chunked_prefill=False)
    eng.submit(Request(rid=0, prompt=prefix + tail, max_new_tokens=6))
    eng.submit(Request(rid=1, prompt=prefix + tail[:3] + [99],
                       max_new_tokens=6))
    done = {r.rid: r for r in eng.run()}
    assert len(done) == 2
    ps = eng.prefix_stats()
    assert ps["cow_copies"] == 1                  # the copy fired ONCE
    # solo oracle: the CoW'd slot's output is unaffected by sharing +
    # speculative rollback
    solo = Engine(cfg, params, slots=2, max_len=64, page_size=8,
                  spec=SpecConfig(draft="ngram", k=4),
                  chunked_prefill=False)
    solo.submit(Request(rid=1, prompt=prefix + tail[:3] + [99],
                        max_new_tokens=6))
    (s,) = solo.run()
    assert done[1].out_tokens == s.out_tokens
    # every slot lease was released; only the radix tree holds pages, at
    # refcount 1 each
    sched = eng.scheduler
    assert sched.pages_in_use == sched.radix.node_count
    pool = sched.pools[sched.share_key]
    for leaf in sched.radix._leaves():
        assert pool.refcount(leaf.page) == 1


# ---------------------------------------------------------------------------
# Capacity: paged lifts the per-slot dense ceiling at equal memory
# ---------------------------------------------------------------------------

def test_output_exceeds_dense_max_len_at_equal_memory():
    """Old dense layout: 2 slots x 32 tokens.  Same total budget as pages
    (8 pages x 8 tokens) serves ONE request of 56 tokens — longer than any
    single dense slot could ever hold — and it still matches teacher
    forcing.  The queued second request (3-page reservation vs 1 free)
    back-pressures mid-run, then completes after the long one evicts."""
    dense_max_len = 32
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=2, max_len=64, page_size=8,
                 num_pages=2 * dense_max_len // 8)   # equal slots x max_len
    n_new = 51                                        # 5 + 51 = 56 tokens
    eng.submit(Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new_tokens=n_new))
    eng.submit(Request(rid=1, prompt=[2, 7], max_new_tokens=20))
    eng.step()
    # r0 reserved 7 of 8 pages; r1 needs 3 -> engine-level backpressure
    assert [r.rid for r in eng.queue] == [1]
    assert eng.scheduler.pages_in_use == 7
    done = {r.rid: r for r in eng.run(max_steps=10_000)}
    assert len(done) == 2
    r = done[0]
    assert len(r.prompt) + len(r.out_tokens) == 56 > dense_max_len
    full = r.prompt + r.out_tokens
    dense = jax.jit(lambda p, b: forward_dense_logits(p, cfg, b))(
        params, {"tokens": jnp.asarray([full], jnp.int32)})
    for i, tok in enumerate(r.out_tokens):
        pos = len(r.prompt) - 1 + i
        assert int(jnp.argmax(dense[0, pos])) == tok, f"diverged at {i}"
    assert len(done[1].out_tokens) == 20


# ---------------------------------------------------------------------------
# Sharding: CacheSpec threads Rules onto the data mesh axis
# ---------------------------------------------------------------------------

def test_cachespec_data_axis_sharding_specs():
    cfg, _ = _model("internlm2-1.8b")
    spec = CacheSpec.from_config(cfg, slots=4, max_len=64, page_size=8)
    rules = sh.Rules(table={sh.BATCH: "data", sh.PAGES: "data"})
    # slot batch and every group's page pool shard over the data mesh axis
    assert rules.spec_for(spec.TABLE_AXES) == P("data")
    assert rules.spec_for(spec.POOL_AXES) == P("data")
    struct = spec.structure()
    key = spec.widest_group.key
    assert struct["page_tables"][key][0] == (4, spec.max_blocks)
    assert struct["len"][1] == (sh.BATCH,)
    # shardings() is a full-tree map; without a mesh it yields None leaves
    shardings = spec.shardings(rules)
    leaves = jax.tree.leaves(shardings)
    assert leaves == []         # mesh-less Rules -> no NamedShardings
    # multi-group spec: each group's table/pool carries its own shapes
    cfg2, _ = _model("gemma2-2b")
    spec2 = CacheSpec.from_config(cfg2, slots=4, max_len=64, page_size=8)
    struct2 = spec2.structure()
    assert struct2["page_tables"]["ring2"][0] == (4, 2)
    assert struct2["page_tables"]["ring8"][0] == (4, 8)


def test_engine_accepts_rules_single_device():
    """rules wiring is a no-op on one device but must not change tokens."""
    mesh = make_serving_mesh(1)
    rules = sh.Rules(table={sh.BATCH: "data", sh.PAGES: "data"}, mesh=mesh)
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=2, max_len=64, rules=rules)
    eng.submit(Request(rid=0, prompt=[5, 6, 7], max_new_tokens=6))
    (r,) = eng.run()
    plain = Engine(cfg, params, slots=2, max_len=64)
    plain.submit(Request(rid=0, prompt=[5, 6, 7], max_new_tokens=6))
    (p,) = plain.run()
    assert r.out_tokens == p.out_tokens


# ------------------------------------------------------ quantized KV pools
def _supported_qdtypes():
    from repro.serve.cache import KV_DTYPES

    return [d for d in KV_DTYPES if d != "fp32"]


@pytest.mark.parametrize("page_size", [4, 8, 16])
@pytest.mark.parametrize("hkv", [1, 2, 4])
def test_quantize_roundtrip_bounded_error(page_size, hkv):
    """Property sweep: per-(page, kv-head) symmetric quantization across
    page sizes and head counts must reconstruct within one quantization
    step of that page's amax — including tiny (1e-20) and huge (1e8)
    amax pages and exactly-zero pages (which must come back exactly 0,
    never NaN from a zero scale)."""
    from repro.models.attention import dequantize_pages, quantize_pages
    from repro.serve.cache import kv_pool_dtype

    npg, dh = 6, 8
    rng = np.random.RandomState(page_size * 10 + hkv)
    x = jnp.asarray(rng.randn(npg, page_size, hkv, dh).astype(np.float32))
    x = x.at[1].multiply(1e-20)          # tiny amax
    x = x.at[2].multiply(1e8)            # huge amax
    x = x.at[3].set(0.0)                 # zero page -> zero scale floor
    for kv_dtype in _supported_qdtypes():
        qmax = {"int8": 127.0, "fp8_e4m3": 448.0}[kv_dtype]
        q, scale = quantize_pages(x, kv_pool_dtype(kv_dtype))
        y = dequantize_pages(q, scale)
        assert not np.any(np.isnan(np.asarray(y))), kv_dtype
        np.testing.assert_array_equal(np.asarray(y[3]), 0.0)
        amax = np.max(np.abs(np.asarray(x)), axis=(1, 3))   # [npg, hkv]
        # int8: uniform grid, error <= amax/qmax per (page, head).
        # fp8_e4m3: 3 mantissa bits -> relative error <= 1/16 of amax
        step = amax / qmax if kv_dtype == "int8" else amax / 16.0
        err = np.max(np.abs(np.asarray(y - x)), axis=(1, 3))
        assert np.all(err <= step + 1e-30), (kv_dtype, err, step)


def test_quantize_scale_shape_and_trash_invariance():
    """Scales are per (page, kv head); quantizing must not mix pages —
    overwriting one page (the trash row) leaves every other page's
    quantized block and scale bit-identical."""
    from repro.models.attention import quantize_pages

    npg, P, hkv, dh = 5, 4, 2, 8
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(npg, P, hkv, dh).astype(np.float32))
    q1, s1 = quantize_pages(x, jnp.int8)
    assert s1.shape == (npg, hkv)
    q2, s2 = quantize_pages(x.at[npg - 1].set(1e6), jnp.int8)
    np.testing.assert_array_equal(np.asarray(q1[:-1]), np.asarray(q2[:-1]))
    np.testing.assert_array_equal(np.asarray(s1[:-1]), np.asarray(s2[:-1]))


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8_e4m3"])
def test_quantized_splice_parity_with_fp32(kv_dtype):
    """admit_cache on a quantized spec must land the same KV (within
    quantization error) as the fp32 spec — full admission and the
    partial-page read-modify-write suffix admission (start mid-page
    re-quantizes the boundary page without losing its earlier tokens).
    The trash page is excluded: fp32 scatters masked tokens there, the
    quantized RMW zeros it; both are write-discard garbage."""
    from repro.models.attention import dequantize_pages
    from repro.serve import cache as cm

    cfg, _ = _model("internlm2-1.8b")
    spec32 = CacheSpec.from_config(cfg, 2, 64, page_size=8)
    spec8 = CacheSpec.from_config(cfg, 2, 64, page_size=8,
                                  kv_dtype=kv_dtype)
    rows = {g.key: jnp.arange(1, g.ring_blocks + 1, dtype=jnp.int32)
            for g in spec32.groups}

    def one_cache(seed):
        r = np.random.RandomState(seed)
        layers = []
        for entry in spec32.init_paged_cache()["layers"]:
            if entry is not None and "pk" in entry:
                hkv, dh = entry["pk"].shape[2], entry["pk"].shape[3]
                layers.append({
                    "k": jnp.asarray(r.randn(1, hkv, 16, dh)
                                     .astype(np.float32)),
                    "v": jnp.asarray(r.randn(1, hkv, 16, dh)
                                     .astype(np.float32))})
            else:
                layers.append(entry)
        return {"layers": layers}

    def worst_err(Ca, Cb):
        worst = 0.0
        for l32, l8 in zip(Ca["layers"], Cb["layers"]):
            if l32 is None or "pk" not in l32:
                continue
            trash = l32["pk"].shape[0] - 1
            for pool, sc, ref in (("pk", "ks", "pk"), ("pv", "vs", "pv")):
                deq = dequantize_pages(l8[pool], l8[sc])[:trash]
                worst = max(worst, float(jnp.max(jnp.abs(
                    deq - l32[ref][:trash]))))
        return worst

    C32, C8 = spec32.init_paged_cache(), spec8.init_paged_cache()
    args = (jnp.int32(0), jnp.int32(0), jnp.int32(13), rows)
    C32 = cm.admit_cache(spec32, C32, one_cache(0), *args)
    C8 = cm.admit_cache(spec8, C8, one_cache(0), *args)
    tol = 0.05 if kv_dtype == "int8" else 0.3
    assert worst_err(C32, C8) < tol

    # suffix admission starting mid-page: the boundary page is RMW
    # re-quantized (earlier tokens dequantized, overlaid, re-scaled)
    args2 = (jnp.int32(0), jnp.int32(13), jnp.int32(24), rows)
    C32 = cm.admit_cache(spec32, C32, one_cache(7), *args2)
    C8 = cm.admit_cache(spec8, C8, one_cache(7), *args2)
    assert worst_err(C32, C8) < tol


def test_quantized_copy_shared_page_copies_scales():
    """CoW page copies on a quantized spec must carry the scale rows:
    a copied page dequantizes identically to its source."""
    from repro.models.attention import dequantize_pages
    from repro.serve import cache as cm

    cfg, _ = _model("internlm2-1.8b")
    spec = CacheSpec.from_config(cfg, 2, 64, page_size=8, kv_dtype="int8")
    C = spec.init_paged_cache()
    rng = np.random.RandomState(3)
    for entry in C["layers"]:
        if entry is None or "pk" not in entry:
            continue
        shape = entry["pk"].shape
        entry["pk"] = jnp.asarray(
            rng.randint(-127, 128, size=shape).astype(np.int8))
        entry["ks"] = jnp.asarray(
            rng.rand(*entry["ks"].shape).astype(np.float32) + 0.01)
    key = max(spec.groups, key=lambda g: g.ring_blocks).key
    C2 = cm.copy_shared_page(spec, C, key, jnp.int32(1), jnp.int32(4))
    for entry in C2["layers"]:
        if entry is None or "pk" not in entry:
            continue
        src = dequantize_pages(entry["pk"][1][None], entry["ks"][1][None])
        dst = dequantize_pages(entry["pk"][4][None], entry["ks"][4][None])
        np.testing.assert_array_equal(np.asarray(src), np.asarray(dst))


def test_quantized_spec_memory_accounting():
    """8-bit pools cost ~1/4 the fp32 pool bytes (+ scale rows), and the
    capacity ratio vs the dense fp32 layout reflects it — the >=1.8x
    concurrent-slots claim rests on this accounting."""
    cfg, _ = _model("internlm2-1.8b")
    s32 = CacheSpec.from_config(cfg, 4, 64, page_size=8)
    s8 = CacheSpec.from_config(cfg, 4, 64, page_size=8, kv_dtype="int8")
    assert s8.paged_kv_bytes() < s32.paged_kv_bytes() / 2
    m32 = s32.memory_stats({}, 0)
    m8 = s8.memory_stats({}, 0)
    assert m8["kv_dtype"] == "int8" and m32["kv_dtype"] == "fp32"
    assert (m8["dense_vs_paged_capacity_ratio"]
            >= 1.8 * m32["dense_vs_paged_capacity_ratio"])
    # fp32-width accounting of the same spec matches the fp32 spec's
    # pools exactly (scale rows only exist at stored precision)
    assert s8.paged_kv_bytes(4) == s32.paged_kv_bytes()


def test_engine_kv_dtype_validation_and_fallback():
    """Engine(kv_dtype=...): unknown names raise; 'auto' is fp32; every
    8-bit dtype is served as requested — there is no silent fallback to
    fp32 pools."""
    cfg, params = _model("internlm2-1.8b")
    with pytest.raises(ValueError, match="kv_dtype"):
        Engine(cfg, params, slots=2, max_len=64, kv_dtype="int4")
    eng = Engine(cfg, params, slots=2, max_len=64, kv_dtype="auto")
    assert eng.kv_dtype == "fp32" and eng.spec.kv_dtype == "fp32"
    for kv_dtype in _supported_qdtypes():
        eng8 = Engine(cfg, params, slots=2, max_len=64, kv_dtype=kv_dtype)
        assert eng8.kv_dtype == kv_dtype and eng8.spec.quantized
        stats = eng8.memory_stats()
        assert stats["kv_dtype"] == kv_dtype
        assert "pool_bytes_per_live_token" in stats
        assert "peak_live_slots" in stats
