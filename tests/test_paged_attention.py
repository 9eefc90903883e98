"""Paged decode-attention: kernel / XLA-lowering / oracle parity across
page sizes, ring widths, GQA ratios, un-aligned offsets, and trash-page
masking — plus engine-level token parity and the CacheSpec page-size
validation.

The Pallas-kernel tests run the kernel in interpret mode on CPU;
``tests/test_tpu_compile.py`` compiles it for a v5e chip at real widths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import (live_blocks,
                                           paged_attention_ref,
                                           paged_decode_attention,
                                           pool_attention_xla)

KEY = jax.random.PRNGKey(11)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _case(b, h, hkv, dh, page_size, nb, num_pages, seed=0, trash_tail=0):
    """Random pool + *valid* tables: distinct non-trash pages per row
    (the scheduler invariant the pool-wide lowering relies on), with an
    optional all-trash tail on row 0."""
    k = jax.random.fold_in(KEY, seed)
    q = jax.random.normal(k, (b, h, dh)) * 0.5
    pool_k = jax.random.normal(jax.random.fold_in(k, 1),
                               (num_pages + 1, page_size, hkv, dh)) * 0.5
    pool_v = jax.random.normal(jax.random.fold_in(k, 2),
                               (num_pages + 1, page_size, hkv, dh))
    rs = np.random.RandomState(seed)
    pt = np.stack([rs.permutation(num_pages)[:nb] for _ in range(b)])
    if trash_tail:
        pt[0, -trash_tail:] = num_pages
    return q, pool_k, pool_v, jnp.asarray(pt, jnp.int32)


@pytest.mark.parametrize("page_size,nb", [(4, 4), (8, 8), (16, 2)])
@pytest.mark.parametrize("h,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("mode", ["full", "window", "softcap"])
def test_kernel_vs_ref(page_size, nb, h, hkv, mode):
    kw = {"full": {},
          "window": {"window": 3 * page_size},
          "softcap": {"softcap": 20.0}}[mode]
    ring = page_size * nb
    q, pk, pv, pt = _case(3, h, hkv, 16, page_size, nb, 4 * nb,
                          seed=nb + h)
    # un-aligned offsets on purpose: mid-page, page-boundary, wrapped
    cl = jnp.asarray([ring - 3, 1 + page_size, 2 * ring + 5], jnp.int32)
    got = paged_decode_attention(q, pk, pv, pt, cl,
                                 interpret=_interpret(), **kw)
    want = paged_attention_ref(q, pk, pv, pt, cl, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("page_size,nb", [(4, 4), (8, 8), (16, 2)])
@pytest.mark.parametrize("h,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("mode", ["full", "window", "softcap"])
def test_pool_lowering_vs_ref(page_size, nb, h, hkv, mode):
    """The gather-free XLA lowering must match the gather oracle on the
    same sweep the kernel runs (it is the non-TPU serving path)."""
    kw = {"full": {},
          "window": {"window": 3 * page_size},
          "softcap": {"softcap": 20.0}}[mode]
    ring = page_size * nb
    q, pk, pv, pt = _case(3, h, hkv, 16, page_size, nb, 4 * nb,
                          seed=50 + nb + h)
    cl = jnp.asarray([ring - 3, 1 + page_size, 2 * ring + 5], jnp.int32)
    got = pool_attention_xla(q, pk, pv, pt, cl, **kw)
    want = paged_attention_ref(q, pk, pv, pt, cl, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_unaligned_suffix_offsets():
    """Every cache_len in a full ring sweep, page-aligned or not."""
    page_size, nb = 4, 4
    q, pk, pv, pt = _case(1, 4, 2, 16, page_size, nb, 3 * nb, seed=7)
    for cl_val in range(1, 2 * page_size * nb + 1):
        cl = jnp.asarray([cl_val], jnp.int32)
        got = paged_decode_attention(q, pk, pv, pt, cl,
                                     interpret=_interpret())
        want = paged_attention_ref(q, pk, pv, pt, cl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4, err_msg=str(cl_val))


def test_trash_page_masked():
    """Table entries pointing at the trash page contribute -inf scores:
    corrupting the trash page must not change the output, and an
    all-trash row (unadmitted slot) returns exactly 0."""
    page_size, nb = 8, 4
    q, pk, pv, pt = _case(3, 4, 2, 16, page_size, nb, 3 * nb, seed=3,
                          trash_tail=2)
    trash = pk.shape[0] - 1
    pt = pt.at[1].set(trash)                      # slot 1: never admitted
    cl = jnp.asarray([2 * page_size + 1, 5, page_size * nb], jnp.int32)
    out1 = paged_decode_attention(q, pk, pv, pt, cl,
                                  interpret=_interpret())
    poisoned_k = pk.at[trash].set(1e4)
    poisoned_v = pv.at[trash].set(-1e4)
    out2 = paged_decode_attention(q, poisoned_k, poisoned_v, pt, cl,
                                  interpret=_interpret())
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out1[1]), 0.0)
    out3 = pool_attention_xla(q, poisoned_k, poisoned_v, pt, cl)
    np.testing.assert_allclose(np.asarray(out3), np.asarray(out1),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("s", [2, 5])
@pytest.mark.parametrize("mode", ["full", "window", "softcap"])
def test_multiquery_pool_lowering_vs_ref(s, mode):
    """Speculative verify reads: S query rows per slot, per-row causal
    ring masks, against the multi-query gather oracle."""
    page_size, nb, h, hkv = 4, 4, 4, 2
    kw = {"full": {},
          "window": {"window": 3 * page_size},
          "softcap": {"softcap": 20.0}}[mode]
    ring = page_size * nb
    q, pk, pv, pt = _case(3, h, hkv, 16, page_size, nb, 4 * nb,
                          seed=90 + s)
    q = jnp.repeat(q[:, None], s, axis=1) * (1 + jnp.arange(s)[
        None, :, None, None] * 0.1)
    cl = jnp.asarray([ring - 3, s + 1, 2 * ring + 5], jnp.int32)
    got = pool_attention_xla(q, pk, pv, pt, cl, **kw)
    want = paged_attention_ref(q, pk, pv, pt, cl, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("s", [2, 5])
@pytest.mark.parametrize("mode", ["full", "window", "softcap"])
def test_multiquery_kernel_vs_ref(s, mode):
    kw = {"full": {},
          "window": {"window": 3 * 4},
          "softcap": {"softcap": 20.0}}[mode]
    page_size, nb, h, hkv = 4, 4, 4, 2
    ring = page_size * nb
    q, pk, pv, pt = _case(3, h, hkv, 16, page_size, nb, 4 * nb,
                          seed=70 + s)
    q = jnp.repeat(q[:, None], s, axis=1) * (1 + jnp.arange(s)[
        None, :, None, None] * 0.1)
    cl = jnp.asarray([ring - 3, s + 1, 2 * ring + 5], jnp.int32)
    got = paged_decode_attention(q, pk, pv, pt, cl,
                                 interpret=_interpret(), **kw)
    want = paged_attention_ref(q, pk, pv, pt, cl, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_multiquery_kernel_page_stale_for_newest_row_only():
    """Regression: a page whose tokens are all outside the NEWEST query
    row's window can still be in-window for earlier draft rows — the
    kernel's page-skip predicate must use the per-row mask, not the
    newest row's.  window=12, P=4, S=5, cache_len=40: the page holding
    absolute tokens 24..27 is stale for row 4 (position 39 needs u>27)
    but valid for rows at positions 35..38."""
    page_size, nb, h, hkv, s, window = 4, 4, 4, 2, 5, 12
    q, pk, pv, pt = _case(2, h, hkv, 16, page_size, nb, 4 * nb, seed=21)
    q = jnp.repeat(q[:, None], s, axis=1) * (1 + jnp.arange(s)[
        None, :, None, None] * 0.1)
    cl = jnp.asarray([40, 2 * 16 + 8], jnp.int32)   # page-aligned stale
    got = paged_decode_attention(q, pk, pv, pt, cl, window=window,
                                 interpret=_interpret())
    want = paged_attention_ref(q, pk, pv, pt, cl, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_multiquery_first_row_matches_single_query():
    """The newest row of an S-row verify equals the single-query result
    at the same cache_len, and a 3-D q keeps the legacy behavior."""
    page_size, nb, h, hkv = 4, 4, 4, 2
    q, pk, pv, pt = _case(2, h, hkv, 16, page_size, nb, 3 * nb, seed=31)
    cl = jnp.asarray([9, 14], jnp.int32)
    single = paged_attention_ref(q, pk, pv, pt, cl)
    multi = paged_attention_ref(
        jnp.stack([jax.random.normal(KEY, q.shape), q], axis=1),
        pk, pv, pt, cl)
    np.testing.assert_allclose(np.asarray(multi[:, -1]),
                               np.asarray(single), rtol=1e-5, atol=1e-5)


# ------------------------------------------------ live page bound
_LB_PAGE, _LB_NB = 4, 4
_LB_RING = _LB_PAGE * _LB_NB


@pytest.mark.parametrize("q_len,window", [(1, None), (5, None), (5, 6)],
                         ids=["q1", "q5", "q5-window"])
@pytest.mark.parametrize("cache_len", [
    0, 1, _LB_PAGE, _LB_PAGE + 1, _LB_RING - 1, _LB_RING, _LB_RING + 5])
def test_live_blocks_covers_every_valid_page(cache_len, q_len, window):
    """The kernel's page-loop bound reaches every table entry that holds
    a valid position for any query row (so no live page is cut), and
    until the ring wraps it reaches no further (so no dead page is
    streamed).  Validity is the oracle's ring formula, per row."""
    ps, nb, ring = _LB_PAGE, _LB_NB, _LB_RING
    t = cache_len - 1
    r = np.arange(ring)
    u = t - ((t - r) % ring)
    qpos = cache_len - q_len + np.arange(q_len)[:, None]
    valid = (u >= 0) & (u <= qpos)
    if window is not None:
        valid &= u > qpos - window
    pages = np.flatnonzero(valid.any(axis=0).reshape(nb, ps).any(axis=1))
    needed = int(pages[-1]) + 1 if pages.size else 0
    n = int(live_blocks(cache_len, ps, nb))
    assert needed <= n <= nb
    if cache_len < ring:               # not wrapped: tight, ceil(len / P)
        assert n == needed == -(-cache_len // ps)
    else:
        assert n == nb
    traced = jax.jit(lambda c: live_blocks(c, ps, nb))(jnp.int32(cache_len))
    assert int(traced) == n


@pytest.mark.parametrize("q_len", [1, 5])
@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_kernel_never_reads_pages_past_the_live_count(kv_dtype, q_len):
    """Every table entry past a slot's live count points at a real page
    full of NaN (for int8, NaN scale rows): the kernel's output stays
    finite and bitwise equal to the same call with those entries on the
    trash page, which in turn matches the oracle reading finite pages
    in every entry.  Slots: empty, one-token, partial, exactly full,
    wrapped."""
    ps, nb, h, hkv = 4, 4, 4, 2
    ring = ps * nb
    cl_host = np.asarray([0, 1, 2 * ps + 3, ring, ring + 5], np.int32)
    b = len(cl_host)
    q, pk, pv, _ = _case(b, h, hkv, 16, ps, nb, 2 * b * nb, seed=41)
    if q_len > 1:
        q = jnp.repeat(q[:, None], q_len, axis=1) * (1 + jnp.arange(
            q_len)[None, :, None, None] * 0.1)
    trash = pk.shape[0] - 1
    live = np.minimum(-(-cl_host // ps), nb)      # ceil(len / P), wrapped
    dead = np.arange(nb)[None, :] >= live[:, None]          # [B, nb]
    pt_live = np.arange(b * nb, dtype=np.int32).reshape(b, nb)  # distinct
    pt_nan = np.where(dead, b * nb + pt_live, pt_live)      # NaN pages
    pt_trash = np.where(dead, trash, pt_live)
    nan_pages = jnp.asarray(np.unique(pt_nan[dead]))
    kw = nan_kw = {}
    if kv_dtype == "fp32":
        pk = pk.at[nan_pages].set(jnp.nan)
        pv = pv.at[nan_pages].set(jnp.nan)
    else:
        q, pk, pv, sk, sv, _ = _quantize_case((q, pk, pv, None), kv_dtype)
        kw = {"k_scale": sk, "v_scale": sv}
        nan_kw = {"k_scale": sk.at[nan_pages].set(jnp.nan),
                  "v_scale": sv.at[nan_pages].set(jnp.nan)}
    cl = jnp.asarray(cl_host)
    got = paged_decode_attention(q, pk, pv, jnp.asarray(pt_nan), cl,
                                 interpret=_interpret(), **nan_kw)
    clean = paged_decode_attention(q, pk, pv, jnp.asarray(pt_trash), cl,
                                   interpret=_interpret(), **kw)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))
    # the oracle reads every entry, on finite pages: none past the live
    # count may matter
    want = paged_attention_ref(q, pk, pv, jnp.asarray(pt_live), cl, **kw)
    np.testing.assert_allclose(np.asarray(clean), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(got)[0], 0.0)  # empty slot


def test_model_paged_decode_step_kernel_vs_gather():
    """models/attention.paged_decode_step with paged_kernel on/off must
    produce the same attention output and pool writes."""
    from repro.models import attention

    b, h, hkv, dh, page_size, nb = 2, 4, 2, 16, 4, 4
    q = jax.random.normal(KEY, (b, 1, h, dh)) * 0.5
    kk = jax.random.normal(jax.random.fold_in(KEY, 1), (b, 1, hkv, dh)) * 0.5
    vv = jax.random.normal(jax.random.fold_in(KEY, 2), (b, 1, hkv, dh))
    _, pk, pv, pt = _case(b, h, hkv, dh, page_size, nb, 3 * nb, seed=9)
    cache = {"pk": pk, "pv": pv, "pt": pt}
    cl = jnp.asarray([6, 13], jnp.int32)
    outs = {}
    for paged_kernel in (False, True):
        out, new = attention.paged_decode_step(
            q, kk, vv, dict(cache), cl, window=None, softcap=None,
            paged_kernel=paged_kernel)
        outs[paged_kernel] = (out, new["pk"], new["pv"])
    for a, b_ in zip(outs[False], outs[True]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def _run_engine(eng, n_req=6, max_new=20):
    from repro.serve.engine import Request

    for i in range(n_req):
        eng.submit(Request(rid=i, prompt=[1 + i, 2, 3 + i % 3, 4],
                           max_new_tokens=max_new))
    done = eng.run(max_steps=100_000)
    assert len(done) == n_req
    return {r.rid: r.out_tokens for r in done}


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "gemma2-2b"])
def test_engine_token_parity_paged_kernel(arch):
    """Pool-direct decode must be invisible in the tokens: paged-kernel
    engine == gather engine == dense ReferenceEngine, for a pure
    full-attention arch and a sliding-window arch whose 16-token ring
    wraps during the 20-token generation."""
    from repro.configs import get_config, reduced
    from repro.models import model_defs
    from repro.models import module as m
    from repro.serve.engine import Engine
    from repro.serve.reference import ReferenceEngine

    cfg = reduced(get_config(arch))
    params = m.init_params(model_defs(cfg), jax.random.PRNGKey(0),
                           jnp.float32)
    out_paged = _run_engine(Engine(cfg, params, slots=3, max_len=64,
                                   sync_interval=8, prefix_sharing=False,
                                   paged_kernel=True))
    out_gather = _run_engine(Engine(cfg, params, slots=3, max_len=64,
                                    sync_interval=8, prefix_sharing=False,
                                    paged_kernel=False))
    out_ref = _run_engine(ReferenceEngine(cfg, params, slots=3, max_len=64))
    assert out_paged == out_gather == out_ref


def test_engine_paged_kernel_oversubscribed_pool():
    """The configuration the pool-direct path exists for: table width 32
    blocks (max_len=256) but only 24 physical pages — outputs must still
    match the gather path."""
    from repro.configs import get_config, reduced
    from repro.models import model_defs
    from repro.models import module as m
    from repro.serve.engine import Engine

    cfg = reduced(get_config("internlm2-1.8b"))
    params = m.init_params(model_defs(cfg), jax.random.PRNGKey(0),
                           jnp.float32)
    kw = dict(slots=3, max_len=256, page_size=8, num_pages=24,
              sync_interval=8, prefix_sharing=False)
    out_paged = _run_engine(Engine(cfg, params, paged_kernel=True, **kw))
    out_gather = _run_engine(Engine(cfg, params, paged_kernel=False, **kw))
    assert out_paged == out_gather


def test_page_size_rejected_at_spec_construction():
    """Bugfix: page sizes the kernel block spec can't tile fail at
    CacheSpec construction with an actionable error, not inside Pallas
    at trace time."""
    from repro.configs import get_config, reduced
    from repro.serve.cache import CacheSpec

    cfg = reduced(get_config("internlm2-1.8b"))
    with pytest.raises(ValueError, match="power of two"):
        CacheSpec.from_config(cfg, slots=2, max_len=64, page_size=6)
    with pytest.raises(ValueError, match="ring width"):
        CacheSpec.from_config(cfg, slots=2, max_len=64, page_size=128)
    wcfg = reduced(get_config("gemma2-2b"))     # window 16 < page 32
    with pytest.raises(ValueError, match="ring width"):
        CacheSpec.from_config(wcfg, slots=2, max_len=64, page_size=32)
    # the boundary cases stay constructible
    CacheSpec.from_config(cfg, slots=2, max_len=64, page_size=64)
    CacheSpec.from_config(wcfg, slots=2, max_len=64, page_size=16)


# ---------------------------------------------------------------- quantized
def _qdtypes():
    """The quantized (8-bit) pool storage dtypes."""
    from repro.serve.cache import KV_DTYPES

    return [d for d in KV_DTYPES if d != "fp32"]


def _quantize_case(case, kv_dtype):
    """Quantize a ``_case`` pool pair into (q-pools, scale pools)."""
    from repro.models.attention import quantize_pages
    from repro.serve.cache import kv_pool_dtype

    q, pk, pv, pt = case
    dt = kv_pool_dtype(kv_dtype)
    qk, sk = quantize_pages(pk, dt)
    qv, sv = quantize_pages(pv, dt)
    return q, qk, qv, sk, sv, pt


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8_e4m3"])
@pytest.mark.parametrize("mode", ["full", "window", "softcap"])
def test_quantized_kernel_vs_ref(kv_dtype, mode):
    """In-kernel dequant (scales folded into scores / PV inside the
    Pallas kernel) must match the gather-then-dequant oracle bit-for-bit
    up to fp accumulation order — same quantized pools on both sides."""
    kw = {"full": {}, "window": {"window": 12},
          "softcap": {"softcap": 20.0}}[mode]
    page_size, nb = 4, 4
    ring = page_size * nb
    q, qk, qv, sk, sv, pt = _quantize_case(
        _case(3, 4, 2, 16, page_size, nb, 4 * nb, seed=5), kv_dtype)
    cl = jnp.asarray([ring - 3, 1 + page_size, 2 * ring + 5], jnp.int32)
    got = paged_decode_attention(q, qk, qv, pt, cl, k_scale=sk, v_scale=sv,
                                 interpret=_interpret(), **kw)
    want = paged_attention_ref(q, qk, qv, pt, cl, k_scale=sk, v_scale=sv,
                               **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8_e4m3"])
@pytest.mark.parametrize("s", [1, 3])
def test_quantized_pool_lowering_vs_ref(kv_dtype, s):
    """The XLA pool-wide lowering's folded dequant (scales into scores /
    softmax weights, no fp32 pool ever stored) vs the oracle — single
    and multi-query (speculative verify) row counts."""
    page_size, nb = 4, 4
    ring = page_size * nb
    q, qk, qv, sk, sv, pt = _quantize_case(
        _case(3, 4, 2, 16, page_size, nb, 4 * nb, seed=60 + s), kv_dtype)
    if s > 1:
        q = jnp.repeat(q[:, None], s, axis=1) * (1 + jnp.arange(s)[
            None, :, None, None] * 0.1)
    cl = jnp.asarray([ring - 3, s + 1, 2 * ring + 5], jnp.int32)
    got = pool_attention_xla(q, qk, qv, pt, cl, k_scale=sk, v_scale=sv)
    want = paged_attention_ref(q, qk, qv, pt, cl, k_scale=sk, v_scale=sv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_quantized_reconstruction_error_bounded_through_attention():
    """Quantized-pool attention vs the fp32 pool it was quantized from:
    the output error must stay within the per-page quantization step
    propagated through softmax (weights sum to 1, so the V error bound
    is max over pages of amax/qmax; scores perturb weights smoothly)."""
    page_size, nb = 4, 4
    case = _case(2, 4, 2, 16, page_size, nb, 3 * nb, seed=13)
    q, pk, pv, pt = case
    cl = jnp.asarray([11, 2 * page_size * nb + 3], jnp.int32)
    want = paged_attention_ref(q, pk, pv, pt, cl)
    for kv_dtype in _qdtypes():
        qq, qk, qv, sk, sv, _ = _quantize_case(case, kv_dtype)
        got = paged_attention_ref(qq, qk, qv, pt, cl,
                                  k_scale=sk, v_scale=sv)
        err = float(jnp.max(jnp.abs(got - want)))
        # amax/qmax per page; int8 grid is ~1/127 of amax, fp8 coarser
        step = {"int8": 1.0 / 127.0, "fp8_e4m3": 1.0 / 16.0}[kv_dtype]
        bound = float(jnp.max(jnp.abs(pv))) * step * 4 \
            + float(jnp.max(jnp.abs(pk))) * step * 4
        assert err < bound, (kv_dtype, err, bound)


def test_quantized_trash_page_invariance():
    """Corrupting the trash page AND its scale rows must not change
    quantized-pool attention output; an all-trash row returns exactly
    0 in every lowering."""
    page_size, nb = 8, 4
    q, qk, qv, sk, sv, pt = _quantize_case(
        _case(3, 4, 2, 16, page_size, nb, 3 * nb, seed=3, trash_tail=2),
        "int8")
    trash = qk.shape[0] - 1
    pt = pt.at[1].set(trash)                      # slot 1: never admitted
    cl = jnp.asarray([2 * page_size + 1, 5, page_size * nb], jnp.int32)
    out1 = pool_attention_xla(q, qk, qv, pt, cl, k_scale=sk, v_scale=sv)
    bad_k = qk.at[trash].set(127)
    bad_v = qv.at[trash].set(-127)
    bad_sk = sk.at[trash].set(1e4)
    bad_sv = sv.at[trash].set(1e4)
    out2 = pool_attention_xla(q, bad_k, bad_v, pt, cl,
                              k_scale=bad_sk, v_scale=bad_sv)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out2[1]), 0.0)
    out3 = paged_attention_ref(q, bad_k, bad_v, pt, cl,
                               k_scale=bad_sk, v_scale=bad_sv)
    np.testing.assert_allclose(np.asarray(out3), np.asarray(out1),
                               rtol=2e-4, atol=2e-4)
    out4 = paged_decode_attention(q, bad_k, bad_v, pt, cl,
                                  k_scale=bad_sk, v_scale=bad_sv,
                                  interpret=_interpret())
    np.testing.assert_allclose(np.asarray(out4), np.asarray(out1),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(out4[1]), 0.0)


def test_quantized_model_paged_decode_step_kernel_vs_gather():
    """paged_decode_step on a quantized cache: kernel and gather paths
    must agree on the attention output AND the re-quantized pool + scale
    writes (the RMW write path is shared, so pools must be identical)."""
    from repro.models import attention

    b, h, hkv, dh, page_size, nb = 2, 4, 2, 16, 4, 4
    q = jax.random.normal(KEY, (b, 1, h, dh)) * 0.5
    kk = jax.random.normal(jax.random.fold_in(KEY, 1), (b, 1, hkv, dh)) * 0.5
    vv = jax.random.normal(jax.random.fold_in(KEY, 2), (b, 1, hkv, dh))
    _, qk, qv, sk, sv, pt = _quantize_case(
        _case(b, h, hkv, dh, page_size, nb, 3 * nb, seed=9), "int8")
    cl = jnp.asarray([6, 13], jnp.int32)
    outs = {}
    for paged_kernel in (False, True):
        cache = {"pk": qk, "pv": qv, "pt": pt, "ks": sk, "vs": sv}
        out, new = attention.paged_decode_step(
            q, kk, vv, cache, cl, window=None, softcap=None,
            paged_kernel=paged_kernel)
        outs[paged_kernel] = (out, new["pk"], new["pv"], new["ks"],
                              new["vs"])
    np.testing.assert_allclose(np.asarray(outs[False][0]),
                               np.asarray(outs[True][0]),
                               rtol=2e-4, atol=2e-4)
    for a, b_ in zip(outs[False][1:], outs[True][1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_quantized_engine_token_parity_kernel_vs_gather():
    """int8 pools end to end: pool-direct and gather engines run the
    same quantization (identical pool writes), so greedy tokens must
    match exactly — and no pages may leak."""
    from repro.configs import get_config, reduced
    from repro.models import model_defs
    from repro.models import module as m
    from repro.serve.engine import Engine

    cfg = reduced(get_config("internlm2-1.8b"))
    params = m.init_params(model_defs(cfg), jax.random.PRNGKey(0),
                           jnp.float32)
    kw = dict(slots=3, max_len=64, sync_interval=8, prefix_sharing=False,
              kv_dtype="int8")
    gather = Engine(cfg, params, paged_kernel=False, **kw)
    out_gather = _run_engine(gather)
    assert gather.leaked_pages() == 0
    paged = Engine(cfg, params, paged_kernel=True, **kw)
    out_paged = _run_engine(paged)
    assert paged.leaked_pages() == 0
    assert out_paged == out_gather
