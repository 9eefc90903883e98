"""Serving engine: continuous batching, ring buffers, request lifecycle,
bucketed prefill, retrace/sync regression guards."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import (forward_dense_logits, forward_prefill, model_defs)
from repro.models import module as m
from repro.serve.engine import Engine, Request
from repro.serve.reference import ReferenceEngine


def _model(arch, **kw):
    cfg = reduced(get_config(arch), **kw)
    params = m.init_params(model_defs(cfg), jax.random.PRNGKey(0),
                           jnp.float32)
    return cfg, params


def _engine(arch, slots=3, max_len=64, **kw):
    cfg, params = _model(arch, **kw)
    return cfg, params, Engine(cfg, params, slots=slots, max_len=max_len)


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "rwkv6-7b", "gemma2-2b"])
def test_engine_completes_more_requests_than_slots(arch):
    cfg, params, eng = _engine(arch)
    for i in range(7):
        eng.submit(Request(rid=i, prompt=[1 + i % 5, 2, 3],
                           max_new_tokens=6))
    done = eng.run()
    assert len(done) == 7
    assert all(len(r.out_tokens) == 6 for r in done)
    assert all(0 <= t < cfg.vocab_size for r in done for t in r.out_tokens)


def test_engine_greedy_matches_teacher_forcing():
    cfg, params, eng = _engine("internlm2-1.8b", slots=2)
    eng.submit(Request(rid=0, prompt=[5, 6, 7, 8], max_new_tokens=5))
    (r,) = eng.run()
    full = r.prompt + r.out_tokens
    dense = jax.jit(lambda p, b: forward_dense_logits(p, cfg, b))(
        params, {"tokens": jnp.asarray([full], jnp.int32)})
    for i, tok in enumerate(r.out_tokens):
        pos = len(r.prompt) - 1 + i
        assert int(jnp.argmax(dense[0, pos])) == tok, (i, pos)


def test_engine_windowed_arch_long_generation():
    """gemma-style sliding windows: generate beyond the window so the ring
    buffer wraps, then check against teacher forcing."""
    cfg, params, eng = _engine("gemma2-2b", slots=1, max_len=96)
    window = next(b.window for b in cfg.blocks if b.window)
    n_new = window + 8  # force wraparound
    eng.submit(Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new_tokens=n_new))
    (r,) = eng.run(max_steps=n_new + 4)
    full = r.prompt + r.out_tokens
    dense = jax.jit(lambda p, b: forward_dense_logits(p, cfg, b))(
        params, {"tokens": jnp.asarray([full], jnp.int32)})
    for i, tok in enumerate(r.out_tokens):
        pos = len(r.prompt) - 1 + i
        assert int(jnp.argmax(dense[0, pos])) == tok, f"diverged at {i}"


def test_windowed_prompt_longer_than_window_wraps_in_splice():
    """A prompt LONGER than the sliding window ring-wraps *within one
    prefill splice*: only the newest occupant of each ring slot may land
    (token-scatter mask), older wrapped tokens go to the trash page.
    Teacher forcing is the oracle."""
    cfg, params, eng = _engine("gemma2-2b", slots=1, max_len=96)
    window = next(b.window for b in cfg.blocks if b.window)
    prompt = [(5 * j) % 200 + 1 for j in range(window + 6)]
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=10))
    (r,) = eng.run(max_steps=200)
    full = r.prompt + r.out_tokens
    dense = jax.jit(lambda p, b: forward_dense_logits(p, cfg, b))(
        params, {"tokens": jnp.asarray([full], jnp.int32)})
    for i, tok in enumerate(r.out_tokens):
        pos = len(r.prompt) - 1 + i
        assert int(jnp.argmax(dense[0, pos])) == tok, f"diverged at {i}"


def test_eos_terminates():
    cfg, params, eng = _engine("internlm2-1.8b", slots=1)
    # discover greedy continuation, then set its 3rd token as eos
    eng.submit(Request(rid=0, prompt=[2, 3], max_new_tokens=8))
    (probe,) = eng.run()
    eos = probe.out_tokens[2]
    cfg2, params2, eng2 = _engine("internlm2-1.8b", slots=1)
    eng2.submit(Request(rid=1, prompt=[2, 3], max_new_tokens=8, eos_id=eos))
    (r,) = eng2.run()
    assert r.out_tokens[-1] == eos and len(r.out_tokens) == 3


# ---------------------------------------------------------------------------
# Fast-path regression suite: ragged batching, bucketed prefill, retraces
# ---------------------------------------------------------------------------

def test_ragged_continuous_batching_staggered():
    """Mixed prompt lengths AND generation budgets: slots free and refill
    at different chunk boundaries; every request still completes with
    exactly its budget (no EOS set)."""
    cfg, params, eng = _engine("internlm2-1.8b", slots=2)
    budgets = [3, 9, 5, 14, 7, 4, 11]
    for i, mn in enumerate(budgets):
        plen = 1 + (3 * i) % 9
        eng.submit(Request(rid=i, prompt=[(2 * i + j) % cfg.vocab_size
                                          for j in range(plen)],
                           max_new_tokens=mn))
    done = eng.run()
    assert len(done) == len(budgets)
    by_rid = {r.rid: r for r in done}
    for i, mn in enumerate(budgets):
        assert len(by_rid[i].out_tokens) == mn, (i, by_rid[i].out_tokens)
        assert all(0 <= t < cfg.vocab_size for t in by_rid[i].out_tokens)


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "gemma2-2b", "rwkv6-7b",
                                  "zamba2-7b"])
def test_engine_matches_reference_engine(arch):
    """Token-for-token parity with the pre-fast-path dense engine (greedy)
    across all mixer families: full attention (internlm2), windowed rings
    (gemma2), rwkv6 state, and the zamba2 mamba2+shared-attention hybrid."""
    cfg, params = _model(arch)
    eng = Engine(cfg, params, slots=2, max_len=64)
    ref = ReferenceEngine(cfg, params, slots=2, max_len=64)
    for i in range(5):
        plen = 2 + (4 * i) % 7
        prompt = [(5 * i + j) % cfg.vocab_size for j in range(plen)]
        eng.submit(Request(rid=i, prompt=prompt, max_new_tokens=5 + i % 3))
        ref.submit(Request(rid=i, prompt=prompt, max_new_tokens=5 + i % 3))
    em = {r.rid: r.out_tokens for r in eng.run()}
    rm = {r.rid: r.out_tokens for r in ref.run()}
    assert em == rm


def test_empty_prompt_no_stale_slot():
    """plen == 0 admits cleanly (fresh state, len 0) and generates."""
    cfg, params, eng = _engine("rwkv6-7b", slots=2)
    eng.submit(Request(rid=0, prompt=[], max_new_tokens=4))
    eng.submit(Request(rid=1, prompt=[4, 5, 6], max_new_tokens=4))
    done = eng.run()
    assert len(done) == 2
    assert all(len(r.out_tokens) == 4 for r in done)
    # the non-empty request must be unaffected by its neighbour: compare
    # against a solo run
    cfg2, params2, solo = _engine("rwkv6-7b", slots=2)
    solo.submit(Request(rid=1, prompt=[4, 5, 6], max_new_tokens=4))
    (s,) = solo.run()
    assert s.out_tokens == next(r for r in done if r.rid == 1).out_tokens


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "rwkv6-7b", "gemma2-2b",
                                  "zamba2-7b"])
def test_bucketed_prefill_matches_unpadded(arch):
    """Right-padding a prompt to a bucket with a true ``length`` argument
    must reproduce the unpadded prefill: last-token logits and every
    carried state (KV rows, SSM/wkv states, token shifts) within fp32
    tolerance.  Pad token is deliberately != 0 to prove masking."""
    cfg, params = _model(arch)
    prompt = [3, 1, 4, 1, 5]
    plen, bucket = len(prompt), 16

    @jax.jit
    def fn(toks, length):
        return forward_prefill(params, cfg, {"tokens": toks}, length=length)

    logits_u, cache_u = fn(jnp.asarray([prompt], jnp.int32), None)
    padded = prompt + [9] * (bucket - plen)
    logits_p, cache_p = fn(jnp.asarray([padded], jnp.int32),
                           jnp.asarray([plen], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits_u), np.asarray(logits_p),
                               atol=1e-4, rtol=1e-4)
    assert int(cache_p["len"][0]) == plen
    for lu, lp in zip(cache_u["layers"], cache_p["layers"]):
        if lu is None:
            continue
        for k in lu:
            u, p = np.asarray(lu[k]), np.asarray(lp[k])
            if u.shape != p.shape:        # attention KV: seq axis padded
                p = p[..., :u.shape[-2], :]
            np.testing.assert_allclose(u, p, atol=1e-4, rtol=1e-4,
                                       err_msg=f"{arch} state {k}")


def test_prefill_retrace_bounded_by_buckets():
    """Legacy two-executable mode: mixed prompt lengths compile at most
    len(buckets) prefill executables and exactly one decode executable
    (fused chunked prefill — the default — compiles zero prefill
    executables; tests/test_chunked_prefill.py covers that mode)."""
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=3, max_len=64, chunked_prefill=False)
    lengths = [1, 2, 3, 5, 7, 8, 9, 11, 13, 4, 6, 12]
    for i, plen in enumerate(lengths):
        eng.submit(Request(rid=i, prompt=[(i + j) % cfg.vocab_size
                                          for j in range(plen)],
                           max_new_tokens=4))
    done = eng.run()
    assert len(done) == len(lengths)
    assert eng.prefill_compiles <= len(eng.buckets), (
        eng.prefill_compiles, eng.buckets)
    assert eng.prefill_compiles == 2       # lengths 1..8 -> 8, 9..13 -> 16
    assert eng.decode_compiles == 1


def test_overlong_prompt_rejected_for_full_attention():
    """Full-attention caches cap at max_len; a longer prompt must fail
    loudly instead of silently mod-wrapping into the KV rows."""
    cfg, params = _model("internlm2-1.8b")   # non-windowed attention
    eng = Engine(cfg, params, slots=1, max_len=16)
    with pytest.raises(ValueError, match="max_len"):
        # raises at submit(), before anything is in flight
        eng.submit(Request(rid=0, prompt=list(range(1, 21)),
                           max_new_tokens=2))
    assert not eng.queue


def test_steady_state_decode_is_sync_free():
    """A fused decode chunk dispatch performs zero device->host transfers.
    The guard raises on any sync on accelerator backends (on CPU d2h is
    zero-copy so it cannot fire); the host_syncs accounting below is the
    backend-independent check."""
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=2, max_len=64, chunked_prefill=False)
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=32))
    eng.submit(Request(rid=1, prompt=[4, 5], max_new_tokens=32))
    eng._admit()
    with jax.transfer_guard_device_to_host("disallow"):
        toks = eng.step_chunk()
        toks2 = eng.step_chunk()
    eng._drain(jnp.concatenate([toks, toks2]))   # un-drained history so far
    reqs = [r for r in eng._slot_req if r is not None]
    assert len(reqs) == 2
    assert all(len(r.out_tokens) == 1 + 2 * eng.sync_interval for r in reqs)
    # 2 chunks of decode, exactly 1 batched host sync to read them back
    assert eng.host_syncs == 1 and eng.steps == 2 * eng.sync_interval


def test_warmup_precompiles_and_stays_inert():
    """warmup() compiles every bucket + the decode chunk without
    activating slots, and later serving adds no new compiles for bucketed
    lengths (legacy mode; the fused mode's 2-executable warmup is covered
    in tests/test_chunked_prefill.py)."""
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=2, max_len=64, chunked_prefill=False)
    eng.warmup()
    n_prefill, n_decode = eng.prefill_compiles, eng.decode_compiles
    assert n_prefill == len(eng.buckets) and n_decode == 1
    assert not bool(np.asarray(eng.state["active"]).any())
    for i in range(3):
        eng.submit(Request(rid=i, prompt=[1 + i] * (2 + 7 * i),
                           max_new_tokens=4))
    done = eng.run()
    assert len(done) == 3
    assert eng.prefill_compiles == n_prefill
    assert eng.decode_compiles == n_decode


def test_warmup_preserves_sampling_reproducibility():
    """Seeded sampled runs are identical with and without warmup (the
    warmup chunk restores the threaded PRNG key)."""
    cfg, params = _model("internlm2-1.8b")
    outs = []
    for do_warmup in (False, True):
        eng = Engine(cfg, params, slots=2, max_len=64, temperature=1.0,
                     seed=3)
        if do_warmup:
            eng.warmup()
        eng.submit(Request(rid=0, prompt=[7, 8, 9], max_new_tokens=6))
        (r,) = eng.run()
        outs.append(r.out_tokens)
    assert outs[0] == outs[1]


def test_batched_admission_single_executable():
    """All admissions at a chunk boundary coalesce into ONE jitted splice
    dispatch whose executable compiles exactly once, whatever mix of
    buckets and batch sizes the workload produces."""
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=3, max_len=64)
    eng.warmup()
    assert eng.admit_compiles == 1
    for i in range(9):
        plen = 1 + (5 * i) % 11          # several buckets, ragged refills
        eng.submit(Request(rid=i, prompt=[(i + j) % cfg.vocab_size
                                          for j in range(plen)],
                           max_new_tokens=3 + i % 4))
    done = eng.run()
    assert len(done) == 9
    assert eng.admit_compiles == 1


def test_chunked_prefill_reuses_buckets():
    """A prompt longer than the largest bucket runs as several
    suffix-prefill segments (suffix-capable archs): no new bucket, no
    bucket-growth recompile, token output identical to teacher
    forcing."""
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=2, max_len=64, buckets=[8],
                 chunked_prefill=False)
    prompt = [(7 * j) % 200 + 1 for j in range(30)]
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=6))
    (r,) = eng.run()
    assert eng.buckets == [8]                  # reuse, don't grow
    assert eng.prefill_compiles <= 1
    full = prompt + r.out_tokens
    dense = jax.jit(lambda p, b: forward_dense_logits(p, cfg, b))(
        params, {"tokens": jnp.asarray([full], jnp.int32)})
    for i, tok in enumerate(r.out_tokens):
        pos = len(prompt) - 1 + i
        assert int(jnp.argmax(dense[0, pos])) == tok, f"diverged at {i}"


def test_chunked_prefill_matches_single_shot():
    """Chunked prefill (buckets=[8]) and single-shot prefill (default
    buckets) produce identical outputs for the same requests — including
    a second request sharing the engine."""
    cfg, params = _model("internlm2-1.8b")
    prompts = [[(11 * j) % 250 + 1 for j in range(27)], [3, 1, 4]]
    outs = []
    for buckets in ([8], None):
        eng = Engine(cfg, params, slots=2, max_len=64, buckets=buckets,
                     chunked_prefill=False)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
        outs.append({r.rid: r.out_tokens for r in eng.run()})
    assert outs[0] == outs[1]


def test_chunked_prefill_non_capable_arch_grows_bucket():
    """Archs without the suffix machinery (windowed layers) keep the old
    fallback: the bucket list grows and output stays correct."""
    cfg, params = _model("gemma2-2b")
    eng = Engine(cfg, params, slots=1, max_len=96, buckets=[8],
                 chunked_prefill=False)
    prompt = [(5 * j) % 200 + 1 for j in range(22)]
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=5))
    (r,) = eng.run()
    assert eng.buckets != [8]                  # grew to cover the prompt
    full = prompt + r.out_tokens
    dense = jax.jit(lambda p, b: forward_dense_logits(p, cfg, b))(
        params, {"tokens": jnp.asarray([full], jnp.int32)})
    for i, tok in enumerate(r.out_tokens):
        pos = len(prompt) - 1 + i
        assert int(jnp.argmax(dense[0, pos])) == tok, f"diverged at {i}"


def test_per_request_temperature_mixed_batch():
    """Greedy and sampled requests share one compiled decode step."""
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=2, max_len=64)
    eng.submit(Request(rid=0, prompt=[2, 3], max_new_tokens=6))
    eng.submit(Request(rid=1, prompt=[2, 3], max_new_tokens=6,
                       temperature=2.0))
    done = {r.rid: r for r in eng.run()}
    # greedy slot must match a solo greedy run exactly
    cfg2, params2, solo = _engine("internlm2-1.8b", slots=2)
    solo.submit(Request(rid=0, prompt=[2, 3], max_new_tokens=6))
    (s,) = solo.run()
    assert done[0].out_tokens == s.out_tokens
    assert len(done[1].out_tokens) == 6
    assert eng.decode_compiles == 1


@pytest.mark.parametrize("arch,kw", [
    ("internlm2-1.8b", {}),                              # fused chunks
    ("internlm2-1.8b", {"chunked_prefill": False}),      # bucketed prefill
    ("internlm2-1.8b", {"spec": "ngram", "kv_dtype": "int8"}),
    ("rwkv6-7b", {}),
])
def test_donating_engine_matches_non_donating(arch, kw):
    """Engines donate their cache and slot state off CPU.  Every donated
    leaf must be its own buffer (a buffer donated twice in one call is
    an error), and donation must not change a token."""
    cfg, params = _model(arch)
    outs = []
    for donate in (False, True):
        eng = Engine(cfg, params, slots=2, max_len=64, donate=donate, **kw)
        eng.warmup()
        for i in range(3):
            eng.submit(Request(rid=i, prompt=[3 + i, 4, 5, 6, 7],
                               max_new_tokens=7))
        outs.append({r.rid: r.out_tokens for r in eng.run()})
        assert eng.leaked_pages() == 0
    assert outs[0] == outs[1] and len(outs[1]) == 3
