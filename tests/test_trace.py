"""Observability invariants: tracing on vs off is token-identical with
identical compile counts and a sync-free decode chunk (tracing records
host-side at chunk boundaries only); the bounded ring evicts oldest
non-terminal events while terminal events survive by contract; seeded
``TrafficGenerator`` replays under a ``VirtualClock`` produce
byte-identical trace fingerprints; ``Engine.observe()`` emits only
registry-known dotted names; ``export_trace`` / ``explain`` render
complete submit->terminal chains; the engine's phase spans match the
host plane of JAX's profiler, the ``chunk`` row counters add up to what
was admitted and drained, and collections are ``gc`` spans only while a
traced engine lives."""

import gc
import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.models import model_defs
from repro.models import module as m
from repro.serve import metrics
from repro.serve.engine import Engine, Request
from repro.serve.trace import (ENGINE_TID, GC_TID, TERMINAL_KINDS, Tracer,
                               to_chrome_trace)
from repro.serve.traffic import TrafficGenerator, VirtualClock, replay


def _model(arch, **kw):
    cfg = reduced(get_config(arch), **kw)
    params = m.init_params(model_defs(cfg), jax.random.PRNGKey(0),
                           jnp.float32)
    return cfg, params


def _workload(eng, n=6):
    for i in range(n):
        plen = 2 + (3 * i) % 7
        eng.submit(Request(rid=i, prompt=[(i + j) % 150 + 1
                                          for j in range(plen)],
                           max_new_tokens=4 + i % 3))
    return eng.run(max_steps=50_000)


# ---------------------------------------------------------------------------
# Tracing on vs off: token parity, compile parity, sync freedom
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["internlm2-1.8b", "gemma2-2b"])
def test_tracing_is_invisible_to_outputs_and_compiles(arch):
    """Tracing must be a pure host-side observer: identical tokens and
    identical executable counts with and without it."""
    cfg, params = _model(arch)
    runs = {}
    for traced in (False, True):
        eng = Engine(cfg, params, slots=2, max_len=64,
                     prefix_sharing=False, trace=traced)
        done = _workload(eng)
        runs[traced] = ({r.rid: list(r.out_tokens) for r in done},
                        (eng.prefill_compiles, eng.suffix_prefill_compiles,
                         eng.decode_compiles, eng.admit_compiles))
        if traced:
            evs = eng.tracer.events()
            assert {e.kind for e in evs} >= {"submit", "admit", "chunk",
                                             "finish"}
            assert eng.tracer.dropped == 0
    assert runs[False][0] == runs[True][0]
    assert runs[False][1] == runs[True][1]


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "gemma2-2b"])
def test_traced_decode_chunk_stays_sync_free(arch):
    """With tracing on, the fused decode chunk still performs zero
    device->host transfers (events are recorded by the host drain at
    chunk boundaries, from the drain's one clock read)."""
    cfg, params = _model(arch)
    eng = Engine(cfg, params, slots=2, max_len=64,
                 prefix_sharing=False, trace=True)
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=32))
    eng.submit(Request(rid=1, prompt=[4, 5], max_new_tokens=32))
    eng._admit()
    with jax.transfer_guard_device_to_host("disallow"):
        toks = eng.step_chunk()
        toks2 = eng.step_chunk()
    eng._drain(jnp.concatenate([toks, toks2]))
    assert eng.host_syncs == 1 and eng.steps == 2 * eng.sync_interval
    assert eng.decode_compiles == 1
    assert len(eng.tracer.events()) > 0


def test_token_chunks_parallel_to_token_times():
    """Satellite bugfix: every emitted token carries the chunk sequence
    number it was drained in, parallel to token_times, and admission_log
    entries carry the chunk id for cross-referencing."""
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=2, max_len=64, sync_interval=4,
                 prefix_sharing=False)
    done = _workload(eng, n=4)
    for r in done:
        assert len(r.token_chunks) == len(r.token_times) \
            == len(r.out_tokens)
        assert r.token_chunks == sorted(r.token_chunks)  # monotone
        # tokens drained in the same chunk share the same timestamp;
        # distinct chunk ids disambiguate them for TPOT attribution
        for i in range(1, len(r.token_chunks)):
            if r.token_chunks[i] == r.token_chunks[i - 1]:
                assert r.token_times[i] == r.token_times[i - 1]
    assert len({c for r in done for c in r.token_chunks}) > 1
    for entry in eng.scheduler.admission_log:
        assert len(entry) == 5
        assert entry[4] >= 0                             # the chunk id


# ---------------------------------------------------------------------------
# Ring buffer: bounded, oldest-first eviction, terminal retention
# ---------------------------------------------------------------------------

def test_ring_evicts_oldest_but_never_terminal_events():
    tr = Tracer(capacity=8)
    for i in range(6):
        tr.record("chunk", float(i), chunk=i)
    tr.record("finish", 6.0, rid=0, status="FINISHED")
    tr.record("reject", 7.0, rid=1, why="queue_full")
    assert len(tr) == 8 and tr.dropped == 0
    for i in range(20):
        tr.record("prefill", 8.0 + i, rid=2, slot=0)
    # ring stayed bounded; evicted chunk/prefill events were counted
    # (2 of the 8 retained are the pinned terminals, so 6 of the 26
    # non-terminal events survive and 20 were dropped)
    assert len(tr) == tr.capacity
    assert tr.dropped == 20
    kinds = [e.kind for e in tr.events()]
    assert kinds.count("finish") == 1 and kinds.count("reject") == 1
    # events() stays seq-ordered even with pinned terminals interleaved
    seqs = [e.seq for e in tr.events()]
    assert seqs == sorted(seqs)


def test_ring_terminal_events_may_exceed_capacity():
    """Terminal events are never dropped, even if that means holding
    more than ``capacity`` events."""
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.record("finish", float(i), rid=i, status="FINISHED")
    assert len(tr) == 10 and tr.dropped == 0
    assert all(e.kind in TERMINAL_KINDS for e in tr.events())
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_engine_trace_capacity_and_eviction_end_to_end():
    """A tiny engine-side ring still retains every terminal event after
    a workload that overflows it many times over."""
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=2, max_len=64, sync_interval=2,
                 prefix_sharing=False, trace=8)
    done = _workload(eng, n=6)
    assert len(done) == 6
    assert eng.tracer.dropped > 0
    terms = [e for e in eng.tracer.events() if e.kind in TERMINAL_KINDS]
    assert sorted(e.rid for e in terms) == list(range(6))


# ---------------------------------------------------------------------------
# Deterministic fingerprints under VirtualClock replay
# ---------------------------------------------------------------------------

def test_replayed_traffic_yields_identical_fingerprints():
    """Two virtual-clock replays of one seeded trace produce
    byte-identical trace fingerprints (the property fig04
    --trace-report gates); a different traffic seed changes them."""
    cfg, params = _model("internlm2-1.8b")

    def once(seed):
        trace = TrafficGenerator(seed, rate=3.0,
                                 process="bursty").generate(8)
        clk = VirtualClock(dt=0.05)
        eng = Engine(cfg, params, slots=2, max_len=64, page_size=8,
                     num_pages=10, sync_interval=4, policy="slo",
                     prefix_sharing=False, clock=clk, trace=True)
        replay(eng, trace, clock=clk)
        assert eng.leaked_pages() == 0
        return eng.tracer.fingerprint()

    fp1, fp2 = once(5), once(5)
    assert fp1 == fp2
    assert fp1 != once(6)


# ---------------------------------------------------------------------------
# observe() registry discipline + exporters
# ---------------------------------------------------------------------------

def test_observe_emits_only_registered_names():
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=2, max_len=64, trace=True)
    _workload(eng, n=3)
    obs = eng.observe()
    assert obs, "observe() returned nothing"
    for name, value in obs.items():
        assert metrics.kind_of(name) is not None, name
        assert isinstance(value, (int, float, bool)), (name, value)
    # the registry's headline names are present
    for name in ("engine.chunks", "engine.host_syncs",
                 "pool.pages_in_use", "pool.peak_pages_in_use",
                 "sched.admissions", "sched.preemptions.total",
                 "latency.goodput", "trace.events", "trace.dropped"):
        assert name in obs, name
    # metric names are stable API: renaming one must raise loudly
    with pytest.raises(KeyError):
        metrics._put({}, "engine.not_a_metric", 1)


def test_export_trace_and_explain_complete_chains(tmp_path):
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=2, max_len=64, trace=True)
    done = _workload(eng, n=4)
    path = tmp_path / "trace.json"
    obj = eng.export_trace(str(path))
    assert json.loads(path.read_text()) == obj
    evs = obj["traceEvents"]
    # every finished rid has a submit instant, a terminal instant, and
    # a flow chain (s ... f) linking them
    for r in done:
        inst = [e for e in evs if e["ph"] == "i"
                and e.get("args", {}).get("rid") == r.rid]
        assert any(e["name"] == "submit" for e in inst), r.rid
        assert any(e["name"] == "finish" for e in inst), r.rid
        flows = [e for e in evs if e["ph"] in ("s", "t", "f")
                 and e.get("id") == str(r.rid)]
        assert [e["ph"] for e in flows][:1] == ["s"]
        assert [e["ph"] for e in flows][-1:] == ["f"]
        txt = eng.explain(r.rid)
        assert "submit" in txt and "terminal: FINISHED" in txt
        assert "queued:" in txt and "running:" in txt
    assert eng.explain(9999) == "rid 9999: no trace events recorded"
    # untraced engines refuse rather than silently returning nothing
    bare = Engine(cfg, params, slots=1, max_len=64)
    with pytest.raises(ValueError):
        bare.export_trace()
    with pytest.raises(ValueError):
        bare.explain(0)


def test_chrome_trace_preempt_flow_spans_slot_hop():
    """A preempted-and-resumed request's flow chain hops across slot
    tracks and its wait phases include a requeued span."""
    tr = Tracer()
    tr.record("submit", 0.0, rid=7)
    tr.record("admit", 1.0, rid=7, slot=0, chunk=1)
    tr.record("preempt", 2.0, rid=7, slot=0, why="pressure")
    tr.record("admit", 3.0, rid=7, slot=1, chunk=3, resume=True)
    tr.record("finish", 4.0, rid=7, slot=1, status="FINISHED")
    obj = to_chrome_trace(tr.events())
    evs = obj["traceEvents"]
    flows = [e for e in evs if e["ph"] in ("s", "t", "f")]
    assert [e["ph"] for e in flows] == ["s", "t", "t", "t", "f"]
    assert flows[-1]["bp"] == "e"
    assert len({e["tid"] for e in flows}) == 3     # queue + both slots
    waits = [e for e in evs if e["ph"] == "b"]
    assert [w["args"]["phase"] for w in waits] == ["queued", "requeued"]
    runs = [e for e in evs if e["ph"] == "X"]
    assert len(runs) == 2 and all(e["dur"] > 0 for e in runs)


# ---------------------------------------------------------------------------
# Host phase spans on the profiler's clock, chunk row counters, gc spans
# ---------------------------------------------------------------------------

PHASES = ("engine.submit", "engine.reap", "engine.admit", "engine.dispatch",
          "engine.wait", "engine.drain")


def _host_events(log_dir):
    """``[(name, start_ns, dur_ns)]`` of the profile's host-plane events
    named like an engine phase or a collection, by start."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    pd = jax.profiler.ProfileData.from_file(files[0])
    out = [(e.name, e.start_ns, e.duration_ns)
           for plane in pd.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(("engine.", "gc."))]
    return sorted(out, key=lambda x: x[1])


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A traced tiny engine served under JAX's profiler (host spans
    only, as the benchmark records), a forced collection at the end;
    every ``jax.device_get`` counted."""
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=2, max_len=64, sync_interval=4,
                 trace=True, clock=time.perf_counter)
    eng.warmup()
    log_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    gets = []
    real_get = jax.device_get
    steps = []
    gc.collect()      # engines of earlier tests leave gc.callbacks now
    mark = len(eng.tracer.events())
    gc.disable()      # the one collection inside is the forced one
    try:
        with pytest.MonkeyPatch.context() as mp, \
                jax.transfer_guard_device_to_host("disallow"), \
                jax.profiler.trace(log_dir, profiler_options=opts):
            mp.setattr(jax, "device_get",
                       lambda x: gets.append(1) or real_get(x))
            for i in range(4):
                eng.submit(Request(rid=i, prompt=[(5 * i + j) % 150 + 1
                                                  for j in range(3 + i)],
                                   max_new_tokens=6 + i))
            while eng.queue or eng._live():
                c0, t0 = eng.chunks, time.perf_counter()
                eng.step()
                steps.append((c0, t0, time.perf_counter(),
                              eng.chunks > c0))
            gc.collect()
    finally:
        gc.enable()
    ring = eng.tracer.events()[mark:]
    return {"eng": eng, "ring": [e for e in ring if e.kind == "span"],
            "host": _host_events(log_dir), "steps": steps,
            "device_gets": len(gets)}


@pytest.mark.parametrize("check", ["names_and_order", "durations",
                                   "step_coverage", "gc", "sync_free"])
def test_engine_spans_match_the_profilers_host_plane(profiled, check):
    """Every engine phase is one ``span`` event in the ring and one host
    span in JAX's profiler, on the clock the device trace uses: same
    count, names and order, durations within 1 ms or 10%, start offsets
    within 1 ms; the phases cover each ``step()``; the forced collection
    is one ``gc`` span and one ``gc.gen2`` host span; and none of it adds
    a device-to-host transfer or an executable."""
    eng = profiled["eng"]
    ring = [e for e in profiled["ring"] if e.attrs["name"] != "gc"]
    ring.sort(key=lambda e: e.ts)
    host = [h for h in profiled["host"] if h[0].startswith("engine.")]
    if check == "names_and_order":
        assert {e.attrs["name"] for e in ring} == set(PHASES)
        assert [h[0] for h in host] == [e.attrs["name"] for e in ring]
    elif check == "durations":
        assert len(host) == len(ring)
        for (name, start_ns, dur_ns), e in zip(host, ring):
            d = dur_ns / 1e9
            assert abs(d - e.attrs["dur"]) <= max(1e-3, 0.1 * d), name
            offset = (start_ns - host[0][1]) / 1e9
            assert abs(offset - (e.ts - ring[0].ts)) <= 1e-3, name
    elif check == "step_coverage":
        ran = [s for s in profiled["steps"] if s[3]]
        assert len(ran) == eng.chunks >= 3
        for c0, t0, t1, _ in ran:
            phases = [e for e in ring if e.attrs["chunk"] == c0
                      and e.attrs["name"] != "engine.submit"]
            assert [e.attrs["name"] for e in phases] == list(PHASES[1:])
            assert sum(e.attrs["dur"] for e in phases) >= 0.95 * (t1 - t0)
    elif check == "gc":
        gcs = [e for e in profiled["ring"] if e.attrs["name"] == "gc"]
        assert len(gcs) == 1 and gcs[0].attrs["generation"] == 2
        assert gcs[0].attrs["chunk"] == eng.chunks
        host_gc = [h for h in profiled["host"] if h[0].startswith("gc.")]
        assert [h[0] for h in host_gc] == ["gc.gen2"]
        d = host_gc[0][2] / 1e9
        assert abs(d - gcs[0].attrs["dur"]) <= max(1e-3, 0.1 * d)
    else:
        assert profiled["device_gets"] == eng.host_syncs == eng.chunks
        assert eng.decode_compiles == 1


@pytest.mark.parametrize("chunked_prefill", [True, False])
def test_chunk_events_count_rows_run_prefilled_and_decoded(chunked_prefill):
    """Per-chunk row counters, checked against what the run admitted and
    drained: the prompt rows written are every admission's ``plen -
    suffix_start`` (prefix hits included), rows that decoded plus one
    prefill-sampled first token per admission are the tokens drained,
    and every chunk ran ``slots x chunk_rows x sync_interval`` rows."""
    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=3, max_len=96, page_size=8,
                 sync_interval=4, prefill_budget=8,
                 chunked_prefill=chunked_prefill, trace=True)
    head = [(7 * j) % 150 + 1 for j in range(20)]
    for wave in range(2):          # the second wave hits the first's pages
        for i in range(3):
            rid = 3 * wave + i
            eng.submit(Request(rid=rid, prompt=head + [rid + 1] * (2 + i),
                               max_new_tokens=5 + i))
        done = eng.run(max_steps=eng.steps + 10_000)
    evs = eng.tracer.events()
    admits = [e for e in evs if e.kind == "admit"]
    chunks = [e for e in evs if e.kind == "chunk"]
    assert len(admits) == len(done) == 6 and len(chunks) == eng.chunks
    assert not any(e.kind == "preempt" for e in evs)
    if chunked_prefill:
        assert sum(e.attrs["suffix_start"] for e in admits) > 0
        assert sum(e.attrs["prefill_rows"] for e in chunks) == sum(
            e.attrs["plen"] - e.attrs["suffix_start"] for e in admits)
    else:
        assert all(e.attrs["prefill_rows"] == 0 for e in chunks)
    assert sum(e.attrs["decode_rows"] for e in chunks) + len(admits) == sum(
        len(r.out_tokens) for r in done)
    rows = 3 * eng.executor.chunk_rows * eng.sync_interval
    assert {e.attrs["rows_run"] for e in chunks} == {rows}
    assert rows == 3 * (8 if chunked_prefill else 1) * 4
    table = 3 * eng.spec.widest_group.ring_blocks
    assert {e.attrs["kv_pages_table"] for e in chunks} == {table}
    assert all(3 <= e.attrs["kv_pages_streamed"] <= table for e in chunks)


def test_chunk_events_count_kv_pages_streamed():
    """On a known schedule (two prompts streaming through a fused chunk
    of 8-row prefill micro-steps, then decoding; the third slot idle),
    each ``chunk`` event's ``kv_pages_streamed`` is the sum over slots
    of ``ceil(cache_len / page_size)`` at the lengths after the chunk,
    the idle slot counting 1, and ``kv_pages_table`` is slots x table
    width."""
    cfg, params = _model("internlm2-1.8b")
    page, budget, micro = 8, 8, 4
    eng = Engine(cfg, params, slots=3, max_len=96, page_size=page,
                 sync_interval=micro, prefill_budget=budget,
                 prefix_sharing=False, trace=True)
    plens = (20, 11)
    for rid, plen in enumerate(plens):
        eng.submit(Request(rid=rid, prompt=[(5 * rid + j) % 150 + 1
                                            for j in range(plen)],
                           max_new_tokens=40))
    for _ in range(5):                 # nobody finishes in 20 micro-steps
        eng.step()
    chunks = [e for e in eng.tracer.events() if e.kind == "chunk"]
    assert len(chunks) == 5

    def length(plen, steps):           # prefill micro-steps, then 1 a step
        k = -(-plen // budget)
        return budget * steps if steps < k else plen + steps - k

    for c, e in enumerate(chunks, start=1):
        lens = [length(p, micro * c) for p in plens]
        assert e.attrs["kv_pages_streamed"] == sum(
            -(-n // page) for n in lens) + 1, (c, lens)
        assert e.attrs["kv_pages_table"] == 3 * (96 // page)


def test_gc_spans_only_with_a_tracer_and_the_hook_leaves_with_the_engine():
    """A forced collection is one ``gc`` span in a traced engine's ring;
    an untraced engine installs no hook; deleting the traced engine
    takes its hook out of ``gc.callbacks``."""
    cfg, params = _model("internlm2-1.8b")
    gc.collect()
    before = list(gc.callbacks)
    bare = Engine(cfg, params, slots=1, max_len=64)
    assert gc.callbacks == before
    eng = Engine(cfg, params, slots=1, max_len=64, trace=True)
    hooks = [h for h in gc.callbacks if h not in before]
    assert len(hooks) == 1

    def gc_spans():
        return [e for e in eng.tracer.events()
                if e.kind == "span" and e.attrs["name"] == "gc"]

    gc.disable()
    try:
        n0 = len(gc_spans())
        gc.collect()
        spans = gc_spans()
    finally:
        gc.enable()
    assert len(spans) == n0 + 1
    last = spans[-1]
    assert last.attrs["generation"] == 2 and last.attrs["dur"] >= 0
    assert last.attrs["collected"] >= 0 and last.attrs["chunk"] == 0
    del bare, eng, spans, last
    gc.collect()
    assert hooks[0] not in gc.callbacks
    assert gc.callbacks == before


def test_chrome_trace_draws_spans_on_the_engine_and_gc_tracks():
    """Phase spans are complete events on the engine track, collections
    on a ``gc`` track of their own; the export passes the schema check
    and the fingerprint leaves spans out."""
    from benchmarks.check_trace import validate

    tr = Tracer()
    tr.record("submit", 0.0, rid=1)
    tr.record("span", 0.0, name="engine.submit", dur=0.001, chunk=0)
    tr.record("admit", 0.5, rid=1, slot=0, chunk=0)
    tr.record("span", 0.5, name="engine.admit", dur=0.002, chunk=0)
    tr.record("span", 0.6, name="gc", dur=0.25, generation=2, collected=3,
              chunk=0)
    tr.record("finish", 1.0, rid=1, slot=0, status="FINISHED")
    obj = to_chrome_trace(tr.events())
    assert validate(obj) == []
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"
          and e["cat"] == "host"]
    assert [(e["name"], e["tid"], e["dur"]) for e in xs] == [
        ("engine.submit", ENGINE_TID, 1000.0),
        ("engine.admit", ENGINE_TID, 2000.0),
        ("gc.gen2", GC_TID, 250000.0)]
    assert xs[2]["args"] == {"generation": 2, "collected": 3, "chunk": 0}
    names = {e["tid"]: e["args"]["name"] for e in obj["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names[GC_TID] == "gc"
    plain = Tracer()
    for e in tr.events():
        if e.kind != "span":
            plain.record(e.kind, e.ts, rid=e.rid, slot=e.slot, **e.attrs)
    assert tr.fingerprint() == plain.fingerprint()


def test_traced_engine_export_passes_the_schema_check():
    """An engine's own export, spans and collections included, passes
    ``benchmarks/check_trace.py``."""
    from benchmarks.check_trace import validate

    cfg, params = _model("internlm2-1.8b")
    eng = Engine(cfg, params, slots=2, max_len=64, trace=True,
                 clock=time.perf_counter)
    _workload(eng, n=4)
    gc.collect()
    obj = eng.export_trace()
    assert validate(obj) == []
    host = {e["name"] for e in obj["traceEvents"]
            if e["ph"] == "X" and e.get("cat") == "host"}
    assert host >= set(PHASES) | {"gc.gen2"}
