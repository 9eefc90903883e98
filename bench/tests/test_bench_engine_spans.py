"""The readers of the engine's phase spans and chunk row counters
(``boundary_host_ms``, ``row_use``), on hand-built events, on the events
of a tiny served run, and on a program that records neither."""

import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run as harness  # noqa: E402
import spec  # noqa: E402
from repro.serve.trace import Tracer  # noqa: E402

boundary_host_ms = spec.load_reader(BENCH, "boundary_host_ms")
row_use = spec.load_reader(BENCH, "row_use")


def record(events, w0, w1):
    return harness.RunRecord(cell=None, model={}, peaks={}, settings={},
                             setup_time=0.0, w0=w0, w1=w1, served=[],
                             steps=[], events=events)


def test_readers_by_hand():
    tr = Tracer()
    tr.record("span", 0.5, name="engine.reap", dur=9.0, chunk=0)  # before
    for c in (1, 2):
        t = float(c)
        tr.record("span", t, name="engine.reap", dur=0.001, chunk=c)
        tr.record("span", t, name="engine.admit", dur=0.002, chunk=c)
        tr.record("span", t, name="engine.dispatch", dur=0.003, chunk=c)
        tr.record("span", t, name="engine.wait", dur=0.5, chunk=c)
        tr.record("span", t, name="gc", dur=0.7, generation=2,
                  collected=0, chunk=c)
        tr.record("chunk", t + 0.6, chunk=c, rows_run=1024,
                  prefill_rows=100 * c, decode_rows=28)
        tr.record("span", t + 0.6, name="engine.drain", dur=0.004, chunk=c)
    tr.record("span", 2.7, name="engine.submit", dur=0.002, chunk=2)
    tr.record("chunk", 3.6, chunk=3, rows_run=1024, prefill_rows=0,
              decode_rows=32)                                   # after
    rec = record(tr.events(), 0.9, 3.0)
    # (2 x (1 + 2 + 3 + 4) + 2) ms over 2 chunks; wait and gc left out
    assert boundary_host_ms(rec) == pytest.approx(11.0)
    # (100 + 28 + 200 + 28) / 2048
    assert row_use(rec) == pytest.approx(100.0 * 356 / 2048)


def test_readers_report_nothing_without_events():
    assert boundary_host_ms(record([], 0.0, 10.0)) is None
    assert row_use(record([], 0.0, 10.0)) is None
    # a program whose chunk events carry no counters and which records no
    # phase spans: chunks in the window, nothing to read
    tr = Tracer()
    for c in range(3):
        tr.record("chunk", 1.0 + c, chunk=c, live_slots=4, queue_depth=0,
                  pages_in_use=8)
    rec = record(tr.events(), 0.0, 10.0)
    assert boundary_host_ms(rec) is None and row_use(rec) is None


def test_readers_on_a_served_run():
    """On a tiny engine's own events: host time per boundary is the
    non-wait phases of every step over its chunks, and the rows that
    carried a token are what was admitted and drained."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, reduced
    from repro.models import model_defs
    from repro.models import module as m
    from repro.serve.engine import Engine, Request
    cfg = reduced(get_config("internlm2-1.8b"))
    params = m.init_params(model_defs(cfg), jax.random.PRNGKey(0),
                           jnp.float32)
    eng = Engine(cfg, params, slots=2, max_len=64, page_size=8,
                 sync_interval=4, prefill_budget=8, chunked_prefill=True,
                 trace=True, clock=time.perf_counter)
    eng.warmup()
    w0 = time.perf_counter()
    for i in range(4):
        eng.submit(Request(rid=i, prompt=[3 + i] * (5 + 3 * i),
                           max_new_tokens=6))
    done = eng.run(max_steps=10_000)
    rec = record(eng.tracer.events(), w0, time.perf_counter())
    evs = rec.events
    admits = [e for e in evs if e.kind == "admit"]
    host = sum(e.attrs["dur"] for e in evs if e.kind == "span"
               and e.attrs["name"] in ("engine.submit", "engine.reap",
                                       "engine.admit", "engine.dispatch",
                                       "engine.drain"))
    assert boundary_host_ms(rec) == pytest.approx(1e3 * host / eng.chunks)
    useful = sum(e.attrs["plen"] - e.attrs["suffix_start"] for e in admits)
    useful += sum(len(r.out_tokens) for r in done) - len(admits)
    ran = eng.chunks * 2 * 8 * 4
    assert row_use(rec) == pytest.approx(100.0 * useful / ran)
    assert 0 < row_use(rec) < 100
