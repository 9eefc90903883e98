"""The trace reducer on hand-made op intervals and on a small recorded
TPU trace (``data/small.xplane.pb``: a Pallas kernel and a matmul, run
three times inside a ``bench.trace`` host span on one v5e chip)."""

import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
from devtrace import DeviceTrace, Op  # noqa: E402

KERNEL = ('%closed_call.3 = bf16[32,512,128]{2,1,0} custom-call(s32[32,128] '
          '%a), custom_call_target="tpu_custom_call"')


def hand_trace():
    ops = [Op("%while.7 = (s32[]) while(%t)", 0, 1000),        # container
           Op("%fusion.1 = bf16[8,64]{1,0} fusion(%p)", 100, 300),
           Op(KERNEL, 300, 700),
           Op("%fusion.2 = bf16[8,64]{1,0} fusion(%q)", 650, 900),
           Op("%copy.4 = f32[8]{0} copy(%r)", 1500, 1600)]
    host = [(0, 2000, "bench.step"), (1000, 1400, "host.work")]
    return DeviceTrace(window=(0, 2000), ops=[ops], host=host)


def test_busy_idle_and_kernel_time_by_hand():
    t = hand_trace()
    assert t.window_s == pytest.approx(2e-6)
    assert t.busy_s == pytest.approx(1.1e-6)       # [0,1000] + [1500,1600]
    assert t.time_of(lambda o: "tpu_custom_call" in o.name) == \
        pytest.approx(4e-7)
    assert t.idle_gaps() == [(1000, 1500), (1600, 2000)]


def test_window_clips_the_intervals():
    t = hand_trace()
    t.window = (200, 800)
    assert t.busy_s == pytest.approx(6e-7)
    assert t.time_of(lambda o: "tpu_custom_call" in o.name) == \
        pytest.approx(4e-7)


def test_breakdown_names_ops_and_gaps():
    b = hand_trace().breakdown()
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "fusion bf16[8,64]"
    assert b["device_ops"][0][1] == pytest.approx(4.5e-7)
    assert "closed_call:tpu_custom_call bf16[32,512,128]" in names
    assert not any(n.startswith("while") for n in names)
    gaps = dict(b["idle_gaps"])
    assert gaps["host.work"] == pytest.approx(5e-7)    # middle at 1250
    assert gaps["bench.step"] == pytest.approx(4e-7)   # middle at 1800


def test_op_label():
    assert devtrace.op_label("%fusion.12 = bf16[32,8192]{1,0:T(8,128)} "
                             "fusion(%a)") == "fusion bf16[32,8192]"
    assert devtrace.op_label(KERNEL).startswith("closed_call:tpu_custom_call")


def perfetto_events():
    with gzip.open(DATA / "small.perfetto.json.gz") as f:
        obj = json.load(f)
    return obj["traceEvents"] if isinstance(obj, dict) else obj


def test_recorded_trace_against_its_perfetto_twin():
    """The reducer's numbers on the recorded trace equal the same numbers
    worked out independently from the profiler's Perfetto export of the
    same profile."""
    t = devtrace.load(DATA, "bench.trace")
    evs = perfetto_events()
    pids = {e["pid"]: e["args"]["name"] for e in evs
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    tids = {(e["pid"], e["tid"]): e["args"]["name"] for e in evs
            if e.get("ph") == "M" and e.get("name") == "thread_name"}
    span = next(e for e in evs if e.get("name") == "bench.trace")
    lo, hi = span["ts"], span["ts"] + span["dur"]
    ops = [e for e in evs if e.get("ph") == "X"
           and pids.get(e["pid"], "").startswith("/device:TPU:0")
           and tids.get((e["pid"], e["tid"])) == "XLA Ops"]
    clipped = [(max(e["ts"], lo), min(e["ts"] + e["dur"], hi), e["name"])
               for e in ops if e["ts"] + e["dur"] > lo and e["ts"] < hi]
    iv = sorted((s, e) for s, e, _ in clipped)
    busy, cur = 0.0, None
    for s, e in iv:
        if cur is None or s > cur[1]:
            busy += 0 if cur is None else cur[1] - cur[0]
            cur = [s, e]
        else:
            cur[1] = max(cur[1], e)
    busy += cur[1] - cur[0]
    # the export names ops short: the Pallas call is "f.1", the matmul
    # "convolution_tanh_fusion"
    kern = sum(e - s for s, e, n in clipped if n.startswith("f."))
    assert t.window_s == pytest.approx((hi - lo) * 1e-6, rel=1e-6)
    assert t.busy_s == pytest.approx(busy * 1e-6, rel=1e-3)
    assert t.time_of(lambda o: "tpu_custom_call" in o.name) == \
        pytest.approx(kern * 1e-6, rel=1e-3)
    assert 0 < t.busy_s < t.window_s and kern > 0
