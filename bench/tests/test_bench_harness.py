"""The harness: BENCHMARK.json to its schema, files found by name, no
run without a chip, and one whole run of a tiny cell on the CPU."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
import spec  # noqa: E402

TOP = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in TOP["workloads"]]


def test_benchmark_json_keys_and_names():
    assert set(TOP) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert TOP["command"][:2] == ["python3", "bench/run.py"]
    assert TOP["paths"] == ["bench"]
    assert 1 <= TOP["run_seconds"] <= 51
    metrics = TOP["end_to_end"] + TOP["per_layer"]
    names = [x["name"] for x in TOP["configs"] + TOP["workloads"] + metrics]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for m in TOP["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for c in TOP["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
    layers = {m["layer"] for m in TOP["per_layer"]}
    assert all("\n" not in x for x in layers)


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_what_it_must(cell):
    c = spec.load_cell(cell)
    e2e = [m.name for m in c.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in TOP["per_layer"]:
        if cell in m.get("workloads", []):
            assert m["moves"] in e2e
    assert c.chips == 1
    for key in ("engine", "rate_rps", "ramp_s", "check"):
        assert key in c.settings
    assert all(callable(m.read) for m in c.end_to_end + c.per_layer)


def test_unknown_names_raise():
    with pytest.raises(KeyError, match="unknown workload"):
        spec.load_cell("no-such-cell")
    with pytest.raises(KeyError, match="no peaks"):
        spec.load_peaks("TPU v0 imaginary")
    assert spec.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12


def test_no_cell_config_or_metric_name_in_harness_code():
    names = [x["name"] for x in TOP["configs"] + TOP["workloads"]]
    names += [w["traffic"] for w in TOP["workloads"]]
    names += [m["name"] for m in TOP["end_to_end"] + TOP["per_layer"]]
    for path in BENCH.glob("*.py"):
        text = path.read_text()
        for n in names:
            assert n not in text, (path.name, n)


def test_a_run_without_a_chip_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())
    assert "no accelerator" in p.stderr


def test_tiny_cell_runs_whole_on_cpu(tmp_path):
    bench = bench_tiny.make(tmp_path)
    res = bench_tiny.run(bench, "--seed", str(2 ** 33 + 1), "--seconds",
                         "2", "--trace", "0")
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-2] == "check"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "ttft_p85_s", "tpot_p85_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["check"]["max_gap"]["limit"] == 0.05
    assert any("compilations inside the window: 0" in x for x in res["log"])


def test_a_chance_prefix_hit_compiles_nothing_after_warmup():
    """Two unique prompts that share their first token make a one-token
    prefix hit and a copy-on-write page copy; the harness's warm-up has
    compiled it, so serving them compiles nothing."""
    import dataclasses
    import time
    import jax
    import jax.numpy as jnp
    import run as harness
    import weights
    from repro.configs import get_config
    from repro.configs.base import uniform_blocks
    from repro.models import model_defs
    from repro.models import module as m
    from repro.serve.engine import Engine, Request
    cfg = dataclasses.replace(get_config("internlm2-1.8b"),
                              blocks=uniform_blocks(2), **bench_tiny.TINY)
    params = weights.make_weights(
        m.abstract_params(model_defs(cfg), jnp.bfloat16), 1, jnp.bfloat16)
    eng = Engine(cfg, params, slots=4, max_len=128, page_size=8,
                 num_pages=64, sync_interval=4, prefill_budget=8,
                 kv_dtype="fp32", chunked_prefill=True, prefix_sharing=True,
                 paged_kernel="auto", clock=time.perf_counter)
    eng.warmup()
    harness.warm_page_copy(eng)
    events = harness.CompileEvents()
    jax.monitoring.register_event_duration_secs_listener(events.duration)
    events.window = True
    for rid, tail in enumerate((range(10, 30), range(40, 60))):
        eng.submit(Request(rid=rid, prompt=[5, *tail], max_new_tokens=6))
        eng.run(max_steps=eng.steps + 1000)
    events.window = False
    assert eng.prefix_stats()["cow_copies"] == 1
    assert events.in_window == 0

