"""A tiny cell for CPU tests of the harness: a copy of the benchmark's
files beside a 2-layer, 64-wide internlm2 and a small chat mix."""

import contextlib
import io
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

CELL = "tiny.chat"
TINY = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab_size": 256}


def make(root: Path, limit: float = 0.05) -> Path:
    """Lay out ``root/BENCHMARK.json`` and ``root/bench``; returns the
    bench directory."""
    bench = root / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    (bench / "configs" / "tiny.json").write_text(json.dumps({
        "name": "tiny", "arch": "internlm2-1.8b",
        "model": {**TINY, "rope_theta": 1000000.0, "norm_eps": 1e-05},
        "overrides": TINY, "param_dtype": "bfloat16", "kv_dtype": "fp32"}))
    (bench / "traffic" / "tinychat.json").write_text(json.dumps({
        "block": 8, "arrivals": {"process": "poisson"}, "turns": {"dist": "uniform", "lo": 1, "hi": 2},
        "think_s": {"dist": "exponential", "mean": 0.5},
        "prefix": {"share": "pool", "count": 2,
                   "popularity": {"zipf_s": 1.0},
                   "len": {"dist": "uniform", "lo": 20, "hi": 40}},
        "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.5, "lo": 2,
                   "hi": 20},
        "output": {"dist": "uniform", "lo": 6, "hi": 12}}))
    (bench / "cells" / f"{CELL}.json").write_text(json.dumps({
        "engine": {"slots": 4, "max_len": 128, "page_size": 8,
                   "num_pages": 64, "sync_interval": 4, "prefill_budget": 8},
        "rate_rps": 4.0, "ramp_s": 0.5, "finish_due": True,
        "check": {"requests": 4, "min_tokens": 20,
                  "limits": {"max_gap": limit}}}))
    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    (bench / "peaks.json").write_text(json.dumps(peaks))
    top = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    top["configs"].append({"name": "tiny", "source": "test",
                           "file": "bench/configs/tiny.json", "reduced": [],
                           "why": "test"})
    top["workloads"].append({"name": CELL, "config": "tiny",
                             "traffic": "tinychat", "chips": 1,
                             "why": "test"})
    for m in top["end_to_end"]:
        if "workloads" in m and m["name"] != "tokens_per_s":
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(top))
    return bench


def run(bench: Path, *args: str) -> dict:
    """One in-process run of the tiny cell on the CPU; its result line."""
    import run as harness
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(["--workload", CELL, *args], chip=False,
                          bench_dir=bench)
    assert rc == 0, out.getvalue()[-3000:]
    lines = out.getvalue().strip().splitlines()
    result = json.loads(lines[-1])
    result["log"] = lines[:-1]
    return result
