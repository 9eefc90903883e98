"""One run of one benchmark cell, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic
mix, serving settings and metric readers are files found by name (see
``spec.py``).  A run:

1. refuses anything but the accelerator the cell asks for (exit 2, no
   result line);
2. makes the model's weights on the device from ``--seed`` and builds
   ``repro.serve.engine.Engine`` with the cell's settings, then warms up
   the cell's shapes;
3. offers the cell's traffic open loop on the wall clock, at the cell's
   fixed rate: every request whose due time has passed is submitted,
   then ``Engine.step()`` runs; an idle engine sleeps to the next due
   time.  Latencies count from the due time;
4. lets the traffic ramp for the cell's ``ramp_s``, then measures for
   ``--seconds`` (set-up is everything before: imports, weights,
   warm-up and ramp); a cell judged on tails keeps serving, with the
   arrivals going on, until every request due in the window is done;
5. with ``--trace 1``, records the device with JAX's profiler and the
   engine's lifecycle tracer through the same window, and reports the
   cell's per-layer metrics instead of its end-to-end ones;
6. frees the engine and compares a sample of the served tokens with the
   plain float32 reference (``reference.py``; ``check.py``);
7. prints, last on standard output, one JSON line: ``correct``,
   ``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
   ``breakdown``, and last ``check``, the numbers compared beside their
   limits; the same numbers end standard error.

Two options serve calibration, and the benchmark's own runs never give
them: ``--control 1`` puts the fp8 control in the program's place in the
comparison, on the same sample once the run is over, so that ``correct``
is the control's verdict and has to read false (``check.py``); and
``--sweep r1,r2,...`` offers the traffic at several rates to find the
knee (``sweep.py``).
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional, Sequence  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))

EXIT_NO_CHIP = 2
TRACE_S = 5.0        # the profiler records the window's last seconds
DRAIN_CAP_S = 60.0   # how long a tail cell serves past the window


def log(*parts: Any) -> None:
    print(*parts, flush=True)


def parse_args(argv: Optional[Sequence[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--control", type=int, choices=[0, 1], default=0)
    ap.add_argument("--sweep", default="")
    return ap.parse_args(argv)


def require_chip(chips: int) -> Dict[str, Any]:
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform == "cpu":
        raise NoChip(f"JAX found no accelerator (platform {d.platform!r})")
    if len(devices) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX found {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


class NoChip(RuntimeError):
    pass


def enable_cache(root: Path) -> str:
    """JAX's persistent compilation cache: where the environment says,
    else at a fixed path inside the checkout.  Every program is cached,
    however fast it compiled, so that set-up is the same on every run
    after the first."""
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".bench_cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileEvents:
    """Counts cache hits and misses, and traces and compiles while
    ``window`` is set."""

    def __init__(self) -> None:
        self.hits = self.misses = 0
        self.window = False
        self.in_window = 0

    def event(self, name: str, **_: Any) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def duration(self, name: str, _secs: float, **_: Any) -> None:
        if self.window and name in ("/jax/core/compile/jaxpr_trace_duration",
                                    "/jax/core/compile/backend_compile_duration"):
            self.in_window += 1


def build_config(config: Dict[str, Any]):
    """The program's configuration for a configuration file: the registry
    entry with the file's overrides, checked against every number of the
    file's ``model``."""
    from repro.configs import get_config
    from repro.configs.base import uniform_blocks, validate
    cfg = get_config(config["arch"])
    over = dict(config.get("overrides", {}))
    if "num_layers" in over:
        over["blocks"] = uniform_blocks(over["num_layers"])
    cfg = validate(dataclasses.replace(cfg, **over))
    got = {k: (cfg.resolved_head_dim if k == "head_dim" else getattr(cfg, k))
           for k in config["model"]}
    if got != config["model"]:
        raise ValueError(f"program config {got} != file {config['model']}")
    return cfg


@dataclasses.dataclass
class Served:
    """One request as the open loop offered it."""
    arrival: Any
    due: float                       # absolute, perf_counter seconds
    req: Any = None
    submitted: Optional[float] = None
    rejected: bool = False

    @property
    def ttft(self) -> float:
        t = None if self.req is None else self.req.first_token_time
        return math.inf if t is None or self.rejected else t - self.due

    @property
    def done(self) -> bool:
        return self.req is not None and self.req.status == "FINISHED"

    @property
    def tpot(self) -> float:
        r = self.req
        if not self.done or len(r.token_times) < 2:
            return math.inf
        return (r.token_times[-1] - r.first_token_time) / (
            len(r.token_times) - 1)


@dataclasses.dataclass
class RunRecord:
    """Everything a metric reader may read about one run."""
    cell: Any
    model: Dict[str, Any]
    peaks: Dict[str, Any]
    settings: Dict[str, Any]
    setup_time: float
    w0: float
    w1: float
    served: List[Served]
    steps: List[tuple]               # (start, end, ran_a_chunk)
    events: List[Any] = dataclasses.field(default_factory=list)
    traced: Optional[tuple] = None   # host (start, end) of the traced part
    device_trace: Any = None
    replay: Any = None

    @property
    def window_s(self) -> float:
        return self.w1 - self.w0

    @property
    def due_in_window(self) -> List[Served]:
        return [s for s in self.served if self.w0 <= s.due < self.w1]

    def tokens_in_window(self) -> int:
        return sum(1 for s in self.served if s.req is not None
                   for t in s.req.token_times if self.w0 < t <= self.w1)


@dataclasses.dataclass
class Loop:
    """What the open loop saw: the window, the traced part of it, every
    ``Engine.step()`` and how late each submission ran."""
    w0: float
    w1: float
    steps: List[tuple]               # (start, end, ran_a_chunk)
    late: List[float]
    queue_open: int
    traced: Optional[tuple] = None   # (start, end) of the traced part


def drive(eng, served: List[Served], *, open_at: float, seconds: float,
          finish_due: bool, events: CompileEvents,
          trace_dir: Optional[str] = None) -> Loop:
    """The open loop.  With ``finish_due`` it serves on past the window,
    for up to ``DRAIN_CAP_S``, until every request due in the window is
    done.  With ``trace_dir``, the profiler records the last ``TRACE_S``
    seconds of the window, inside the host span ``bench.trace``; it
    starts a second before that span and stops when the window
    closes."""
    import jax
    from repro.serve.engine import Request
    clock = time.perf_counter
    i, n = 0, len(served)
    steps: List[tuple] = []
    late: List[float] = []
    w0 = w1 = queue_open = None
    profiling = False
    span = traced0 = traced = None
    while True:
        now = clock()
        while i < n and served[i].due <= now:
            s = served[i]
            a = s.arrival
            s.req = Request(rid=a.rid, prompt=list(a.prompt),
                            max_new_tokens=a.max_new)
            s.rejected = eng.submit(s.req) is not None
            s.submitted = clock()
            late.append(s.submitted - s.due)
            i += 1
        now = clock()
        if w0 is None and now >= open_at:
            w0 = now
            queue_open = len(eng.queue)
            events.window = True
        if trace_dir and w0 is not None and w1 is None:
            t_span = w0 + max(seconds - TRACE_S, 0.0)
            if not profiling and now >= t_span - 1.0:
                t = clock()
                # host spans only: no per-call Python frames, no HLO
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                profiling = True
                log(f"trace: profiler started in {clock() - t:.3f} s")
                now = clock()
            if span is None and now >= t_span:
                span = jax.profiler.TraceAnnotation("bench.trace")
                span.__enter__()
                traced0 = now = clock()
        if w0 is not None and w1 is None and now >= w0 + seconds:
            w1 = now
            events.window = False
            if span is not None:
                span.__exit__(None, None, None)
                traced = (traced0, w1)
                t = clock()
                jax.profiler.stop_trace()
                log(f"trace: profiler stopped in {clock() - t:.3f} s")
        if w1 is not None:
            if not finish_due or now >= w1 + DRAIN_CAP_S or all(
                    s.done or s.rejected for s in served
                    if w0 <= s.due < w1):
                break
        c0, t0 = eng.chunks, clock()
        eng.step()
        t1 = clock()
        ran = eng.chunks > c0
        steps.append((t0, t1, ran))
        if not ran:
            wake = [served[i].due] if i < n else []
            wake.append(open_at if w0 is None else
                        (w0 + seconds if w1 is None else t1 + 0.05))
            pause = min(wake) - clock()
            if pause > 0:
                time.sleep(pause)
    return Loop(w0, w1, steps, late, queue_open, traced)


def make_engine(cfg, params, cell, trace: bool):
    from repro.serve.engine import Engine
    from repro.serve.trace import Tracer
    return Engine(cfg, params, **cell.settings["engine"],
                  kv_dtype=cell.config["kv_dtype"], chunked_prefill=True,
                  prefix_sharing=True, paged_kernel="auto",
                  clock=time.perf_counter,
                  trace=Tracer(capacity=1 << 22) if trace else None)


def warm_page_copy(eng) -> None:
    """Compile the copy-on-write page copy that the first partial-page
    prefix hit makes: copy the shared pool's trash page onto itself,
    which changes nothing.  Traffic of unique prompts needs it too: two
    random prompts that start with the same token share one."""
    import jax.numpy as jnp
    key = eng.scheduler.share_key
    trash = next(g.trash_page for g in eng.spec.groups if g.key == key)
    eng.cache = eng.executor.copy_page(eng.cache, jnp.int32(trash),
                                       jnp.int32(trash), key)


def serve_once(cell, cfg, params, seed: int, seconds: float, trace: bool,
               events: CompileEvents, run_dir: Path, t_setup0: float):
    """Engine, warm-up, open loop.  Returns (record, engine, loop)."""
    import jax
    from traffic import generate
    settings = cell.settings
    eng = make_engine(cfg, params, cell, trace)
    t = time.perf_counter()
    eng.warmup()
    warm_page_copy(eng)
    jax.block_until_ready((eng.cache, eng.state))
    log(f"setup: warm-up {time.perf_counter() - t:.3f} s "
        f"(decode executables {eng.decode_compiles}, pool-direct kernel "
        f"{eng.paged_kernel}, pages {eng.spec.num_pages})")
    finish_due = bool(settings.get("finish_due", False))
    horizon = settings["ramp_s"] + seconds + (
        DRAIN_CAP_S if finish_due else 1.0)
    arrivals = generate(cell.traffic, rate=settings["rate_rps"], seed=seed,
                        horizon_s=horizon, vocab=cfg.vocab_size,
                        max_len=settings["engine"]["max_len"])
    base = time.perf_counter()
    served = [Served(a, base + a.due) for a in arrivals]
    trace_dir = str(run_dir / "profile") if trace else None
    loop = drive(eng, served, open_at=base + settings["ramp_s"],
                 seconds=seconds, finish_due=finish_due, events=events,
                 trace_dir=trace_dir)
    setup = loop.w0 - t_setup0
    log(f"setup: ramp {loop.w0 - base:.3f} s, set-up in all {setup:.3f} s")
    rec = RunRecord(cell=cell, model=cell.config["model"], peaks={},
                    settings=settings, setup_time=setup, w0=loop.w0,
                    w1=loop.w1, served=served, steps=loop.steps,
                    traced=loop.traced)
    if trace:
        rec.events = eng.tracer.events()
        if eng.tracer.dropped:
            raise RuntimeError(f"tracer dropped {eng.tracer.dropped} events")
    return rec, eng, loop


def summarize(rec: RunRecord, late: List[float], events: CompileEvents,
              eng) -> Dict[str, Any]:
    from stats import percentile
    due = rec.due_in_window
    late_s = sorted(late)
    log(f"window: {rec.window_s:.3f} s, {len(due)} requests due, "
        f"{rec.tokens_in_window()} tokens drained, "
        f"{sum(1 for s in rec.steps if s[2] and rec.w0 < s[1] <= rec.w1)} "
        f"chunks; compilations inside the window: {events.in_window}")
    if late_s:
        log(f"open loop: submission late by median "
            f"{percentile(late_s, 50) * 1e3:.3f} ms, max "
            f"{late_s[-1] * 1e3:.3f} ms over {len(late_s)} submissions")
    ttft = [s.ttft for s in due]
    tpot = [s.tpot for s in due]
    log(f"tails over requests due in the window: ttft p50 "
        f"{percentile(ttft, 50)} p90 {percentile(ttft, 90)} max "
        f"{max(ttft, default=None)}; tpot p50 {percentile(tpot, 50)} p90 "
        f"{percentile(tpot, 90)}; finished {sum(s.done for s in due)}, "
        f"rejected {sum(s.rejected for s in due)}, queue at the end "
        f"{len(eng.queue)}")
    ps = eng.prefix_stats()
    log(f"engine: {eng.chunks} chunks, {eng.host_syncs} host syncs, prefix "
        f"hits {ps['prefix_hits']} of {ps['admissions']} admissions, "
        f"{ps['prefill_tokens_skipped']} prompt tokens from cached pages, "
        f"preemptions {eng.fault_counters['preemptions']}, leaked pages "
        f"{eng.leaked_pages()}")
    failed = [s for s in due if s.rejected or (
        s.req is not None and s.req.status not in ("FINISHED", "RUNNING",
                                                   "QUEUED", "PREEMPTED"))]
    if rec.settings.get("finish_due"):
        failed = [s for s in due if not s.done]
    return {"attempted": len(due), "failed": len(failed)}


def read_metrics(metrics, rec: RunRecord) -> Dict[str, Any]:
    out = {}
    for m in metrics:
        v = m.read(rec)
        if v is None or (isinstance(v, float) and not math.isfinite(v)):
            log(f"metric {m.name}: nothing to read")
            continue
        out[m.name] = {"value": float(v), "unit": m.unit}
    return out


def memory_peak() -> int:
    import jax
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def one_run(args, cell, cfg, device: Dict[str, Any],
            events: CompileEvents) -> Dict[str, Any]:
    """Weights, engine, traffic, metrics and check; the result line."""
    import jax
    import jax.numpy as jnp
    from check import check_served
    from repro.models import model_defs
    from repro.models import module as m
    from spec import load_peaks
    from weights import flat_weights, make_weights
    peaks = load_peaks(device["kind"], args.bench_dir)
    t = time.perf_counter()
    shapes = m.abstract_params(model_defs(cfg), jnp.dtype(
        cell.config["param_dtype"]))
    params = make_weights(shapes, args.seed,
                          jnp.dtype(cell.config["param_dtype"]))
    jax.block_until_ready(params)
    log(f"setup: weights {time.perf_counter() - t:.3f} s (seed {args.seed})")
    run_dir = args.root / ".bench_cache" / "runs" / cell.name
    if args.trace:
        shutil.rmtree(run_dir / "profile", ignore_errors=True)
    run_dir.mkdir(parents=True, exist_ok=True)
    rec, eng, loop = serve_once(cell, cfg, params, args.seed, args.seconds,
                                bool(args.trace), events, run_dir,
                                PROCESS_START)
    rec.peaks = peaks
    counts = summarize(rec, loop.late, events, eng)
    device = dict(device, memory_peak_bytes=memory_peak())
    result: Dict[str, Any] = {}
    if args.trace:
        from devtrace import reduce_trace
        from work import replay_events
        t = time.perf_counter()
        rec.device_trace = reduce_trace(run_dir / "profile", "bench.trace")
        rec.replay = replay_events(rec.events, rec.served,
                                   eng.sync_interval, eng.prefill_budget)
        log(f"trace: read in {time.perf_counter() - t:.3f} s; traced "
            f"{rec.device_trace.window_s:.3f} s of the window, "
            f"{sum(len(o) for o in rec.device_trace.ops)} device ops; "
            f"replay: {len(rec.replay.steps(rec.w0, rec.w1))} micro-steps "
            f"in the window, {rec.replay.unmatched} drained tokens not "
            f"placed")
        device.update(busy_s=rec.device_trace.busy_s,
                      window_s=rec.device_trace.window_s)
        result["breakdown"] = rec.device_trace.breakdown()
        metrics = read_metrics(cell.per_layer, rec)
    else:
        metrics = read_metrics(cell.end_to_end, rec)
    served = [s for s in rec.served
              if s.req is not None and len(s.req.out_tokens) >= 2]
    del eng
    gc.collect()
    check = check_served(flat_weights(params), cell, served, args.seed,
                         control=bool(args.control))
    return {"correct": check["correct"], "attempted": counts["attempted"],
            "failed": counts["failed"], "metrics": metrics,
            "device": device, **result, "check": check["numbers"]}


def main(argv: Optional[Sequence[str]] = None, chip: bool = True,
         bench_dir: Path = BENCH_DIR) -> int:
    """One run.  ``chip=False`` skips the look for an accelerator and the
    persistent cache (CPU tests of the harness); ``bench_dir`` is where
    the cell's files are found."""
    args = parse_args(argv)
    args.root, args.bench_dir = bench_dir.parent, bench_dir
    import jax
    from spec import load_cell
    try:
        cell = load_cell(args.workload, bench_dir)
        device = (require_chip(cell.chips) if chip else
                  {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())})
    except NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return EXIT_NO_CHIP
    log(f"device: {device['kind']} x{device['count']} ({device['platform']})")
    cache_dir = enable_cache(args.root) if chip else "off"
    events = CompileEvents()
    jax.monitoring.register_event_listener(events.event)
    jax.monitoring.register_event_duration_secs_listener(events.duration)
    cfg = build_config(cell.config)
    log(f"model: {cell.config['name']}: {cell.config['model']}, "
        f"{cell.config['param_dtype']} weights, {cell.config['kv_dtype']} "
        f"pools; cell {cell.name}: {cell.settings['engine']}, "
        f"{cell.settings['rate_rps']} requests/s")
    if args.sweep:
        from sweep import sweep
        return sweep(args, cell, cfg, device, events)
    line = one_run(args, cell, cfg, device, events)
    log(f"compile cache: {cache_dir}: {events.hits} hits, "
        f"{events.misses} misses")
    for name, v in line["check"].items():
        print(f"check {name}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
