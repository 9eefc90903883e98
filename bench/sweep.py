"""Find a cell's knee: offer its traffic at several fixed rates in one
process, on one engine, and print what each rate sustained.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --sweep 1.0,2.0,3.0

For each rate: the cell's ramp, then ``--seconds`` measured; then every
request still queued or running is cancelled and the engine drained
before the next rate.  A rate is sustained while the queue does not
grow through the window and the requests due in it finish.  It fixes
each cell's rate once, when the cell is added; measured runs never
sweep.
"""

from __future__ import annotations

import time
from typing import Any, Dict


def sweep(args, cell, cfg, device: Dict[str, Any], events) -> int:
    import jax
    import jax.numpy as jnp
    from repro.models import model_defs
    from repro.models import module as m
    from run import Served, drive, log, make_engine
    from stats import percentile
    from traffic import generate
    from weights import make_weights
    dtype = jnp.dtype(cell.config["param_dtype"])
    params = make_weights(m.abstract_params(model_defs(cfg), dtype),
                          args.seed, dtype)
    eng = make_engine(cfg, params, cell, trace=False)
    eng.warmup()
    jax.block_until_ready((eng.cache, eng.state))
    settings = cell.settings
    for rate in [float(r) for r in args.sweep.split(",")]:
        horizon = settings["ramp_s"] + args.seconds + 1.0
        arrivals = generate(cell.traffic, rate=rate, seed=args.seed,
                            horizon_s=horizon, vocab=cfg.vocab_size,
                            max_len=settings["engine"]["max_len"])
        base = time.perf_counter()
        served = [Served(a, base + a.due) for a in arrivals]
        loop = drive(eng, served, open_at=base + settings["ramp_s"],
                     seconds=args.seconds, finish_due=False, events=events)
        w0, w1, steps, late = loop.w0, loop.w1, loop.steps, loop.late
        due = [s for s in served if w0 <= s.due < w1]
        toks = sum(1 for s in served if s.req is not None
                   for t in s.req.token_times if w0 < t <= w1)
        fin = sum(1 for s in served if s.req is not None
                  and s.req.finish_time is not None
                  and w0 < s.req.finish_time <= w1)
        chunks = [b - a for a, b, ran in steps if ran and w0 < b <= w1]
        ttft = [s.ttft for s in due]
        tpot = [s.tpot for s in due]
        log(f"sweep rate {rate}: {toks / (w1 - w0):.1f} tokens/s, "
            f"{fin / (w1 - w0):.3f} finished/s, due {len(due)}, queue "
            f"{loop.queue_open} -> {len(eng.queue)}, ttft p50 "
            f"{percentile(ttft, 50)} p90 {percentile(ttft, 90)}, tpot p50 "
            f"{percentile(tpot, 50)} p90 {percentile(tpot, 90)}, chunk ms "
            f"{1e3 * sum(chunks) / max(len(chunks), 1):.1f}, late max "
            f"{max(late, default=0):.4f}")
        for s in served:
            if s.req is not None and s.req.status in ("QUEUED", "RUNNING",
                                                      "PREEMPTED"):
                s.req.cancel()
        eng.run(max_steps=eng.steps + 10 ** 9)
    return 0
