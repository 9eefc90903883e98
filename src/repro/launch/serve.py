"""Batched serving driver.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b \
        --requests 6 --max-new 12

``--smoke`` (the default) serves the arch's ``reduced()`` CPU-scale twin
with fp32 weights.  ``--no-smoke`` serves the published config at full
width; on a TPU give it ``--param-dtype bfloat16`` (internlm2-1.8b's
≈1.9 B parameters take ≈3.8 GB in bf16 on one v5e chip).  Weights are
random.

``--warmup`` pre-compiles every full-prefill bucket, the jitted cache
splice, and the fused decode chunk before the first request arrives, so
the steady-state loop runs one dispatch per ``--sync-interval`` decode
steps with zero per-token host syncs (see docs/serving.md).  Suffix-
prefill executables (prefix hits) still compile lazily on the first hit
per (suffix bucket, ctx bucket) shape — so with the default shared-
prefix workload the first timed run includes one such compile; re-run or
lengthen the workload for steady-state tok/s.

Prefix sharing is on by default for sharing-capable archs (pure
full-attention stacks): requests whose prompts share a cached prefix ride
on refcounted shared pages and prefill only their suffix.  The default
workload sends every request the same prompt head, so the effect shows up
directly in the printed hit rate / pages summary; ``--no-prefix-sharing``
restores exclusive page ownership for comparison.

``--spec-draft ngram --spec-k 4`` turns on speculative decoding
(serve/spec): each fused chunk step drafts K tokens per slot (prompt-
lookup n-gram drafter, or a reduced draft model when an arch name is
given), verifies all K+1 positions in one multi-query paged dispatch,
and commits a variable number via on-device rejection sampling — output
tokens are identical to the non-speculative engine at temperature 0.
The summary prints the measured acceptance rate and tokens per verify
step.

``--policy slo`` switches the scheduler to least-slack-first SLO
ordering (serve/scheduler.py classes: interactive > batch >
best_effort) with class-aware preemption victims and dynamic prefill-
budget throttling; ``--slo-class`` tags the synthetic workload, and
``--traffic poisson:SEED`` / ``--traffic bursty:SEED`` replays a
seeded deterministic mixed-class arrival trace (serve/traffic.py) on a
virtual clock instead, printing per-class TTFT/TPOT percentiles and
goodput from ``Engine.latency_stats()``.

``--trace out.json`` turns on the request-lifecycle tracer
(serve/trace.py): every submit/admit/preempt/resume/finish transition
is recorded host-side at chunk boundaries (zero added device syncs)
and exported as a Chrome-trace/Perfetto timeline — per-slot tracks,
flow arrows following each request across preemption, and counter
tracks for pool occupancy and queue depth.  See docs/observability.md.
"""

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the arch's reduced() smoke twin (default); "
                         "--no-smoke serves the published config")
    ap.add_argument("--param-dtype", choices=["float32", "bfloat16"],
                    default="float32",
                    help="weight dtype (activations follow it)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64,
                    help="logical per-slot token cap (widest page-table "
                         "width x page size)")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per KV page (serve/cache.py paged pools)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page budget of the widest (full-attention) pool "
                         "group; default slots*max_len/page_size (the old "
                         "dense cache's token capacity).  Sliding-window "
                         "groups are always window-sized: slots x "
                         "ceil(window/page_size) pages each")
    ap.add_argument("--kv-dtype", choices=["auto", "fp32", "int8",
                                           "fp8_e4m3"], default="auto",
                    help="paged KV pool storage precision: 8-bit pools "
                         "('int8'/'fp8_e4m3') store per-page fp32 scales "
                         "alongside and dequantize inside the attention "
                         "read (in-kernel on TPU).  'auto'/'fp32' keep "
                         "full-precision pools")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable radix prefix sharing / copy-on-write "
                         "page reuse (exclusive page ownership)")
    ap.add_argument("--paged-kernel", choices=["auto", "on", "off"],
                    default="auto",
                    help="decode-attention pool reads: 'on' forces the "
                         "pool-direct path (Pallas kernel on TPU, "
                         "pool-wide masked attention elsewhere), 'off' "
                         "forces gather-then-attend (parity debugging), "
                         "'auto' picks the kernel on TPU and gather "
                         "elsewhere")
    ap.add_argument("--spec-draft", default="off",
                    help="speculative decoding drafter: 'off' (default), "
                         "'ngram' (prompt-lookup, no second model), or a "
                         "configs/ arch name served reduced as the draft "
                         "model (attention-only archs only)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per verify step (the fused chunk "
                         "verifies K+1 positions per slot per dispatch)")
    ap.add_argument("--shared-prefix", type=int, default=12,
                    help="length of the prompt head shared by every "
                         "request in the synthetic workload (0 = fully "
                         "distinct prompts)")
    ap.add_argument("--chunked-prefill", choices=["auto", "on", "off"],
                    default="auto",
                    help="fused mixed prefill+decode chunks (Sarathi-"
                         "style): prompts stream through the decode "
                         "executable prefill_budget tokens per micro-"
                         "step — no prefill executables at all. 'auto' "
                         "enables it whenever the arch is paged-KV "
                         "capable with no model drafter")
    ap.add_argument("--prefill-budget", type=int, default=32,
                    help="prompt tokens each fused chunk micro-step "
                         "spends per admitting slot (the TTFT-vs-decode-"
                         "jitter knob; only with chunked prefill)")
    ap.add_argument("--sync-interval", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--warmup", action="store_true",
                    help="pre-compile prefill buckets + decode chunk")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="run under a seeded fault-injection schedule "
                         "(serve/chaos.py smoke preset: admission "
                         "denials, preemption storms, slot stalls, "
                         "CoW degradation); asserts every request "
                         "reaches a clean terminal status and zero "
                         "pages leak at drain")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bound the admission queue; overflow is "
                         "handled by --shed-policy")
    ap.add_argument("--shed-policy", default="reject",
                    choices=["reject", "block", "evict-lru-prefix"],
                    help="load-shedding at a full admission queue")
    ap.add_argument("--ttl", type=float, default=None,
                    help="per-request deadline in seconds from submit; "
                         "expired requests are reaped as TIMED_OUT at "
                         "the next chunk boundary")
    ap.add_argument("--policy", default="fifo", choices=["fifo", "slo"],
                    help="admission/victim policy: 'slo' orders the "
                         "queue least-slack-first by SLO class "
                         "(interactive > batch > best_effort), picks "
                         "lowest-class preemption victims, and throttles "
                         "non-interactive prefill budgets when an "
                         "interactive TTFT slack goes negative")
    ap.add_argument("--slo-class", default=None,
                    choices=["interactive", "batch", "best_effort"],
                    help="SLO class for every synthetic request "
                         "(default: best_effort; carries per-class "
                         "TTFT/TPOT targets from serve/scheduler.py)")
    ap.add_argument("--traffic", default=None, metavar="PROC:SEED",
                    help="replace the synthetic workload with a seeded "
                         "deterministic arrival trace from "
                         "serve/traffic.py: 'poisson:SEED' or "
                         "'bursty:SEED' (mixed SLO classes and lengths, "
                         "virtual-clock replay, prints per-class "
                         "TTFT/TPOT percentiles + goodput)")
    ap.add_argument("--traffic-rate", type=float, default=8.0,
                    help="arrivals per virtual clock unit for --traffic")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record request-lifecycle trace events "
                         "(serve/trace.py ring buffer; zero added device "
                         "syncs) and export a Chrome-trace/Perfetto "
                         "timeline JSON here at exit — open it in "
                         "ui.perfetto.dev or chrome://tracing; also "
                         "prints the unified Engine.observe() metric "
                         "snapshot and an Engine.explain() causal chain "
                         "for one request.  benchmarks/check_trace.py "
                         "validates the exported schema in CI")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduced
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model_defs
    from repro.models import module as m
    from repro.serve.chaos import ChaosMonkey
    from repro.serve.engine import Engine, Request
    from repro.serve.scheduler import RequestStatus

    from repro.serve.spec import SpecConfig

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    params = m.init_params(model_defs(cfg), jax.random.PRNGKey(0),
                           jnp.dtype(args.param_dtype))
    spec = None
    if args.spec_draft != "off":
        spec = SpecConfig(draft=args.spec_draft, k=args.spec_k)
    chaos = None
    if args.chaos is not None:
        chaos = ChaosMonkey.smoke(args.chaos)
    clock = None
    traffic_proc = traffic_seed = None
    if args.traffic is not None:
        from repro.serve.traffic import VirtualClock
        traffic_proc, _, s = args.traffic.partition(":")
        traffic_seed = int(s or 0)
        # virtual clock: arrival times, TTFT/TPOT, and deadlines all move
        # in trace units, one tick per chunk boundary — deterministic on
        # any machine
        clock = VirtualClock(dt=0.05)
    eng = Engine(cfg, params, slots=args.slots, max_len=args.max_len,
                 policy=args.policy, clock=clock,
                 page_size=args.page_size, num_pages=args.num_pages,
                 prefix_sharing=not args.no_prefix_sharing,
                 paged_kernel={"auto": "auto", "on": True,
                               "off": False}[args.paged_kernel],
                 spec=spec,
                 temperature=args.temperature, top_k=args.top_k,
                 sync_interval=args.sync_interval,
                 queue_limit=args.queue_limit,
                 shed_policy=args.shed_policy,
                 chaos=chaos,
                 chunked_prefill={"auto": "auto", "on": True,
                                  "off": False}[args.chunked_prefill],
                 prefill_budget=args.prefill_budget,
                 kv_dtype=args.kv_dtype,
                 trace=args.trace is not None)
    if args.warmup:
        t0 = time.perf_counter()
        eng.warmup()
        if eng.chunked_prefill:
            print(f"warmup: fused prefill+decode chunk "
                  f"(prefill_budget={eng.prefill_budget}) + admission "
                  f"splice compiled in {time.perf_counter() - t0:.2f}s")
        else:
            print(f"warmup: {len(eng.buckets)} prefill buckets "
                  f"{eng.buckets} + decode chunk compiled in "
                  f"{time.perf_counter() - t0:.2f}s")
    t0 = time.perf_counter()
    if args.traffic is not None:
        from repro.serve.traffic import TrafficGenerator, replay
        gen = TrafficGenerator(traffic_seed, rate=args.traffic_rate,
                               process=traffic_proc)
        trace = gen.generate(args.requests)
        replay(eng, trace, clock=clock)
        done = list(eng.finished)
        submitted = done + list(eng.rejected)
        print(f"traffic[{traffic_proc}:{traffic_seed}]: "
              f"{len(trace)} arrivals over "
              f"{trace[-1].arrival:.2f} virtual units, classes="
              f"{sorted(set(tr.slo_class for tr in trace))}")
    else:
        head = [1 + (3 * j) % 97
                for j in range(max(args.shared_prefix, 0))]
        submitted = []
        for i in range(args.requests):
            req = Request(rid=i, prompt=head + [1 + i, 2, 3, 4 + i % 3],
                          max_new_tokens=args.max_new, ttl=args.ttl,
                          slo_class=args.slo_class or "best_effort")
            submitted.append(req)
            eng.submit(req)
        done = eng.run(max_steps=100_000 if chaos is not None else 1000)
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in done)
    for r in sorted(done, key=lambda r: r.rid):
        print(f"req {r.rid}: {r.out_tokens}")
    print(f"{len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s, {eng.steps} engine steps, "
          f"{eng.host_syncs} host syncs, "
          f"{eng.prefill_compiles} prefill compiles / "
          f"{eng.suffix_prefill_compiles} suffix compiles / "
          f"{eng.decode_compiles} decode compiles)")
    ms = eng.memory_stats()
    groups = ", ".join(
        f"{k}:{v['num_pages']}p{'w' if v['windowed'] else ''}"
        for k, v in ms["pool_groups"].items())
    print(f"paged KV: page_size={ms['page_size']} pools=[{groups}] "
          f"kv_dtype={ms.get('kv_dtype', 'fp32')} "
          f"peak_pages_in_use={ms['peak_pages_in_use']} "
          f"dense/paged capacity ratio="
          f"{ms['dense_vs_paged_capacity_ratio']:.2f} "
          f"decode_attention="
          f"{'pool-direct' if eng.paged_kernel else 'gather'} "
          f"prefill="
          f"{'fused-chunked' if eng.chunked_prefill else 'bucketed'}")
    ss = eng.spec_stats()
    if ss["spec"]:
        print(f"speculative [{ss['drafter']}, k={ss['spec_k']}]: "
              f"acceptance={ss['acceptance_rate']:.2f} "
              f"({ss['accepted_tokens']}/{ss['drafted_tokens']} drafts), "
              f"{ss['tokens_per_step']:.2f} tokens/verify-step over "
              f"{ss['spec_steps']} steps")
    fs = eng.fault_stats()
    print(f"faults: {fs['preemptions']} preemptions "
          f"({fs['pressure_preemptions']} pressure / "
          f"{fs['chaos_preemptions']} chaos / "
          f"{fs['watchdog_preemptions']} watchdog), "
          f"{fs['resumes']} resumes "
          f"(recovered_prefill={fs['recovered_prefill_fraction']:.2f}), "
          f"{fs['timed_out']} timed out, {fs['cancelled']} cancelled, "
          f"{fs['rejected']} rejected, "
          f"{eng.leaked_pages()} leaked pages")
    if chaos is not None:
        cs = fs["chaos"]
        print(f"chaos[seed={cs['seed']}]: "
              f"{cs['admission_denials']} admission denials, "
              f"{cs['forced_preemptions']} forced preemptions, "
              f"{cs['stalls_started']} stalls, "
              f"{cs['sharing_faults']} sharing faults")
        bad = [r for r in submitted
               if r.status not in RequestStatus.TERMINAL]
        assert not bad, f"non-terminal requests after drain: " \
            f"{[(r.rid, r.status) for r in bad]}"
        assert eng.leaked_pages() == 0, \
            f"leaked {eng.leaked_pages()} pages at drain"
        print("chaos: clean drain (all terminal statuses, zero leaked "
              "pages)")
    if args.traffic is not None or args.slo_class is not None \
            or args.policy != "fifo":
        ls = eng.latency_stats()
        unit = "vu" if clock is not None else "s"
        for name, c in sorted(ls["classes"].items()):
            print(f"slo[{name}]: n={c['count']} "
                  f"goodput={c['goodput'] if c['goodput'] is not None else '-'} "
                  f"ttft_p50/p99={c['ttft_p50']}/{c['ttft_p99']}{unit} "
                  f"tpot_p50/p99={c['tpot_p50']}/{c['tpot_p99']}{unit}")
        print(f"slo overall: goodput={ls['goodput']} "
              f"budget_throttles={ls['budget_throttles']} "
              f"policy={args.policy}")
    ps = eng.prefix_stats()
    if ps["prefix_sharing"]:
        print(f"prefix sharing: hit_rate={ps['prefix_hit_rate']:.2f} "
              f"({ps['prefix_hits']}/{ps['admissions']} admissions), "
              f"{ps['prefill_tokens_skipped']} prefill tokens skipped, "
              f"{ps['shared_page_attaches']} shared attaches, "
              f"{ps['cow_copies']} CoW copies, "
              f"{ps['radix_evictions']} evictions, "
              f"{ps['radix_pages']} pages indexed")
    if args.trace is not None:
        eng.export_trace(args.trace)
        obs = eng.observe(spec=False)
        print(f"trace: {obs['trace.events']} lifecycle events "
              f"({obs['trace.dropped']} dropped) over "
              f"{obs['engine.chunks']} chunks -> {args.trace} "
              f"(open in ui.perfetto.dev)")
        if done:
            print(eng.explain(min(r.rid for r in done)))


if __name__ == "__main__":
    main()
