"""Chip smoke: serve internlm2-1.8b at full width on a TPU through the
normal serving entry points, check what comes out, and end with one JSON
line.

    python chip_smoke.py              # one chip: engine, run and parity
    python chip_smoke.py --chips 4    # sharded engine vs one-device engine

Phases, in order.  Each raises on failure; none catches its own failure
and carries on.

1. device — ``jax.devices()[0].platform`` must be ``"tpu"``.  A machine
   without one is an error: there is no CPU fallback.
2. model  — internlm2-1.8b at its published widths (24 layers, d_model
   2048, vocab 92544) with random bf16 weights made from ``--seed``.
3. engine — ``Engine(slots=8, max_len=2048, page_size=16)`` with chunked
   prefill, prefix sharing and ``paged_kernel="auto"``.  It must choose
   pool-direct decode, and its compiled decode executable must hold the
   Pallas kernel (``tpu_custom_call``), not an XLA stand-in.
4. run    — warm up, then serve 8 seeded requests (prompts of 128-512
   tokens behind a shared 64-token head, 64 new tokens each, greedy).
   Every request finishes with 64 tokens, the decode chunk compiled
   once, and no page leaks.
5. parity — a teacher-forced ``forward_dense_logits`` of each prompt
   plus its output (plain XLA attention, no paging) in the same process.
   Each generated token must be that forward's argmax, or trail it by at
   most ``LOGIT_REL_TOL * max(1, |top logit|)``.

``--chips 4`` runs only the sharded path and what it is compared with:
the same requests through ``Engine(rules=Rules({BATCH: "data", PAGES:
"data"}))`` on a 4-way ``data`` mesh and through a one-device engine,
with the page tables sharded over ``data``.  With fp32 weights and
full-precision matmuls the tokens must be identical; with bf16 weights
the sharded tokens must pass the parity check of phase 5.

Every figure printed before the last line is one measurement of one
smoke run on the device named in the ``device:`` line, not a benchmark.
The last line is the contract: ``{"ok": true, "device": {"platform":
"tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_serving_mesh  # noqa: E402
from repro.models import forward_dense_logits, model_defs  # noqa: E402
from repro.models import module as m  # noqa: E402
from repro.parallel import sharding as sh  # noqa: E402
from repro.serve.engine import Engine, Request  # noqa: E402
from repro.serve.scheduler import RequestStatus  # noqa: E402

ARCH = "internlm2-1.8b"
ENGINE_KW = dict(slots=8, max_len=2048, page_size=16, chunked_prefill=True,
                 prefix_sharing=True, paged_kernel="auto")
N_REQUESTS, SHARED_HEAD, PROMPT_LO, PROMPT_HI, MAX_NEW = 8, 64, 128, 512, 64
# A generated token that is not the reference argmax passes only if its
# reference logit trails the top one by at most 2^-5 of the top logit's
# magnitude (floor 1): 8 bf16 ulps (bf16 keeps 8 significand bits), room
# for the rounding that bf16 weights and activations accumulate over 24
# layers along two different attention lowerings.
LOGIT_REL_TOL = 2.0 ** -5


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def require_tpu(chips: int) -> Dict[str, object]:
    """The ``device`` object of the contract line; exits non-zero when
    JAX finds no TPU (or fewer than ``chips`` of them)."""
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{d.platform!r}, {len(devices)} device(s)); "
                         f"there is no CPU fallback")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX found "
                         f"{len(devices)} TPU device(s)")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def contract_line(device: Dict[str, object]) -> str:
    return json.dumps({"ok": True, "device": device})


class CompileCacheEvents:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0

    def __call__(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def build_model(cfg: ModelConfig, seed: int, dtype=jnp.bfloat16):
    return m.init_params(model_defs(cfg), jax.random.PRNGKey(seed), dtype)


def make_requests(seed: int, vocab: int) -> List[Request]:
    """The seeded workload: ``N_REQUESTS`` prompts of ``PROMPT_LO`` to
    ``PROMPT_HI`` tokens that share a ``SHARED_HEAD``-token head."""
    rng = np.random.default_rng(seed)
    head = rng.integers(1, vocab, SHARED_HEAD).tolist()
    reqs = []
    for rid in range(N_REQUESTS):
        plen = int(rng.integers(PROMPT_LO, PROMPT_HI + 1))
        tail = rng.integers(1, vocab, plen - SHARED_HEAD).tolist()
        reqs.append(Request(rid=rid, prompt=head + tail,
                            max_new_tokens=MAX_NEW))
    return reqs


def build_engine(cfg: ModelConfig, params, **overrides) -> Engine:
    return Engine(cfg, params, **{**ENGINE_KW, **overrides})


def warm(eng: Engine) -> float:
    """Compile the fused decode chunk and admission splice; seconds."""
    t0 = time.perf_counter()
    eng.warmup()
    return time.perf_counter() - t0


def decode_hlo(eng: Engine) -> str:
    """Optimized HLO text of the engine's compiled decode executable."""
    ex = eng.executor
    with ex._ctx():
        lowered = ex._chunk_fn.lower(eng.params, eng.draft_params,
                                     eng.cache, eng.state)
    return lowered.compile().as_text()


def check_pool_direct(eng: Engine) -> None:
    check(eng.paged_kernel, "engine did not choose pool-direct decode")


def check_decode_kernel(eng: Engine) -> None:
    check("tpu_custom_call" in decode_hlo(eng),
          "decode executable holds no tpu_custom_call: the Pallas "
          "paged-attention kernel is not in it")


def serve(eng: Engine, seed: int) -> tuple:
    """Serve the seeded workload; check that it finished cleanly.
    Returns (finished requests sorted by rid, wall seconds)."""
    reqs = make_requests(seed, eng.cfg.vocab_size)
    for r in reqs:
        check(eng.submit(r) is None, f"request {r.rid} rejected")
    t0 = time.perf_counter()
    done = eng.run()
    secs = time.perf_counter() - t0
    done = sorted(done, key=lambda r: r.rid)
    check(len(done) == len(reqs),
          f"{len(done)}/{len(reqs)} requests finished")
    for r in done:
        check(r.status == RequestStatus.FINISHED
              and len(r.out_tokens) == r.max_new_tokens,
              f"request {r.rid}: {r.status} with {len(r.out_tokens)}/"
              f"{r.max_new_tokens} tokens")
    check(eng.decode_compiles == 1,
          f"{eng.decode_compiles} decode compiles, want 1")
    check(eng.leaked_pages() == 0, f"{eng.leaked_pages()} pages leaked")
    return done, secs


def reference_parity(cfg: ModelConfig, params,
                     done: Sequence[Request]) -> Dict[str, float]:
    """Teacher-forced check of every generated token against a plain
    forward (XLA attention over the whole sequence, no paging)."""
    n_new = len(done[0].out_tokens)
    width = max(len(r.prompt) + len(r.out_tokens) for r in done) - 1

    @jax.jit
    def rows(params, tokens, start):
        lg = forward_dense_logits(params, cfg, {"tokens": tokens})[0]
        return jax.lax.dynamic_slice_in_dim(lg, start, n_new, 0)

    total = agree = 0
    worst = 0.0
    bad = []
    for r in done:
        seq = r.prompt + r.out_tokens[:-1]
        tokens = np.zeros((1, width), np.int32)
        tokens[0, :len(seq)] = seq
        lg = np.asarray(rows(params, tokens, len(r.prompt) - 1), np.float32)
        gen = np.asarray(r.out_tokens)
        top = lg.max(axis=-1)
        gap = top - lg[np.arange(n_new), gen]
        tol = LOGIT_REL_TOL * np.maximum(1.0, np.abs(top))
        total += n_new
        agree += int(np.sum(lg.argmax(axis=-1) == gen))
        worst = max(worst, float(gap.max()))
        bad += [(r.rid, int(i), float(gap[i]), float(tol[i]))
                for i in np.nonzero(gap > tol)[0]]
    stats = {"tokens": total, "argmax_match_rate": agree / total,
             "worst_logit_gap": worst}
    check(not bad, f"tokens off the reference argmax beyond tolerance "
                   f"(rid, step, gap, tol): {bad[:8]}")
    return stats


def one_chip(cfg: ModelConfig, params, seed: int) -> None:
    eng = build_engine(cfg, params)
    check_pool_direct(eng)
    compile_s = warm(eng)
    print(f"chip measurement: warmup compile {compile_s:.2f} s "
          f"(fused chunk prefill_budget={eng.prefill_budget}, "
          f"sync_interval={eng.sync_interval})")
    check_decode_kernel(eng)
    print("engine: decode_attention=pool-direct, decode executable holds "
          "tpu_custom_call (Pallas paged attention)")
    done, secs = serve(eng, seed)
    toks = sum(len(r.out_tokens) for r in done)
    ps = eng.prefix_stats()
    print(f"run: {len(done)}/{N_REQUESTS} requests finished, {toks} tokens, "
          f"{eng.steps} micro-steps, {eng.host_syncs} host syncs, "
          f"decode_compiles={eng.decode_compiles}, "
          f"leaked_pages={eng.leaked_pages()}, prefix hit_rate="
          f"{ps['prefix_hit_rate']:.2f}")
    print(f"chip measurement: {toks} tokens in {secs:.3f} s = "
          f"{toks / secs:.1f} tokens/s (one smoke run, not a benchmark)")
    stats = reference_parity(cfg, params, done)
    print(f"parity: {stats['tokens']} tokens, argmax match rate "
          f"{stats['argmax_match_rate']:.4f}, worst logit gap "
          f"{stats['worst_logit_gap']:.4f} (tolerance {LOGIT_REL_TOL} x "
          f"max(1, |top logit|))")


def serve_sharded_and_one_device(cfg: ModelConfig, host_params, seed: int,
                                 n_devices: int) -> tuple:
    """Serve the seeded workload through a one-device engine, then
    through ``Engine(rules=Rules({BATCH: "data", PAGES: "data"}))`` on an
    ``n_devices``-way ``data`` mesh.  The weights come from host memory,
    so device 0 never holds both copies.  Returns (sharded engine,
    one-device requests, sharded requests)."""
    plain = build_engine(cfg, jax.device_put(host_params))
    print(f"chip measurement: one-device warmup compile "
          f"{warm(plain):.2f} s")
    want = serve(plain, seed)[0]
    del plain
    gc.collect()
    mesh = make_serving_mesh(n_devices)
    rules = sh.Rules(table={sh.BATCH: "data", sh.PAGES: "data"}, mesh=mesh)
    eng = build_engine(cfg, jax.device_put(host_params,
                                           NamedSharding(mesh, P())),
                       rules=rules)
    print(f"chip measurement: {n_devices}-device warmup compile "
          f"{warm(eng):.2f} s")
    got, secs = serve(eng, seed)
    print(f"chip measurement: sharded {sum(len(r.out_tokens) for r in got)} "
          f"tokens in {secs:.3f} s (one smoke run, not a benchmark)")
    table = eng.cache["page_tables"][eng.spec.widest_group.key]
    check("data" in str(table.sharding),
          f"page tables not sharded over data: {table.sharding}")
    return eng, want, got


def sharded_matches_one_device(cfg: ModelConfig, seed: int,
                               n_devices: int) -> Engine:
    """The sharded engine against a one-device engine, on the same
    seeded requests.  Returns the bf16 sharded engine.

    fp32 weights with full-precision matmuls first: the two partitionings
    then differ only in f32 accumulation order, far below any logit gap,
    so the tokens must be identical — this is the check that sharding
    changes nothing.  Then bf16 weights, as served: bf16 rounding
    differs between the partitionings, so a near-tie can flip, and the
    sharded tokens are held to the one-chip parity tolerance against the
    teacher-forced reference instead."""
    with jax.default_matmul_precision("highest"):
        eng, want, got = serve_sharded_and_one_device(
            cfg, jax.device_get(build_model(cfg, seed, jnp.float32)), seed,
            n_devices)
    check([r.out_tokens for r in got] == [r.out_tokens for r in want],
          "fp32: sharded engine tokens differ from the one-device engine's")
    table = eng.cache["page_tables"][eng.spec.widest_group.key]
    print(f"sharded fp32: {len(got)} requests token-identical to the "
          f"one-device engine on a {n_devices}-way data mesh, page tables "
          f"{table.sharding.spec}")
    del eng
    gc.collect()
    eng, want, got = serve_sharded_and_one_device(
        cfg, jax.device_get(build_model(cfg, seed)), seed, n_devices)
    same = sum(a == b for r, w in zip(got, want)
               for a, b in zip(r.out_tokens, w.out_tokens))
    stats = reference_parity(cfg, eng.params, got)
    print(f"sharded bf16: {same}/{stats['tokens']} tokens identical to the "
          f"one-device engine; parity vs the reference: argmax match rate "
          f"{stats['argmax_match_rate']:.4f}, worst logit gap "
          f"{stats['worst_logit_gap']:.4f}")
    return eng


def peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: only the sharded engine vs a one-device one")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the requests")
    args = ap.parse_args(argv)
    device = require_tpu(args.chips)
    print(f"device: {device['kind']} x{device['count']} "
          f"({device['platform']}, as JAX reports it)")
    cache_dir = enable_compile_cache()
    cache_events = CompileCacheEvents()
    jax.monitoring.register_event_listener(cache_events)
    cfg = get_config(ARCH)
    print(f"model: {cfg.name} full width: layers={cfg.num_layers} "
          f"d_model={cfg.d_model} heads={cfg.num_heads}/"
          f"{cfg.num_kv_heads}kv head_dim={cfg.resolved_head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size}, bf16 weights "
          f"(seed {args.seed})")
    if args.chips == 1:
        one_chip(cfg, build_model(cfg, args.seed), args.seed)
    else:
        eng = sharded_matches_one_device(cfg, args.seed, args.chips)
        check_pool_direct(eng)
        check_decode_kernel(eng)
        print("sharded engine: decode executable holds tpu_custom_call "
              "(Pallas paged attention under shard_map)")
    peak = peak_bytes()
    print(f"chip measurement: peak device memory "
          f"{'not reported' if peak is None else f'{peak / 2**30:.2f} GiB'}"
          f" (device 0)")
    print(f"compile cache: {cache_dir}: {cache_events.hits} hits, "
          f"{cache_events.misses} misses")
    print(contract_line(device))
    return 0


if __name__ == "__main__":
    sys.exit(main())
