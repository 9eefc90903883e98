"""Layered, shape-stable, sync-free serving runtime (paper §2.2.3, Fig. 14).

The paper's central measurement is that framework overhead — dispatch,
scheduling, synchronization, and memory management — dominates serving
once the math is tuned.  The runtime is split into three layers so each
overhead has exactly one owner:

* **Scheduler** (``serve/scheduler.Scheduler``) — host-side policy: FIFO
  queue, slot admission, per-group page-budget reservation, refcounted
  prefix sharing over a radix index, LRU prefix eviction.  Continuous
  batching: slots free and re-admit at chunk boundaries without
  recompiling anything.
* **Executor** (``Executor`` below) — the compiled layer: bucketed
  prefill (full and shared-prefix *suffix* variants; overlong prompts
  run as several suffix segments), the batched page-granular admission
  splice (every admission a chunk boundary produces lands in ONE
  dispatch), the copy-on-write page duplication, and the fused decode
  chunk (``sync_interval`` decode steps + on-device sampling + slot
  bookkeeping in ONE ``lax.scan`` executable, zero host<->device syncs
  inside).  With ``Engine(spec=...)`` each chunk step is a speculative
  draft/verify/accept round (``serve/spec``, docs/speculative.md):
  a drafter proposes K tokens per slot, one multi-query dispatch
  verifies K+1 positions, and on-device rejection sampling commits a
  variable number — token-identical at temperature 0.
* **Driver** (``Engine``) — glues them: one batched device->host token
  drain per chunk, finish reporting, admission application.

The decode cache is the refcounted block-paged subsystem from
``serve/cache.py``: attention KV lives in per-ring-width page pools with
independent budgets behind per-slot page tables (sliding-window layers
pay window-sized pools, capacity bounded by the page budgets, not
``slots x max_len``), while mamba2/rwkv6 recurrent state stays dense.
``CacheSpec`` carries logical sharding axes for every buffer, so a
``parallel/sharding.Rules`` table mapping ``BATCH``/``PAGES`` to the data
mesh axis serves multi-device via the existing ``launch/mesh.py``
machinery.

``ReferenceEngine`` in ``repro.serve.reference`` preserves the dense
per-token-sync loop as the measurement baseline and equivalence oracle for
``benchmarks/fig14_dispatch_overhead.py``.
"""

from __future__ import annotations

import contextlib
import functools
import json
import math
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import (forward_decode, forward_prefill, forward_verify,
                          model_defs)
from repro.models import module as m
from repro.parallel import sharding as sh
from repro.serve import cache as cache_mod
from repro.serve import metrics as metrics_mod
from repro.serve import sampling
from repro.serve import trace as trace_mod
from repro.serve.cache import CacheSpec, empty_batch_cache  # noqa: F401
from repro.serve.chaos import ChaosMonkey, GarbageDrafter  # noqa: F401
from repro.serve.scheduler import (SLO_CLASSES, Admission,  # noqa: F401
                                   PagePoolExhausted, Request,
                                   RequestRejected, RequestStatus,
                                   Scheduler, SLOClass)
from repro.serve.spec import (ModelDrafter, NGramDrafter, SpecConfig,
                              check_spec_capable, spec_unsupported_reason)


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


# ---------------------------------------------------------------------------
# Latency telemetry: percentile / goodput math over host-stamped requests.
# Pure functions of Request timestamp fields — no device, no engine — so the
# oracle tests in tests/test_latency_stats.py can grade them by hand.
# ---------------------------------------------------------------------------

def percentile(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (the hand-computable definition): the
    ``ceil(q/100 * n)``-th smallest value.  None on an empty sample —
    an undefined percentile must never masquerade as 0.0."""
    if not values:
        return None
    vals = sorted(values)
    n = len(vals)
    rank = max(1, math.ceil(q * n / 100.0))
    return vals[min(rank, n) - 1]


def request_ttft(req: Request) -> Optional[float]:
    """Submit -> first token, from the ORIGINAL submit time (preemption
    and resume never reset it).  None until a first token is drained."""
    if req.first_token_time is None or req.submit_time is None:
        return None
    return req.first_token_time - req.submit_time


def request_tpot(req: Request) -> Optional[float]:
    """Mean per-token delta after the first token (TPOT).  Tokens
    drained in one chunk share a stamp, so this is the chunk-boundary
    average, not a per-dispatch measurement.  None below 2 tokens."""
    if len(req.token_times) < 2:
        return None
    span = req.token_times[-1] - req.token_times[0]
    return span / (len(req.token_times) - 1)


def request_slo_met(req: Request) -> bool:
    """Did this request deliver its SLO?  Only FINISHED requests can;
    a measured latency over target — or a target with no measurement —
    is a miss, while an absent target (best-effort) always passes."""
    if req.status != RequestStatus.FINISHED:
        return False
    for target, got in ((req.resolved_ttft_target, request_ttft(req)),
                        (req.resolved_tpot_target, request_tpot(req))):
        if target is None:
            continue
        if got is None or got > target:
            return False
    return True


def compute_latency_stats(requests: List[Request]) -> Dict[str, Any]:
    """TTFT/TPOT p50/p99 per SLO class + goodput over ``requests``.

    Percentiles cover every request with the relevant measurement (a
    still-running request's drained first token counts toward TTFT);
    goodput is the fraction of TERMINAL requests that FINISHED meeting
    their class (or per-request) targets — timed-out, cancelled, and
    shed requests are SLO misses by definition, while requests still in
    flight are not graded yet.  Classes with no samples report None
    percentiles and goodput 0.0; so does an empty request list."""
    by_class: Dict[str, List[Request]] = {}
    for req in requests:
        by_class.setdefault(req.slo_class, []).append(req)

    def _summary(reqs: List[Request]) -> Dict[str, Any]:
        ttfts = [t for t in (request_ttft(r) for r in reqs)
                 if t is not None]
        tpots = [t for t in (request_tpot(r) for r in reqs)
                 if t is not None]
        terminal = [r for r in reqs
                    if r.status in RequestStatus.TERMINAL]
        met = sum(request_slo_met(r) for r in terminal)
        return {
            "count": len(reqs),
            "terminal": len(terminal),
            "finished": sum(r.status == RequestStatus.FINISHED
                            for r in reqs),
            "slo_met": met,
            "goodput": met / len(terminal) if terminal else 0.0,
            "ttft_p50": percentile(ttfts, 50),
            "ttft_p99": percentile(ttfts, 99),
            "tpot_p50": percentile(tpots, 50),
            "tpot_p99": percentile(tpots, 99),
        }

    stats: Dict[str, Any] = {
        "classes": {cls: _summary(reqs)
                    for cls, reqs in sorted(by_class.items())},
        "overall": _summary(list(requests)),
    }
    stats["goodput"] = stats["overall"]["goodput"]
    return stats


class Executor:
    """Compiled serving layer: every function here is a jit with stable
    shapes (one executable per prefill bucket — plus one per (suffix
    bucket, ctx-block bucket) pair on the prefix-sharing path; exactly
    one batched admission splice; exactly one decode chunk).  The cache
    and slot state are donated through the chunk and the splice on
    backends that implement donation (not CPU).

    With a speculative config (``spec_cfg`` + ``drafter``) the fused
    chunk becomes ``sync_interval`` draft/verify/accept steps: the
    drafter proposes ``K`` tokens per slot on device, the target model
    verifies all ``K+1`` positions in one multi-query paged dispatch
    (``models/transformer.forward_verify``), and the jitted rejection
    sampler (``serve/sampling.spec_accept``) commits a variable number
    of tokens per slot per step — still zero host syncs and one decode
    executable."""

    def __init__(self, cfg: ModelConfig, spec: CacheSpec, *, top_k: int,
                 sync_interval: int, donate: bool,
                 rules: Optional[sh.Rules] = None,
                 paged_kernel: bool = False,
                 spec_cfg: Optional[SpecConfig] = None,
                 drafter=None, hist_cap: int = 0,
                 prefill_budget: int = 0):
        self.cfg = cfg
        self.spec = spec
        self.top_k = int(top_k)
        self.sync_interval = int(sync_interval)
        self.paged_kernel = bool(paged_kernel)
        self.spec_cfg = spec_cfg
        self.drafter = drafter
        self.hist_cap = int(hist_cap)
        # fused chunked prefill (Sarathi-style mixed chunks): > 0 selects
        # the one-executable mode — no prefill executables exist at all;
        # each chunk step runs every decode row plus up to
        # ``prefill_budget`` prompt tokens per admitting slot, and prompt
        # KV is written through the page tables by the SAME dispatch that
        # decodes (context reads stay pool-direct under ``paged_kernel``)
        self.prefill_budget = int(prefill_budget)
        self.chunk_rows = max(self.prefill_budget,
                              spec_cfg.k + 1 if spec_cfg else 1)
        self._rules = rules
        if self.prefill_budget:
            # satellite of the fused design: the per-bucket prefill,
            # suffix-prefill, and draft-prefill executables are simply
            # never built — steady-state compile count is 1 fused chunk
            # (+ 1 admission bookkeeping dispatch)
            self._prefill_fn = None
            self._suffix_fn = None
            self._draft_prefill_fn = None
            admit_impl = self._fused_admit_impl
        else:
            self._prefill_fn = jax.jit(self._prefill_impl,
                                       static_argnums=(5,))
            # suffix prefill READS the live pools (shared-prefix gather),
            # so its cache argument is never donated
            self._suffix_fn = jax.jit(self._prefill_suffix_impl,
                                      static_argnums=(8,))
            self._draft_prefill_fn = jax.jit(self._draft_prefill_impl,
                                             static_argnums=(3,))
            admit_impl = self._admit_impl
        self._state_sh: Optional[Dict[str, Any]] = None
        admit_impl = self._pinned(admit_impl)
        chunk_impl = self._pinned(self._chunk_impl)
        deact_impl = self._pinned(self._deact_impl)
        if donate:
            self._admit_fn = jax.jit(admit_impl, donate_argnums=(0, 1))
            self._splice_fn = jax.jit(self._splice_impl,
                                      donate_argnums=(0,))
            self._chunk_fn = jax.jit(chunk_impl, donate_argnums=(2, 3))
            self._free_fn = jax.jit(self._free_impl, donate_argnums=(0,))
            self._copy_fn = jax.jit(self._copy_impl, donate_argnums=(0,),
                                    static_argnums=(3,))
            self._deact_fn = jax.jit(deact_impl, donate_argnums=(0,))
        else:
            self._admit_fn = jax.jit(admit_impl)
            self._splice_fn = jax.jit(self._splice_impl)
            self._chunk_fn = jax.jit(chunk_impl)
            self._free_fn = jax.jit(self._free_impl)
            self._copy_fn = jax.jit(self._copy_impl, static_argnums=(3,))
            self._deact_fn = jax.jit(deact_impl)

    def _ctx(self):
        """Sharding rules are a tracing-time thread-local; enter them for
        every compiled call so retraces see the same table."""
        if self._rules is None:
            return contextlib.nullcontext()
        return sh.axis_rules(self._rules)

    def _state_shardings(self, state) -> Optional[Dict[str, Any]]:
        """On a mesh, one fixed layout for the slot state: per-slot rows
        over ``BATCH``, the PRNG key and counters replicated.  Every
        executable takes its state in this layout and returns it in this
        layout, so a chunk's outputs feed the next chunk without a
        recompile.  None without a mesh."""
        rules = self._rules
        if rules is None or rules.mesh is None:
            return None
        if self._state_sh is None:
            def one(name, x):
                if name != "key" and x.ndim and x.shape[0] == self.spec.slots:
                    return rules.sharding_for(
                        (sh.BATCH,) + (None,) * (x.ndim - 1), x.shape)
                return NamedSharding(rules.mesh, P())
            self._state_sh = {k: one(k, v) for k, v in state.items()}
        return self._state_sh

    def _place_state(self, state):
        """Host side: move state the host rebuilt (a restored key, a new
        prefill-budget vector) into the fixed layout; a no-op otherwise."""
        shardings = self._state_shardings(state)
        return state if shardings is None else jax.device_put(state,
                                                              shardings)

    def _pinned(self, impl):
        """``impl`` with its returned state (the last output) held to the
        fixed layout."""
        @functools.wraps(impl)
        def f(*args):
            out = impl(*args)
            state = out[-1] if isinstance(out, tuple) else out
            shardings = self._state_shardings(state)
            if shardings is not None:
                state = jax.lax.with_sharding_constraint(state, shardings)
            return (*out[:-1], state) if isinstance(out, tuple) else state
        return f

    # ------------------------------------------------------ impls (traced)
    @staticmethod
    def _pad_kv(entry, pad_to: int):
        """Pad one {k, v} KV entry's seq axis (2) to ``pad_to``."""
        e = dict(entry)
        for k in ("k", "v"):
            pad = pad_to - e[k].shape[2]
            if pad > 0:
                cfgp = [(0, 0)] * e[k].ndim
                cfgp[2] = (0, pad)
                e[k] = jnp.pad(e[k], cfgp)
        return e

    def _pad_prefill_cache(self, cache, pad_to: int):
        """Pad every attention-KV seq axis to ``pad_to`` inside the
        prefill executable, so admission sees ONE shape whatever bucket
        produced the cache — that is what lets a chunk boundary's
        admissions share a single batched splice executable."""
        layers = []
        for ls, entry in zip(self.spec.layers, cache["layers"]):
            if ls is not None and ls.kind == cache_mod.PAGED_KV \
                    and entry is not None and "k" in entry:
                layers.append(self._pad_kv(entry, pad_to))
            else:
                layers.append(entry)
        return dict(cache, layers=layers)

    def _prefill_impl(self, params, tokens, length, key, temp, pad_to):
        """Padded prefill + on-device first-token sampling.

        tokens [1, bucket], length [1].  One compile per bucket shape;
        the returned cache is padded to ``pad_to`` (the largest bucket)
        so every bucket feeds the same admission executable."""
        batch = {"tokens": tokens}
        if self.cfg.frontend:
            k = "frames" if self.cfg.family == "audio" else "frontend"
            batch[k] = jnp.zeros(
                (1, self.cfg.frontend_len, self.cfg.d_model), jnp.float32)
        logits, cache = forward_prefill(params, self.cfg, batch,
                                        length=length)
        tok = sampling.sample(logits, key, temperature=temp,
                              top_k=self.top_k)
        return tok, self._pad_prefill_cache(cache, pad_to)

    def _prefill_suffix_impl(self, params, tokens, length, off, ctx_row,
                             layer_pools, key, temp, pad_to):
        """Shared-prefix suffix prefill: tokens [1, bucket] hold only the
        un-matched prompt tail at absolute positions ``off + i``; the
        matched prefix is attended through the pool pages named in
        ``ctx_row`` (the new slot's own table row — shared pages plus any
        copy-on-write duplicate) without being recomputed.  One compile
        per (suffix bucket, ctx-block bucket) shape pair.  Also the
        chunked-prefill workhorse: a prompt longer than the largest
        bucket runs as several suffix calls, each attending to the pages
        the previous segments spliced."""
        ctx = {"off": off, "row": ctx_row, "layers": layer_pools}
        logits, cache = forward_prefill(params, self.cfg,
                                        {"tokens": tokens},
                                        length=length, ctx=ctx)
        tok = sampling.sample(logits, key, temperature=temp,
                              top_k=self.top_k)
        return tok, self._pad_prefill_cache(cache, pad_to)

    def _draft_prefill_impl(self, draft_params, tokens, length, pad_to):
        """Draft-model prefill for the model drafter: same bucketed
        tokens, dense KV out (padded to ``pad_to``), logits discarded —
        the draft's first proposal comes from its decode step."""
        _, cache = forward_prefill(draft_params, self.drafter.cfg,
                                   {"tokens": tokens}, length=length)
        return [self._pad_kv(entry, pad_to) for entry in cache["layers"]]

    def _splice_draft(self, draft_layers, one_layers, slot, enabled):
        """Write a batch-1 draft prefill into row ``slot`` of the dense
        draft cache (positions 0..n-1; the pad tail beyond the prompt is
        overwritten by later draft decode writes)."""
        out = []
        for big, small in zip(draft_layers, one_layers):
            e = {}
            for k in ("k", "v"):
                b_, s_ = big[k], small[k]
                n = min(s_.shape[2], b_.shape[2])
                s_ = s_[:, :, :n]
                cur = jax.lax.dynamic_slice(
                    b_, (slot, 0, 0, 0), (1,) + b_.shape[1:])[:, :, :n]
                s_ = jnp.where(enabled, s_.astype(b_.dtype), cur)
                e[k] = jax.lax.dynamic_update_slice(b_, s_,
                                                    (slot, 0, 0, 0))
            out.append(e)
        return out

    def _admit_impl(self, cache, state, one_caches, draft_caches, slots_v,
                    starts, plens, rows, first_toks, out_lens, max_news,
                    eoss, temps, valids, hist_toks):
        """Batched jitted admission: ONE splice dispatch applies every
        admission a chunk boundary produced.  All per-admission operands
        are padded to ``spec.slots`` entries (``valids`` masks the
        padding — a disabled entry's pool writes land on trash pages and
        its table/len/state keep their prior values), and every prefill
        cache arrives padded to the largest bucket, so the executable
        count stays at exactly 1 however many slots fill at once.

        ``out_lens`` is the slot's initial generated-token count: 1 for a
        fresh request, ``len(out_tokens) + 1`` for a preempted request
        being resumed (its replayed tokens already count against
        ``max_new``, so the budget check needs no special casing)."""
        st = dict(state)
        for i in range(self.spec.slots):
            sl = slots_v[i]
            en = valids[i]
            cache = cache_mod.admit_cache(
                self.spec, cache, one_caches[i], sl, starts[i], plens[i],
                {k: rows[k][i] for k in rows}, enabled=en)
            if draft_caches is not None:
                cache["draft"] = self._splice_draft(
                    cache["draft"], draft_caches[i], sl, en)

            def setv(vec, new):
                return vec.at[sl].set(jnp.where(en, new, vec[sl]))

            st["tokens"] = setv(st["tokens"], first_toks[i][0])
            st["out_len"] = setv(st["out_len"], out_lens[i])
            st["max_new"] = setv(st["max_new"], max_news[i])
            st["eos"] = setv(st["eos"], eoss[i])
            st["temp"] = setv(st["temp"], temps[i])
            # active only while budget remains past the prefill-sampled
            # token: a resume whose pending token is its last (and a
            # fresh max_new=1 request) must not decode a step beyond it
            st["active"] = setv(st["active"], out_lens[i] < max_news[i])
            if hist_toks is not None:
                cap = self.hist_cap
                row = jnp.where(jnp.arange(cap) < plens[i], hist_toks[i], 0)
                row = jnp.concatenate(
                    [row, jnp.zeros((1,), jnp.int32)])
                row = row.at[jnp.minimum(plens[i], cap)].set(
                    first_toks[i][0])
                cur = jax.lax.dynamic_slice(st["hist"], (sl, 0),
                                            (1, cap + 1))
                st["hist"] = jax.lax.dynamic_update_slice(
                    st["hist"], jnp.where(en, row[None], cur), (sl, 0))
                st["hist_len"] = setv(st["hist_len"], plens[i] + 1)
        return cache, st

    def _fused_admit_impl(self, cache, state, slots_v, starts, plens, rows,
                          prompts, out_lens, max_news, eoss, temps, valids):
        """Batched admission for fused chunked prefill: pure bookkeeping.
        No prefill KV exists yet — the fused chunk step itself writes
        prompt KV through the page tables — so admission only installs
        the slot's table rows, rewinds ``len`` to the prefill cursor
        (``starts``: 0 fresh, the shared-prefix / resume boundary
        otherwise), stages the prompt row + ``plen`` target, and arms the
        slot.  ``out_lens`` is ``len(out_tokens)`` with NO +1: the first
        sampled token flows through the chunk's emitted history like any
        other, instead of being staged host-side at admission."""
        st = dict(state)
        for i in range(self.spec.slots):
            sl = slots_v[i]
            en = valids[i]
            cache = cache_mod.install_slot_rows(
                self.spec, cache, sl, starts[i],
                {k: rows[k][i] for k in rows}, enabled=en)

            def setv(vec, new):
                return vec.at[sl].set(jnp.where(en, new, vec[sl]))

            st["tokens"] = setv(st["tokens"], 0)
            st["out_len"] = setv(st["out_len"], out_lens[i])
            st["max_new"] = setv(st["max_new"], max_news[i])
            st["eos"] = setv(st["eos"], eoss[i])
            st["temp"] = setv(st["temp"], temps[i])
            st["active"] = setv(st["active"], out_lens[i] < max_news[i])
            st["plen"] = setv(st["plen"], plens[i])
            cur = jax.lax.dynamic_slice(
                st["prompt"], (sl, 0), (1, st["prompt"].shape[1]))
            st["prompt"] = jax.lax.dynamic_update_slice(
                st["prompt"], jnp.where(en, prompts[i][None], cur), (sl, 0))
            if "hist" in st:
                cap = self.hist_cap
                prom = prompts[i]
                if prom.shape[0] < cap + 1:
                    prom = jnp.concatenate(
                        [prom, jnp.zeros((cap + 1 - prom.shape[0],),
                                         jnp.int32)])
                row = jnp.where(jnp.arange(cap + 1) < plens[i],
                                prom[:cap + 1], 0)
                curh = jax.lax.dynamic_slice(st["hist"], (sl, 0),
                                             (1, cap + 1))
                st["hist"] = jax.lax.dynamic_update_slice(
                    st["hist"], jnp.where(en, row[None], curh), (sl, 0))
                st["hist_len"] = setv(st["hist_len"], plens[i])
        return cache, st

    def _splice_impl(self, cache, one_cache, slot, start, plen, rows):
        """Cache-only splice for intermediate chunked-prefill segments:
        writes segment KV through the slot's pages at token offset
        ``start`` without touching slot bookkeeping (the final segment
        goes through the batched admission)."""
        return cache_mod.admit_cache(self.spec, cache, one_cache, slot,
                                     start, plen, rows)

    def _chunk_impl(self, params, draft_params, cache, state):
        """``sync_interval`` fused decode steps: forward (with paged KV
        lookup) + sample + slot bookkeeping, all on device.  Returns the
        [T, slots] token history (-1 where a slot was idle) — the only
        thing the host ever reads.  With speculation each of the ``T``
        steps is a draft/verify/accept round committing up to ``K+1``
        tokens per slot, and the history is [T*(K+1), slots]."""
        if self.prefill_budget:
            return self._fused_chunk_impl(params, draft_params, cache,
                                          state)
        if self.spec_cfg is None:
            def body(carry, _):
                cache, state = carry
                # active as write mask: a finished slot's dead-tail steps
                # must not wrap KV writes into pages now shared with other
                # slots or the radix prefix index
                logits, cache = forward_decode(
                    params, self.cfg, state["tokens"][:, None], cache,
                    write_mask=state["active"],
                    paged_kernel=self.paged_kernel)
                cache.pop("enc_kv", None)   # decoder-only: keep structure
                key, sub = jax.random.split(state["key"])
                nxt = sampling.sample(logits, sub,
                                      temperature=state["temp"],
                                      top_k=self.top_k)
                state, emitted = sampling.decode_update(state, nxt, key)
                return (cache, state), emitted

            (cache, state), toks = jax.lax.scan(
                body, (cache, state), None, length=self.sync_interval)
            return toks, cache, state

        def body(carry, _):
            cache, state = carry
            kd, ka, knext = jax.random.split(state["key"], 3)
            drafts, qprobs, cache = self.drafter.propose(
                draft_params, cache, state, kd, self.top_k)
            toks = jnp.concatenate([state["tokens"][:, None], drafts],
                                   axis=1)
            logits, cache = forward_verify(
                params, self.cfg, toks, cache,
                write_mask=state["active"],
                paged_kernel=self.paged_kernel,
                spec_slack=self.spec_cfg.k)
            cache.pop("enc_kv", None)
            cand, n_acc = sampling.spec_accept(
                logits, drafts, qprobs, state["temp"], self.top_k, ka)
            state, emitted, n_emit = sampling.spec_update(
                state, cand, n_acc, knext)
            cache = dict(cache, len=cache["len"] + n_emit)
            return (cache, state), emitted

        (cache, state), toks = jax.lax.scan(
            body, (cache, state), None, length=self.sync_interval)
        # [T, slots, K+1] -> time-major [T*(K+1), slots] for the drain
        toks = jnp.swapaxes(toks, 1, 2).reshape(-1, toks.shape[1])
        return toks, cache, state

    def _fused_chunk_impl(self, params, draft_params, cache, state):
        """Fused mixed prefill+decode chunk (Sarathi-style chunked
        prefill): ONE executable serves the whole slot population.  Each
        of the ``sync_interval`` micro-steps builds a right-aligned
        [slots, S] token matrix (S = ``chunk_rows``): a mid-prefill slot
        contributes its next ``n = min(plen - len, S)`` prompt tokens, a
        decoding slot its pending token (+ drafts under speculation), and
        every row block sits flush against column S-1 so leading pad rows
        have write masks off (KV lands on trash) and sampling always
        reads the static last row.  Per-slot ``cache_len = len + n``
        keeps the causal/ring masks exact per row — no kernel changes,
        and the prompt's context reads stream pool-direct through the
        paged attention path like any decode.

        A slot whose prefill completes this step (``rem <= S``) samples
        its first token from row S-1 — exactly the logits the legacy
        prefill executable sampled — and starts decoding next micro-step;
        until then nothing is committed for it (and under speculation
        drafting stays disabled for it: its draft rows are write-masked
        and its accept verdicts discarded)."""
        S = self.chunk_rows
        k1 = self.spec_cfg.k + 1 if self.spec_cfg is not None else 1
        col = jnp.arange(S)[None, :]

        def split_rows(cache, state):
            len_ = cache["len"]
            active = state["active"]
            rem = state["plen"] - len_
            prefilling = active & (rem > 0)
            # per-slot dynamic prefill budget: the SLO policy shrinks a
            # lower-priority slot's prompt slice at chunk boundaries
            # (host->device value update, never a retrace — S stays the
            # compiled static width and pbudget is clamped into [1, S])
            budget = jnp.clip(state["pbudget"], 1, S) \
                if "pbudget" in state else S
            n = jnp.where(prefilling, jnp.minimum(rem, budget), k1)
            completing = prefilling & (rem <= S)
            gidx = len_[:, None] + col - (S - n)[:, None]
            pcap = state["prompt"].shape[1]
            ptoks = jnp.take_along_axis(
                state["prompt"], jnp.clip(gidx, 0, pcap - 1), axis=1)
            wm = active[:, None] & (col >= (S - n)[:, None])
            return len_, active, prefilling, completing, n, ptoks, wm

        if self.spec_cfg is None:
            def body(carry, _):
                cache, state = carry
                (len_, active, prefilling, completing, n, ptoks,
                 wm) = split_rows(cache, state)
                toks = jnp.where(
                    prefilling[:, None], ptoks,
                    jnp.where(col == S - 1, state["tokens"][:, None], 0))
                logits, cache = forward_verify(
                    params, self.cfg, toks, cache, write_mask=wm,
                    paged_kernel=self.paged_kernel,
                    spec_slack=self.spec.spec_tokens, n_rows=n)
                cache.pop("enc_kv", None)
                key, sub = jax.random.split(state["key"])
                nxt = sampling.sample(logits[:, -1], sub,
                                      temperature=state["temp"],
                                      top_k=self.top_k)
                # commit the sample for decoding slots and for slots whose
                # prefill just completed (their first token); mid-prefill
                # slots commit nothing
                commit = active & (~prefilling | completing)
                state, emitted = sampling.decode_update(state, nxt, key,
                                                        commit=commit)
                cache = dict(cache, len=len_ + jnp.where(
                    prefilling, n, active.astype(jnp.int32)))
                return (cache, state), emitted

            (cache, state), toks = jax.lax.scan(
                body, (cache, state), None, length=self.sync_interval)
            return toks, cache, state

        def body(carry, _):
            cache, state = carry
            (len_, active, prefilling, completing, n, ptoks,
             wm) = split_rows(cache, state)
            decoding = active & ~prefilling
            kd, ka, kf, kmid, knext = jax.random.split(state["key"], 5)
            drafts, qprobs, cache = self.drafter.propose(
                draft_params, cache, state, kd, self.top_k)
            dtoks = jnp.concatenate([state["tokens"][:, None], drafts],
                                    axis=1)
            if S > k1:
                dtoks = jnp.concatenate(
                    [jnp.zeros((dtoks.shape[0], S - k1), jnp.int32),
                     dtoks], axis=1)
            toks = jnp.where(prefilling[:, None], ptoks, dtoks)
            logits, cache = forward_verify(
                params, self.cfg, toks, cache, write_mask=wm,
                paged_kernel=self.paged_kernel,
                spec_slack=self.spec.spec_tokens, n_rows=n)
            cache.pop("enc_kv", None)
            cand, n_acc = sampling.spec_accept(
                logits[:, S - k1:], drafts, qprobs, state["temp"],
                self.top_k, ka)
            first = sampling.sample(logits[:, -1], kf,
                                    temperature=state["temp"],
                                    top_k=self.top_k)
            # prefill-completing slots commit exactly their first token
            # (drafting for them begins next micro-step); decoding slots
            # commit their accepted draft run as usual
            state, _ = sampling.decode_update(state, first, kmid,
                                              commit=completing)
            state, emitted, n_emit = sampling.spec_update(
                state, cand, n_acc, knext, commit=decoding)
            idx1 = jnp.arange(k1)[None, :]
            emitted = jnp.where(completing[:, None] & (idx1 == 0),
                                first[:, None], emitted)
            cache = dict(cache, len=len_ + jnp.where(
                prefilling, n, n_emit))
            return (cache, state), emitted

        (cache, state), toks = jax.lax.scan(
            body, (cache, state), None, length=self.sync_interval)
        toks = jnp.swapaxes(toks, 1, 2).reshape(-1, toks.shape[1])
        return toks, cache, state

    def _free_impl(self, cache, slot):
        return cache_mod.free_slot_cache(self.spec, cache, slot)

    def _deact_impl(self, state, slot):
        """Clear a slot's active flag (preemption / reaping at a chunk
        boundary): its dead-tail decode steps stop sampling and — with
        the table rows re-trashed by ``free_slot`` — cannot write KV
        anywhere that matters."""
        return dict(state, active=state["active"].at[slot].set(False))

    def _copy_impl(self, cache, src, dst, group_key):
        """Copy-on-write: duplicate page ``src`` into ``dst`` across the
        sharing group's layer pools before the owner slot writes."""
        return cache_mod.copy_shared_page(self.spec, cache, group_key,
                                          src, dst)

    # -------------------------------------------------------- public calls
    def prefill(self, params, tokens, length, key, temp, pad_to):
        with self._ctx():
            return self._prefill_fn(params, tokens, length, key, temp,
                                    pad_to)

    def prefill_suffix(self, params, tokens, length, off, ctx_row,
                       layer_pools, key, temp, pad_to):
        with self._ctx():
            return self._suffix_fn(params, tokens, length, off, ctx_row,
                                   layer_pools, key, temp, pad_to)

    def draft_prefill(self, draft_params, tokens, length, pad_to):
        with self._ctx():
            return self._draft_prefill_fn(draft_params, tokens, length,
                                          pad_to)

    def admit(self, cache, state, *args):
        with self._ctx():
            return self._admit_fn(cache, self._place_state(state), *args)

    def splice(self, cache, one_cache, slot, start, plen, rows):
        with self._ctx():
            return self._splice_fn(cache, one_cache, slot, start, plen,
                                   rows)

    def copy_page(self, cache, src, dst, group_key):
        with self._ctx():
            return self._copy_fn(cache, src, dst, group_key)

    def chunk(self, params, draft_params, cache, state):
        with self._ctx():
            return self._chunk_fn(params, draft_params, cache,
                                  self._place_state(state))

    def free_slot(self, cache, slot):
        with self._ctx():
            return self._free_fn(cache, slot)

    def deactivate(self, state, slot):
        with self._ctx():
            return self._deact_fn(self._place_state(state), slot)

    # ----------------------------------------------------------- telemetry
    @property
    def prefill_compiles(self) -> int:
        if self._prefill_fn is None:     # fused mode: no prefill exec
            return 0
        return self._prefill_fn._cache_size()

    @property
    def suffix_prefill_compiles(self) -> int:
        if self._suffix_fn is None:      # fused mode: no prefill exec
            return 0
        return self._suffix_fn._cache_size()

    @property
    def admit_compiles(self) -> int:
        return self._admit_fn._cache_size()

    @property
    def decode_compiles(self) -> int:
        return self._chunk_fn._cache_size()


class Engine:
    """Host driver: composes Scheduler (policy) + Executor (compiled) over
    the refcounted paged cache.  ``max_len`` is the *logical* per-slot
    token cap (the widest page-table width x page_size); physical
    capacity is per pool group — ``num_pages x page_size`` tokens for the
    widest (full-attention) group (default: the old dense ``slots x
    max_len`` token capacity), ``slots x window`` tokens for each
    sliding-window group (sized to the window, no flat-pool byte
    overhead).  ``prefix_sharing`` (on by default, auto-disabled for
    archs whose prefix state cannot live in pages) admits requests with a
    cached prompt prefix onto shared pages and prefillls only the
    suffix.  ``paged_kernel`` selects how decode attention reads the
    pools: ``True`` = pool-direct (``kernels/paged_attention``: Pallas
    page streaming on TPU, pool-wide masked attention elsewhere — the
    gather buffer never exists), ``False`` = gather-then-attend, and
    ``"auto"`` = the kernel on TPU, gather elsewhere.  ``kv_dtype``
    selects the pool storage precision (``"fp32"`` | ``"int8"`` |
    ``"fp8_e4m3"``; ``"auto"`` == fp32): 8-bit pools carry per-page,
    per-kv-head scales and dequantize inside the attention path — ~4x
    page-pool capacity at a bounded logit error.  ``spec`` turns on
    speculative decoding (``"ngram"``, a
    draft-config name, or a ``serve/spec.SpecConfig``): drafted
    multi-token steps verified in the fused chunk, output
    token-identical at temperature 0 — attention-only archs only
    (``serve/spec/config.py`` documents the gate)."""

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_len: int = 256, greedy: bool = True,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 sync_interval: int = 8, min_bucket: int = 8,
                 buckets: Optional[List[int]] = None,
                 page_size: int = 8, num_pages: Optional[int] = None,
                 prefix_sharing: bool = True,
                 paged_kernel: Any = "auto",
                 spec: Any = None,
                 rules: Optional[sh.Rules] = None,
                 donate: Any = "auto",
                 preemption: bool = True,
                 queue_limit: Optional[int] = None,
                 shed_policy: str = "reject",
                 policy: str = "fifo",
                 clock: Optional[Callable[[], float]] = None,
                 stall_patience: int = 0,
                 chaos: Optional[ChaosMonkey] = None,
                 trace: Any = None,
                 chunked_prefill: Any = "auto",
                 prefill_budget: int = 32,
                 kv_dtype: str = "auto"):
        if cfg.cross_attention:
            raise NotImplementedError(
                "Engine serves decoder-only archs; whisper uses "
                "examples/whisper_transcribe.py's direct loop")
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.greedy = greedy
        if temperature > 0.0:
            self.default_temp = float(temperature)
        else:
            self.default_temp = 0.0 if greedy else 1.0
        self.top_k = int(top_k)
        self.sync_interval = int(sync_interval)
        if buckets is None:
            b, buckets = min_bucket, []
            while b < _next_pow2(max_len):
                buckets.append(b)
                b *= 2
            buckets.append(b)
        self.buckets = sorted(set(int(b) for b in buckets))
        if donate == "auto":
            donate = jax.default_backend() != "cpu"
        self._donate = bool(donate)
        self._rules = rules

        # ---- speculative decoding config + drafter resolution
        if spec in (None, False, "off"):
            spec_cfg = None
        elif isinstance(spec, SpecConfig):
            spec_cfg = spec
        elif isinstance(spec, str):
            spec_cfg = SpecConfig(draft=spec)
        else:
            raise TypeError(f"spec must be None, 'ngram', a draft config "
                            f"name, or a SpecConfig; got {spec!r}")
        self.spec_config = spec_cfg
        self.drafter = None
        self.draft_params = None
        if spec_cfg is not None:
            check_spec_capable(cfg)
            if spec_cfg.k < 1:
                raise ValueError(f"spec.k must be >= 1, got {spec_cfg.k}")
            if spec_cfg.draft == "ngram":
                self.drafter = NGramDrafter(spec_cfg.k, spec_cfg.ngram)
            else:
                dcfg = spec_cfg.draft_cfg
                if dcfg is None:
                    from repro.configs import get_config, reduced
                    dcfg = reduced(get_config(spec_cfg.draft))
                self.drafter = ModelDrafter(
                    dcfg, spec_cfg.k,
                    cache_tokens=max_len + spec_cfg.k + 1)
                self.draft_params = spec_cfg.draft_params
                if self.draft_params is None:
                    self.draft_params = m.init_params(
                        model_defs(dcfg), jax.random.PRNGKey(seed + 17),
                        jnp.float32)
        if chaos is not None and chaos.garbage_drafter \
                and self.drafter is not None:
            # fault isolation: rejection sampling keeps output
            # token-identical however bad the drafts are
            self.drafter = GarbageDrafter(self.drafter)
        # the token-history buffer is the n-gram drafter's lookup corpus;
        # a model drafter never reads it, so it pays neither the buffer
        # nor the per-step scatter
        self._hist_cap = (max_len + spec_cfg.k + 2
                          if spec_cfg is not None
                          and spec_cfg.draft == "ngram" else 0)

        # ---- fused chunked prefill (Sarathi-style mixed chunks)
        # "auto": on whenever the fused chunk can serve the arch — paged
        # KV throughout, attention-only mixer stack, and no model drafter
        # (its separate draft cache still needs a draft-prefill pass).
        # The fused mode deletes every prefill executable: prompts stream
        # through the SAME chunk step that decodes, prefill_budget tokens
        # per slot per micro-step.
        fused_capable = (
            not cfg.cross_attention
            and spec_unsupported_reason(cfg) is None
            and not (spec_cfg is not None and spec_cfg.draft != "ngram"))
        if chunked_prefill == "auto":
            chunked_prefill = fused_capable
        elif chunked_prefill and not fused_capable:
            raise ValueError(
                f"{cfg.name}: chunked_prefill needs paged KV for every "
                "mixer (attention-only stack) and no model drafter; "
                f"reason: {spec_unsupported_reason(cfg) or 'model drafter'}")
        self.chunked_prefill = bool(chunked_prefill)
        if prefill_budget < 1:
            raise ValueError(
                f"prefill_budget must be >= 1, got {prefill_budget}")
        self.prefill_budget = int(prefill_budget) if self.chunked_prefill \
            else 0
        # rows per fused micro-step: prefill slices and draft/verify rows
        # share the one [slots, S] token matrix
        chunk_rows = max(self.prefill_budget,
                         spec_cfg.k + 1 if spec_cfg else 1)
        # windowed rings need ring >= window + S - 1 so a full-width
        # prefill slice can write-wrap legitimately; spec_tokens is
        # exactly that slack (max_len-capped inside CacheSpec)
        cache_slack = (max(spec_cfg.k if spec_cfg else 0, chunk_rows - 1)
                       if self.chunked_prefill
                       else (spec_cfg.k if spec_cfg else 0))
        # ---- pool precision (quantized KV page pool).  "auto" == fp32.
        self.kv_dtype = "fp32" if kv_dtype == "auto" else kv_dtype
        if self.kv_dtype not in cache_mod.KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be 'auto' or one of {cache_mod.KV_DTYPES}, "
                f"got {kv_dtype!r}")
        self.spec = CacheSpec.from_config(
            cfg, slots, max_len, page_size=page_size, num_pages=num_pages,
            spec_tokens=cache_slack, kv_dtype=self.kv_dtype)
        if paged_kernel == "auto":
            # pool-direct attention is the TPU hot path (the compiled
            # Pallas kernel; a compile failure raises, nothing falls
            # back).  Off-TPU the default stays gather-then-attend: at
            # smoke scale XLA's fused gather+softmax wins, and the
            # pool-wide lowering only pays off once the pool is
            # oversubscribed — opt in with paged_kernel=True (fig14
            # measures both).
            paged_kernel = jax.default_backend() == "tpu"
        self.paged_kernel = bool(paged_kernel) and self.spec.has_paged
        if spec_cfg is not None and not self.spec.has_paged:
            raise ValueError(
                f"{cfg.name}: speculative decoding needs the paged decode "
                "cache (rollback by position)")
        if self.chunked_prefill and not self.spec.has_paged:
            raise ValueError(
                f"{cfg.name}: chunked_prefill needs the paged decode cache")
        # admission policy: "fifo" (arrival order) or "slo" (priority +
        # least-TTFT-slack-first; class-aware preemption victims/shed)
        self.policy = policy
        self.scheduler = Scheduler(self.spec, prefix_sharing=prefix_sharing,
                                   defer_radix_insert=self.chunked_prefill,
                                   policy=policy)
        self.executor = Executor(cfg, self.spec, top_k=self.top_k,
                                 sync_interval=self.sync_interval,
                                 donate=self._donate, rules=rules,
                                 paged_kernel=self.paged_kernel,
                                 spec_cfg=spec_cfg, drafter=self.drafter,
                                 hist_cap=self._hist_cap,
                                 prefill_budget=self.prefill_budget)

        self._slot_req: List[Optional[Request]] = [None] * slots
        self._slot_first_tok: List[Optional[jax.Array]] = [None] * slots
        # True while a slot's prefill-sampled token sits on device but
        # has not been drained into req.out_tokens yet (resumed requests
        # arrive with a non-empty out_tokens, so "is out_tokens empty"
        # cannot stand in for this flag)
        self._slot_first_pending: List[bool] = [False] * slots
        self._slot_stale: List[int] = [0] * slots
        # fused chunked prefill: tokens of the slot's effective prompt
        # already covered by past chunks (the host-visible prefill
        # cursor, trailing the device's cache["len"] by one drain) and
        # the admission-time prompt length it is counting toward
        self._slot_seen_len: List[int] = [0] * slots
        self._slot_plen: List[int] = [0] * slots
        self.cache = self._empty_cache()
        self.state = sampling.make_slot_state(
            slots, seed, hist_cap=self._hist_cap,
            spec=spec_cfg is not None,
            prompt_cap=max_len if self.chunked_prefill else 0,
            prefill_budget=(self.executor.chunk_rows
                            if self.chunked_prefill else 0))
        # host mirror of state["pbudget"]: the SLO boundary policy only
        # dispatches a device update when the desired vector changes
        self._budget_vec: Optional[List[int]] = (
            [self.executor.chunk_rows] * slots
            if self.chunked_prefill else None)
        self.budget_throttles = 0
        self._key = jax.random.PRNGKey(seed + 1)
        self.finished: List[Request] = []
        self.rejected: List[Request] = []
        self.steps = 0
        self.host_syncs = 0
        # high-water mark of concurrently occupied slots (the capacity
        # metric the quantized-pool bench reports per workload)
        self.peak_live_slots = 0

        # ---- robustness: preemption / deadlines / admission control
        self.preemption = bool(preemption)
        self.queue_limit = queue_limit
        if shed_policy not in ("reject", "block", "evict-lru-prefix",
                               "shed-lowest-class"):
            raise ValueError(f"shed_policy must be 'reject', 'block', "
                             f"'evict-lru-prefix' or 'shed-lowest-class', "
                             f"got {shed_policy!r}")
        self.shed_policy = shed_policy
        self._clock = clock if clock is not None else time.monotonic
        # ---- observability (serve/trace.py, serve/metrics.py): a
        # bounded lifecycle tracer recorded at chunk boundaries only.
        # None/False disables it entirely (the default: zero overhead);
        # True builds a default-capacity Tracer, an int sets the ring
        # capacity, and a Tracer instance is used as-is.
        if trace in (None, False):
            self.tracer = None
        elif isinstance(trace, trace_mod.Tracer):
            self.tracer = trace
        elif trace is True:
            self.tracer = trace_mod.Tracer()
        elif isinstance(trace, int):
            self.tracer = trace_mod.Tracer(capacity=trace)
        else:
            raise TypeError(f"trace must be None/bool/int/Tracer, "
                            f"got {trace!r}")
        # chunk sequence number: incremented once per drain, stamped on
        # every drained token (Request.token_chunks), every admission
        # (admission_log 5th element), and every trace event at the
        # boundary — the cross-reference key between all three.
        self.chunks = 0
        self.chaos = chaos
        self.scheduler.chaos = chaos
        if chaos is not None and self.tracer is not None:
            chaos.on_event = self._chaos_event
        if chaos is not None and chaos.p_stall > 0 and stall_patience <= 0:
            stall_patience = 4   # a stall must end in watchdog recovery
        self.stall_patience = int(stall_patience)
        self.fault_counters: Dict[str, int] = {
            "preemptions": 0, "pressure_preemptions": 0,
            "chaos_preemptions": 0, "watchdog_preemptions": 0,
            "resumes": 0, "timed_out": 0, "cancelled": 0,
            "rejected": 0, "rejected_infeasible": 0,
            "rejected_queue_full": 0, "rejected_shed_lower_class": 0,
        }
        # every preemption event, in order: the victim's class plus the
        # classes of the OTHER preemptable slots live at that instant —
        # the chaos/SLO tests assert interactive is only ever evicted
        # when no lower-priority victim existed
        self.preemption_log: List[Dict[str, Any]] = []

    # -------------------------------------------------------------- setup
    def _empty_cache(self):
        cache = self.spec.init_paged_cache()
        if self._rules is not None and self._rules.mesh is not None:
            shardings = self.spec.shardings(self._rules)
            cache = jax.tree.map(
                lambda x, s: jax.device_put(x, s) if s is not None else x,
                cache, shardings)
        if self.drafter is not None and self.drafter.kind == "model":
            cache["draft"] = self.drafter.init_cache(self.slots)
        return cache

    # ---------------------------------------------------------- telemetry
    @property
    def queue(self) -> List[Request]:
        return self.scheduler.queue

    @property
    def prefill_compiles(self) -> int:
        return self.executor.prefill_compiles

    @property
    def suffix_prefill_compiles(self) -> int:
        return self.executor.suffix_prefill_compiles

    @property
    def admit_compiles(self) -> int:
        return self.executor.admit_compiles

    @property
    def decode_compiles(self) -> int:
        return self.executor.decode_compiles

    def memory_stats(self) -> Dict[str, Any]:
        """Paged-cache memory telemetry (per-group page occupancy + HBM
        bytes per live generated token at the current instant)."""
        live = sum(len(r.out_tokens) + len(r.prompt)
                   for r in self._slot_req if r is not None)
        stats = self.spec.memory_stats(
            self.scheduler.pages_in_use_by_group, live)
        stats["peak_pages_in_use"] = self.scheduler.peak_pages_in_use
        stats["live_slots"] = sum(r is not None for r in self._slot_req)
        stats["peak_live_slots"] = self.peak_live_slots
        return stats

    def prefix_stats(self) -> Dict[str, Any]:
        """Prefix-sharing telemetry (hit rate, skipped prefill tokens,
        shared-page attaches, CoW copies, radix evictions)."""
        return self.scheduler.prefix_stats()

    def fault_stats(self) -> Dict[str, Any]:
        """Robustness telemetry: preemption / resume / timeout /
        cancellation / rejection counters, the recovered-prefill fraction
        of resumed admissions (replayed tokens that rode on radix pages
        instead of being recomputed), and the chaos schedule's own event
        counts when fault injection is active."""
        sched = self.scheduler
        stats: Dict[str, Any] = dict(self.fault_counters)
        stats["resume_admissions"] = sched.resume_admissions
        stats["resume_replayed_tokens"] = sched.resume_replayed_tokens
        stats["resume_recovered_tokens"] = sched.resume_recovered_tokens
        stats["recovered_prefill_fraction"] = (
            sched.resume_recovered_tokens / sched.resume_replayed_tokens
            if sched.resume_replayed_tokens else 0.0)
        if self.chaos is not None:
            stats["chaos"] = self.chaos.stats()
        return stats

    def latency_stats(self) -> Dict[str, Any]:
        """TTFT/TPOT p50/p99 per SLO class + goodput, from the host-side
        timestamps the chunk-boundary drain stamps on every request
        (``compute_latency_stats`` holds the math — pure, so the oracle
        tests grade it by hand).  Covers every request this engine has
        seen: finished, rejected, running, and still queued.  Also
        reports the dynamic-``prefill_budget`` throttle count (SLO
        policy on fused-chunk engines; 0 elsewhere)."""
        reqs = (list(self.finished) + list(self.rejected)
                + [r for r in self._slot_req if r is not None]
                + list(self.scheduler.queue))
        stats = compute_latency_stats(reqs)
        stats["budget_throttles"] = self.budget_throttles
        return stats

    def leaked_pages(self) -> int:
        """Pages still leased beyond what live slots and the radix index
        legitimately hold — at full drain (no live slots, empty queue)
        anything nonzero is a refcount leak.  The CI chaos smoke asserts
        this is 0 after every fault schedule."""
        sched = self.scheduler
        leaked = 0
        for key, pool in sched.pools.items():
            accounted = set()
            for lease in sched._leases.values():
                accounted.update(lease.get(key, ()))
            if sched.radix is not None and key == sched.share_key:
                stack = list(sched.radix.root.children.values())
                while stack:
                    node = stack.pop()
                    stack.extend(node.children.values())
                    accounted.add(node.page)
            leaked += pool.in_use - len(accounted)
        return leaked

    def spec_stats(self) -> Dict[str, Any]:
        """Speculative-decoding telemetry: acceptance rate (accepted
        drafts / proposed drafts) and committed tokens per verify step,
        from the device-side counters ``serve/sampling.spec_update``
        maintains.  Reading them is one host transfer — call between
        runs, not inside the serving loop."""
        if self.spec_config is None:
            return {"spec": False}
        steps, drafted, accepted, emitted = jax.device_get(
            (self.state["spec_steps"], self.state["spec_drafted"],
             self.state["spec_accepted"], self.state["spec_emitted"]))
        return {
            "spec": True,
            "drafter": self.drafter.kind,
            "spec_k": self.spec_config.k,
            "spec_steps": int(steps),
            "drafted_tokens": int(drafted),
            "accepted_tokens": int(accepted),
            "acceptance_rate": (float(accepted) / float(drafted)
                                if drafted else 0.0),
            "emitted_tokens": int(emitted),
            "tokens_per_step": (float(emitted) / float(steps)
                                if steps else 0.0),
        }

    # ------------------------------------------------------ observability
    def _trace(self, kind: str, rid: Optional[int] = None,
               slot: Optional[int] = None, ts: Optional[float] = None,
               **attrs: Any) -> None:
        """Record one lifecycle event when tracing is on.  Host-only:
        called at chunk boundaries with the boundary's existing clock
        read where one exists (``ts``), so the decode chunk stays
        sync-free and traced runs stay token-identical."""
        if self.tracer is None:
            return
        self.tracer.record(kind, self._clock() if ts is None else ts,
                           rid=rid, slot=slot, **attrs)

    def _chaos_event(self, fault: str, **attrs: Any) -> None:
        slot = attrs.pop("slot", None)
        self._trace("chaos", slot=slot, fault=fault, **attrs)

    def observe(self, *, spec: bool = True) -> Dict[str, Any]:
        """One flat snapshot of every stats surface — ``memory_stats`` /
        ``fault_stats`` / ``latency_stats`` / ``spec_stats`` /
        ``prefix_stats`` — under the stable dotted metric names declared
        in ``repro.serve.metrics`` (``pool.pages_in_use``,
        ``sched.preemptions.pressure``, ``spec.acceptance``, ...).
        ``spec=False`` skips the one device read behind
        ``spec_stats``."""
        return metrics_mod.snapshot(self, spec=spec)

    def export_trace(self, path: Optional[str] = None) -> Dict[str, Any]:
        """Chrome-trace / Perfetto JSON of the buffered lifecycle events
        (per-slot tracks, per-request flow arrows across preempt/resume,
        counter tracks for pool occupancy and queue depth).  Writes to
        ``path`` when given; returns the trace object either way.
        ``benchmarks/check_trace.py`` validates the schema in CI."""
        if self.tracer is None:
            raise ValueError("tracing is disabled; construct the Engine "
                             "with trace=True (or a capacity / Tracer)")
        obj = trace_mod.to_chrome_trace(self.tracer.events())
        if path is not None:
            with open(path, "w") as f:
                json.dump(obj, f)
        return obj

    def explain(self, rid: int) -> str:
        """Per-request text explain: the causal chain from submit to
        terminal with per-phase durations, from the lifecycle trace."""
        if self.tracer is None:
            raise ValueError("tracing is disabled; construct the Engine "
                             "with trace=True (or a capacity / Tracer)")
        return trace_mod.explain(self.tracer.events(), rid)

    # ------------------------------------------------------------ serving
    def submit(self, req: Request) -> Optional[RequestRejected]:
        """Enqueue a request, or shed it with a typed result.

        Never raises ``PagePoolExhausted``: a request whose worst-case
        reservation exceeds the pool's total budget gets an
        ``"infeasible"`` ``RequestRejected`` (queueing it would wedge the
        head of the line), and one arriving at a full bounded queue is
        handled by ``shed_policy`` — ``"reject"`` sheds it immediately,
        ``"block"`` drives the engine until the queue drains (submission
        backpressure), ``"evict-lru-prefix"`` first reclaims unreferenced
        radix prefix pages and drains the queue into freed slots, then
        sheds only if the queue is still full.  Returns ``None`` when the
        request was accepted.  ``ValueError`` for requests violating the
        ``max_len`` contract still raises — that is a caller bug, not
        load."""
        if len(req.prompt) + req.max_new_tokens > self.max_len \
                and not self.cfg.supports_long_context:
            # full-attention page tables cap at max_len tokens; a longer
            # prompt (or a generation budget running past the table)
            # would silently mod-wrap like a ring, overwriting the
            # oldest KV — including prefix pages other slots or the
            # radix index may reference
            raise ValueError(
                f"prompt length {len(req.prompt)} + max_new_tokens "
                f"{req.max_new_tokens} exceeds max_len={self.max_len} "
                f"and {self.cfg.name} has non-windowed attention; raise "
                "max_len or lower max_new_tokens")
        if self.chunked_prefill:
            if not req.prompt:
                raise ValueError("chunked_prefill requires a non-empty "
                                 "prompt")
            if len(req.prompt) + req.max_new_tokens > self.max_len:
                # the fused chunk streams prompt tokens from a per-slot
                # [max_len] staging buffer; a preempted request replays
                # its generated tail as prompt on resume, so the whole
                # prompt+generation span must fit even for windowed archs
                raise ValueError(
                    f"prompt length {len(req.prompt)} + max_new_tokens "
                    f"{req.max_new_tokens} exceeds max_len={self.max_len}: "
                    "chunked_prefill stages prompts in a max_len-sized "
                    "buffer; raise max_len or pass chunked_prefill=False")
        try:
            self.scheduler.validate(req)
        except PagePoolExhausted as e:
            return self._reject(req, "infeasible", str(e))
        if req.submit_time is None:       # TTFT clock starts here; a
            req.submit_time = self._clock()   # resume keeps the original
        if req.deadline is None and req.ttl is not None:
            req.deadline = self._clock() + req.ttl
        self._trace("submit", rid=req.rid, ts=req.submit_time,
                    slo_class=req.slo_class, plen=len(req.prompt),
                    max_new=req.max_new_tokens)
        if self.queue_limit is not None \
                and len(self.scheduler.queue) >= self.queue_limit:
            shed = self._shed(req)
            if shed is not None:
                return shed
        self.scheduler.submit(req)
        return None

    def _reject(self, req: Request, kind: str,
                reason: str) -> RequestRejected:
        req.status = RequestStatus.REJECTED
        req.reject_reason = reason
        req.done = True
        if req.finish_time is None:
            req.finish_time = self._clock()
        self.fault_counters["rejected"] += 1
        self.fault_counters[f"rejected_{kind}"] += 1
        self.rejected.append(req)
        self._trace("reject", rid=req.rid, ts=req.finish_time,
                    why=kind, status=req.status)
        return RequestRejected(req=req, kind=kind, reason=reason)

    def _shed(self, req: Request) -> Optional[RequestRejected]:
        """Apply the shed policy to a submission hitting a full queue.
        Returns the rejection, or None once there is room."""
        def room() -> bool:
            return len(self.scheduler.queue) < self.queue_limit

        if self.shed_policy == "block":
            # submission backpressure: run the engine until the queue
            # drains (bounded — every step finishes or reaps work)
            for _ in range(100_000):
                if room():
                    return None
                if not (self.scheduler.queue or self._live()):
                    break
                self.step()
            if room():
                return None
        elif self.shed_policy == "evict-lru-prefix":
            sched = self.scheduler
            if sched.radix is not None:
                pool = sched.pools[sched.share_key]
                while sched.radix.evict_one(pool) is not None:
                    sched.radix_evictions += 1
            self._reap()
            self._admit()
            if room():
                return None
        elif self.shed_policy == "shed-lowest-class":
            # class-aware load shedding: drop the queued request of the
            # STRICTLY lowest-priority class (worst slack on ties) to
            # make room for a more urgent arrival; when nothing queued
            # outranks the arrival downward, the arrival itself sheds
            now = self._clock()
            queue = self.scheduler.queue
            victim = max(
                (r for r in queue if r.priority > req.priority),
                key=lambda r: (r.priority, -r.ttft_slack(now)),
                default=None)
            if victim is not None:
                queue.remove(victim)
                self.fault_counters["rejected_shed_lower_class"] += 1
                self._reject(
                    victim, "queue_full",
                    f"shed for higher-priority rid={req.rid} "
                    f"({req.slo_class} over {victim.slo_class})")
                return None
        return self._reject(
            req, "queue_full",
            f"admission queue full ({self.queue_limit} waiting, "
            f"shed_policy={self.shed_policy})")

    def bucket_for(self, plen: int) -> int:
        for b in self.buckets:
            if b >= plen:
                return b
        b = _next_pow2(max(plen, 1))
        self.buckets.append(b)   # keep the ≤ len(buckets) compile invariant
        self.buckets.sort()
        return b

    def _ctx_bucket(self, nblocks: int) -> int:
        """Pad the shared-prefix ctx gather to a power-of-two block count
        (capped at the sharing group's table width), so the suffix
        prefill compiles O(log^2) executables, not one per match."""
        ring = self.spec.group_of(self.spec.share_group_key).ring_blocks
        return min(_next_pow2(max(nblocks, 1)), ring)

    def _ctx_row(self, adm: Admission, s: int) -> np.ndarray:
        """Trash-padded page row naming the ``ceil(s/P)`` context pages a
        suffix prefill at offset ``s`` gathers from the slot's table."""
        gkey = self.spec.share_group_key
        nctx = -(-s // self.spec.page_size)
        cb = self._ctx_bucket(nctx)
        row = np.full((cb,), self.spec.group_of(gkey).trash_page, np.int32)
        row[:nctx] = adm.rows[gkey][:nctx]
        return row

    def _batched_admit(self, entries: List[Dict], valids: List[bool]):
        """Apply up to ``slots`` admissions in ONE splice dispatch.  The
        entry list is padded to the slot count by aliasing the first
        entry with its valid flag off (trash-routed writes, bookkeeping
        untouched), so the executable count stays 1 for any batch size."""
        if not entries:
            return
        ent = entries + [entries[0]] * (self.slots - len(entries))
        vf = list(valids) + [False] * (self.slots - len(valids))
        rows = {g.key: jnp.asarray(
            np.stack([en["rows"][g.key] for en in ent]).astype(np.int32))
            for g in self.spec.groups}
        if self.chunked_prefill:
            # fused admission is pure bookkeeping: table rows + prompt
            # staging; the chunk step itself prefills
            self.cache, self.state = self.executor.admit(
                self.cache, self.state,
                jnp.asarray([en["slot"] for en in ent], jnp.int32),
                jnp.asarray([en["start"] for en in ent], jnp.int32),
                jnp.asarray([en["plen"] for en in ent], jnp.int32),
                rows,
                jnp.asarray(np.stack([en["prompt"] for en in ent]),
                            jnp.int32),
                jnp.asarray([en["out_len0"] for en in ent], jnp.int32),
                jnp.asarray([en["max_new"] for en in ent], jnp.int32),
                jnp.asarray([en["eos"] for en in ent], jnp.int32),
                jnp.asarray([en["temp"] for en in ent], jnp.float32),
                jnp.asarray(vf))
            return
        drafts = None
        if self.drafter is not None and self.drafter.kind == "model":
            drafts = tuple(en["draft"] for en in ent)
        hist = None
        if self._hist_cap:
            hist = jnp.asarray(np.stack([en["hist"] for en in ent]),
                               jnp.int32)
        self.cache, self.state = self.executor.admit(
            self.cache, self.state,
            tuple(en["one_cache"] for en in ent), drafts,
            jnp.asarray([en["slot"] for en in ent], jnp.int32),
            jnp.asarray([en["start"] for en in ent], jnp.int32),
            jnp.asarray([en["plen"] for en in ent], jnp.int32),
            rows,
            tuple(en["tok"] for en in ent),
            jnp.asarray([en["out_len0"] for en in ent], jnp.int32),
            jnp.asarray([en["max_new"] for en in ent], jnp.int32),
            jnp.asarray([en["eos"] for en in ent], jnp.int32),
            jnp.asarray([en["temp"] for en in ent], jnp.float32),
            jnp.asarray(vf),
            hist)

    def warmup(self) -> None:
        """Pre-compile every prefill bucket, the batched admission
        splice, and the decode chunk so serving never pays a compile
        inside the hot loop.  Semantically inert: admissions use trash
        page-table rows with their valid flag off, and the PRNG key is
        restored afterwards, so seeded sampled runs are identical with or
        without warmup.  (Suffix-prefill executables still compile lazily
        on the first prefix hit per shape pair.)"""
        key_before = jnp.array(self.state["key"])   # copy: state is donated
        trash_rows = {g.key: np.full((g.ring_blocks,), g.trash_page,
                                     np.int32) for g in self.spec.groups}
        if self.chunked_prefill:
            # fused mode: no prefill buckets exist.  ONE inert admission
            # compiles the bookkeeping splice, ONE chunk compiles the
            # fused executable — total steady-state compile count 2.
            entry = {"slot": 0, "start": 0, "plen": 0, "rows": trash_rows,
                     "prompt": np.zeros((self.max_len,), np.int32),
                     "out_len0": 1, "max_new": 0, "eos": -1, "temp": 0.0}
            self._batched_admit([entry], [False])
            _, self.cache, self.state = self.executor.chunk(
                self.params, self.draft_params, self.cache, self.state)
            self.cache = self.executor.free_slot(self.cache, jnp.int32(0))
            self.state = dict(self.state, key=key_before)
            return
        for b in self.buckets:
            tokens = jnp.zeros((1, b), jnp.int32)
            length = jnp.zeros((1,), jnp.int32)
            key = jax.random.PRNGKey(0)
            temp = jnp.zeros((1,), jnp.float32)
            pad_to = self.buckets[-1]
            tok, one_cache = self.executor.prefill(
                self.params, tokens, length, key, temp, pad_to)
            draft = None
            if self.drafter is not None and self.drafter.kind == "model":
                draft = self.executor.draft_prefill(
                    self.draft_params, tokens, length, pad_to)
            entry = {"slot": 0, "start": 0, "plen": 0, "rows": trash_rows,
                     "tok": tok, "one_cache": one_cache, "draft": draft,
                     "out_len0": 1, "max_new": 0, "eos": -1, "temp": 0.0,
                     "hist": np.zeros((self._hist_cap,), np.int32)}
            self._batched_admit([entry], [False])
        _, self.cache, self.state = self.executor.chunk(
            self.params, self.draft_params, self.cache, self.state)
        # eviction splice: compiling it here keeps the first request
        # completion from paying a trace inside the serving loop (slot 0
        # is idle, so re-trashing its table rows is inert)
        self.cache = self.executor.free_slot(self.cache, jnp.int32(0))
        self.state = dict(self.state, key=key_before)

    def _req_temp(self, req: Request) -> float:
        if req.temperature is not None:
            return float(req.temperature)
        return self.default_temp

    @property
    def _chunked_ok(self) -> bool:
        """Prompts longer than the largest bucket can run as several
        suffix-prefill segments when the arch has the suffix machinery
        (single full-attention pool group) and no model drafter (whose
        dense draft prefill has no suffix path)."""
        return (self.spec.prefix_sharing_capable
                and (self.drafter is None or self.drafter.kind != "model"))

    def _chunked_prefill(self, adm: Admission, s: int) -> int:
        """Run all but the final ``<= Bmax`` prompt tokens of an overlong
        prompt as bucket-sized segments through the suffix-prefill path —
        each segment attends to the pages earlier segments spliced — and
        return the final segment's start offset.  Reuses the existing
        buckets and the existing suffix executables: no new compiles
        beyond the (segment bucket, ctx bucket) pairs sharing already
        pays for."""
        req, slot = adm.req, adm.slot
        prompt = req.effective_prompt
        plen = len(prompt)
        bmax = self.buckets[-1]
        rows = {k: jnp.asarray(v) for k, v in adm.rows.items()}
        cur = s
        while plen - cur > bmax:
            seg = list(prompt[cur:cur + bmax])
            self._key, sub = jax.random.split(self._key)
            temp = jnp.zeros((1,), jnp.float32)
            if cur == 0:
                _tok, oc = self.executor.prefill(
                    self.params, jnp.asarray([seg], jnp.int32),
                    jnp.asarray([bmax], jnp.int32), sub, temp, bmax)
            else:
                pools = [c if (c is not None and "pk" in c) else None
                         for c in self.cache["layers"]]
                _tok, oc = self.executor.prefill_suffix(
                    self.params, jnp.asarray([seg], jnp.int32),
                    jnp.asarray([bmax], jnp.int32), jnp.int32(cur),
                    jnp.asarray(self._ctx_row(adm, cur)), pools, sub,
                    temp, bmax)
            self.cache = self.executor.splice(
                self.cache, oc, jnp.int32(slot), jnp.int32(cur),
                jnp.int32(cur + bmax), rows)
            cur += bmax
        return cur

    def _admit(self) -> None:
        """Chunk-boundary admission with pool-pressure preemption: admit
        while the queue head fits; when it does not but a slot is free
        (pages, not slots, are the bottleneck), evict a victim — fewest
        tokens decoded first, most radix-recoverable on ties — and retry.
        Victims requeue at the back and resume through the radix/suffix
        path; each carries a ``max_preemptions`` cap, and at most
        ``slots`` evictions happen per boundary, so admission cannot
        livelock."""
        if self.chaos is not None and self._live() \
                and self.chaos.deny_admission():
            return   # injected admission-time exhaustion (delay, not loss)
        self._do_admissions()
        if not self.preemption:
            return
        guard = 0
        while self.scheduler.queue and guard < self.slots \
                and any(r is None for r in self._slot_req):
            victim = self._pick_victim()
            if victim is None:
                return
            guard += 1
            qlen = len(self.scheduler.queue)
            self._preempt_slot(victim, "pressure")
            self._do_admissions()
            if len(self.scheduler.queue) > qlen:
                return   # eviction did not unblock the head; stop churning

    def _pick_victim(self) -> Optional[int]:
        """Victim policy: lowest SLO-class priority first (batch yields
        to interactive under pool pressure — for legacy single-class
        workloads every request grades identically, so the historical
        order is unchanged), then fewest tokens decoded (least work
        lost), then most radix-recoverable pages (cheapest to resume),
        then lowest slot.  Slots at their preemption cap are never
        picked."""
        best, best_score = None, None
        P = self.spec.page_size
        for slot in range(self.slots):
            req = self._slot_req[slot]
            if req is None or req.preemptions >= req.max_preemptions:
                continue
            valid = len(req.effective_prompt) - (1 if req.out_tokens else 0)
            recoverable = valid // P if self.scheduler.radix is not None \
                else 0
            score = (-req.priority, len(req.out_tokens), -recoverable,
                     slot)
            if best_score is None or score < best_score:
                best, best_score = slot, score
        return best

    def _clear_slot(self, slot: int) -> None:
        """Device+host teardown shared by preemption and reaping: drop
        page references, re-trash the table rows, clear the active flag
        so the next chunk's dead-tail steps neither sample nor write."""
        self._slot_req[slot] = None
        self._slot_first_tok[slot] = None
        self._slot_first_pending[slot] = False
        self._slot_stale[slot] = 0
        self._slot_seen_len[slot] = 0
        self._slot_plen[slot] = 0
        if self.chaos is not None:
            self.chaos.clear_stall(slot)
        self.scheduler.release(slot)
        self.cache = self.executor.free_slot(self.cache, jnp.int32(slot))
        self.state = self.executor.deactivate(self.state, jnp.int32(slot))

    def _finish_terminal(self, req: Request, status: str) -> None:
        req.status = status
        req.done = True
        if req.finish_time is None:
            req.finish_time = self._clock()
        if status == RequestStatus.TIMED_OUT:
            self.fault_counters["timed_out"] += 1
        elif status == RequestStatus.CANCELLED:
            self.fault_counters["cancelled"] += 1
        self.finished.append(req)
        self._trace("finish", rid=req.rid, ts=req.finish_time,
                    status=req.status, tokens=len(req.out_tokens))

    def _evict_slot(self, slot: int, status: str) -> None:
        req = self._slot_req[slot]
        self._clear_slot(slot)
        self._finish_terminal(req, status)

    def _preempt_slot(self, slot: int, why: str) -> None:
        """Evict a running slot and requeue its request for resumption.
        The request's generated-so-far tokens replay as prompt tail on
        re-admission; full pages are preserved in the radix index first,
        so the resume's prefill recovers them as a prefix hit instead of
        recomputing."""
        req = self._slot_req[slot]
        if len(req.out_tokens) >= req.max_new_tokens or (
                req.eos_id is not None and req.out_tokens
                and req.out_tokens[-1] == int(req.eos_id)):
            # everything was already drained (a stalled slot can hide its
            # own finish): complete, don't resume an empty remainder
            self._evict_slot(slot, RequestStatus.FINISHED)
            return
        req.preemptions += 1
        self.fault_counters["preemptions"] += 1
        self.fault_counters[f"{why}_preemptions"] += 1
        self.preemption_log.append({
            "rid": req.rid, "slo_class": req.slo_class, "why": why,
            "candidate_classes": [
                r.slo_class for s2, r in enumerate(self._slot_req)
                if r is not None and s2 != slot
                and r.preemptions < r.max_preemptions]})
        self._trace("preempt", rid=req.rid, slot=slot, why=why,
                    preemptions=req.preemptions)
        upto = None
        if self.chunked_prefill \
                and self._slot_seen_len[slot] < self._slot_plen[slot]:
            # preempted mid-prefill: only the pages the host has SEEN
            # covered are certainly written (a chaos-stalled drain may
            # trail the device); preserve exactly that prefix
            upto = self._slot_seen_len[slot]
        self.scheduler.preserve(slot, req, upto=upto)
        self._clear_slot(slot)
        self.scheduler.requeue(req)

    def _reap(self) -> None:
        """Chunk-boundary reaping of cancelled and deadline-expired
        requests, queued or running: pages free immediately, the typed
        terminal status lands in ``finished``, and the very same
        boundary's admission pass can re-lease the freed slot."""
        now = self._clock()

        def dead(req: Request) -> bool:
            return req.cancel_requested or (
                req.deadline is not None and now > req.deadline)

        for req in [r for r in self.scheduler.queue if dead(r)]:
            self.scheduler.queue.remove(req)
            self._trace("reap", rid=req.rid, ts=now,
                        why="cancelled" if req.cancel_requested
                        else "timed_out")
            self._finish_terminal(
                req, RequestStatus.CANCELLED if req.cancel_requested
                else RequestStatus.TIMED_OUT)
        for slot in range(self.slots):
            req = self._slot_req[slot]
            if req is None or not dead(req):
                continue
            self._trace("reap", rid=req.rid, slot=slot, ts=now,
                        why="cancelled" if req.cancel_requested
                        else "timed_out")
            self._evict_slot(
                slot, RequestStatus.CANCELLED if req.cancel_requested
                else RequestStatus.TIMED_OUT)

    def _do_admissions(self) -> None:
        free = [i for i in range(self.slots) if self._slot_req[i] is None]
        pend: List[Dict] = []
        pvalid: List[bool] = []

        def flush():
            self._batched_admit(pend, pvalid)
            pend.clear()
            pvalid.clear()

        # stamp this boundary's admissions with the current chunk id so
        # the admission_log cross-references token_chunks / trace events
        self.scheduler.current_chunk = self.chunks
        now = self._clock()
        for adm in self.scheduler.admissions(free, now=now):
            req, slot = adm.req, adm.slot
            prompt = req.effective_prompt   # resume: replay emitted tail
            plen = len(prompt)
            if self.tracer is not None:
                resume = req.preemptions > 0
                if adm.suffix_start > 0:
                    self._trace("radix_hit", rid=req.rid, slot=slot,
                                ts=now, matched_tokens=adm.suffix_start,
                                resume=resume)
                if adm.cow is not None:
                    self._trace("cow", rid=req.rid, slot=slot, ts=now,
                                src_page=adm.cow[1], dst_page=adm.cow[2])
                if resume:
                    self._trace("resume", rid=req.rid, slot=slot, ts=now,
                                preemptions=req.preemptions)
                self._trace("admit", rid=req.rid, slot=slot, ts=now,
                            chunk=self.chunks,
                            suffix_start=adm.suffix_start, plen=plen,
                            resume=resume)
            if self.chunked_prefill:
                # fused chunked prefill: no prefill dispatch at all.  The
                # admission stages the prompt and rewinds the slot's len
                # to the cursor (shared-prefix / resume boundary); the
                # next chunks stream prefill_budget tokens per micro-step
                # through the fused executable.  No flush-before-CoW
                # dance: fused admissions write no KV, and the radix
                # index only ever names fully-written pages (deferred
                # insert), so a CoW source is always materialized.
                if adm.cow is not None:
                    _blk, src, dst = adm.cow
                    self.cache = self.executor.copy_page(
                        self.cache, jnp.int32(src), jnp.int32(dst),
                        self.scheduler.share_key)
                pbuf = np.zeros((self.max_len,), np.int32)
                pbuf[:plen] = prompt
                eos = -1 if req.eos_id is None else int(req.eos_id)
                pend.append({"slot": slot, "start": adm.suffix_start,
                             "plen": plen, "rows": adm.rows,
                             "prompt": pbuf,
                             "out_len0": len(req.out_tokens),
                             "max_new": req.max_new_tokens, "eos": eos,
                             "temp": self._req_temp(req)})
                pvalid.append(True)
                if req.preemptions > 0:
                    self.fault_counters["resumes"] += 1
                self._slot_req[slot] = req
                self._slot_seen_len[slot] = adm.suffix_start
                self._slot_plen[slot] = plen
                self._slot_stale[slot] = 0
                continue
            self._key, sub = jax.random.split(self._key)
            temp = jnp.asarray([self._req_temp(req)], jnp.float32)
            s = adm.suffix_start
            if adm.cow is not None or s > 0:
                # a pending admission in this same batch may own the CoW
                # source / ctx pages this one is about to read (radix
                # match against pages not yet spliced): flush first
                flush()
            if adm.cow is not None:
                # the slot will write into a shared page (partial-page
                # match, or last page of a fully-matched prompt): give it
                # a private copy BEFORE any prefill gather or splice
                _blk, src, dst = adm.cow
                self.cache = self.executor.copy_page(
                    self.cache, jnp.int32(src), jnp.int32(dst),
                    self.scheduler.share_key)
            if plen - s > self.buckets[-1] and self._chunked_ok:
                flush()    # segment splices interleave with self.cache
                s = self._chunked_prefill(adm, s)
            if s > 0:
                # prefix hit and/or chunked prefill: prefill only the
                # remaining tail, reading the earlier tokens from the
                # slot's (shared or just-spliced) pages
                suffix = list(prompt[s:])
                bucket = self.bucket_for(len(suffix))
                padded = suffix + [0] * (bucket - len(suffix))
                pools = [c if (c is not None and "pk" in c) else None
                         for c in self.cache["layers"]]
                tok, one_cache = self.executor.prefill_suffix(
                    self.params, jnp.asarray([padded], jnp.int32),
                    jnp.asarray([len(suffix)], jnp.int32), jnp.int32(s),
                    jnp.asarray(self._ctx_row(adm, s)), pools, sub, temp,
                    self.buckets[-1])
            else:
                bucket = self.bucket_for(plen)
                padded = list(prompt) + [0] * (bucket - plen)
                tok, one_cache = self.executor.prefill(
                    self.params, jnp.asarray([padded], jnp.int32),
                    jnp.asarray([plen], jnp.int32), sub, temp,
                    self.buckets[-1])
            draft = None
            if self.drafter is not None and self.drafter.kind == "model":
                dbucket = self.bucket_for(plen)
                dpadded = list(prompt) + [0] * (dbucket - plen)
                draft = self.executor.draft_prefill(
                    self.draft_params, jnp.asarray([dpadded], jnp.int32),
                    jnp.asarray([plen], jnp.int32), self.buckets[-1])
            hist = None
            if self._hist_cap:
                hist = np.zeros((self._hist_cap,), np.int32)
                head = prompt[:self._hist_cap]
                hist[:len(head)] = head
            eos = -1 if req.eos_id is None else int(req.eos_id)
            pend.append({"slot": slot, "start": s, "plen": plen,
                         "rows": adm.rows, "tok": tok,
                         "one_cache": one_cache, "draft": draft,
                         "out_len0": len(req.out_tokens) + 1,
                         "max_new": req.max_new_tokens, "eos": eos,
                         "temp": self._req_temp(req), "hist": hist})
            pvalid.append(True)
            if req.preemptions > 0:
                self.fault_counters["resumes"] += 1
            self._slot_req[slot] = req
            self._slot_first_tok[slot] = tok   # on device until drain
            self._slot_first_pending[slot] = True
            self._slot_stale[slot] = 0
        flush()
        self.peak_live_slots = max(
            self.peak_live_slots,
            sum(r is not None for r in self._slot_req))

    def _update_prefill_budgets(self) -> None:
        """``prefill_budget`` as a dynamic SLO knob, applied at the chunk
        boundary like every other policy: while any interactive request
        has blown its TTFT slack and is still waiting on a first token,
        NON-interactive slots' per-micro-step prompt slice shrinks to a
        quarter chunk (floor 1) so the urgent prefill and the decode
        rows get the arithmetic; full budgets restore once slack
        recovers.  A pure host->device value update — ``pbudget`` is
        data, not shape, so the one fused executable never retraces, and
        nothing here reads from the device."""
        if not self.chunked_prefill or self.policy != "slo":
            return
        S = self.executor.chunk_rows
        now = self._clock()

        def urgent(r: Request) -> bool:
            return (r.priority == 0 and r.first_token_time is None
                    and r.ttft_slack(now) < 0.0)

        pressure = any(urgent(r) for r in self.scheduler.queue) or any(
            r is not None and urgent(r) for r in self._slot_req)
        throttled = max(1, S // 4)
        vec = [throttled if (pressure and r is not None
                             and r.priority > 0) else S
               for r in self._slot_req]
        if vec != self._budget_vec:
            if pressure:
                self.budget_throttles += 1
            self._budget_vec = vec
            self.state = dict(self.state,
                              pbudget=jnp.asarray(vec, jnp.int32))

    def step_chunk(self) -> jax.Array:
        """Dispatch one fused decode chunk.  No host synchronization —
        safe to call under ``jax.transfer_guard_device_to_host``."""
        toks, self.cache, self.state = self.executor.chunk(
            self.params, self.draft_params, self.cache, self.state)
        self.steps += self.sync_interval
        return toks

    def _drain(self, toks: jax.Array) -> None:
        """One batched device->host transfer: token history + slot state.
        The history is [T, slots] with -1 where a slot was idle — and,
        under speculation, wherever a draft/verify round committed fewer
        than ``K+1`` tokens — so each slot's new tokens are the
        non-negative entries of its column, in order.  Finished slots are
        evicted: page refcounts drop in the scheduler (exclusive pages
        rejoin the free list; shared/radix-indexed pages survive for
        their other referents) and the slot's page-table rows are pointed
        at the trash pages, so its dead tail writes cannot touch
        re-leased pages."""
        fetch = (toks, self.state["out_len"], self.state["active"],
                 [self._slot_first_tok[i] for i in range(self.slots)])
        if self.chunked_prefill:
            # also drain the prefill cursor (cache["len"], capped by the
            # prompt length per slot below) in the SAME transfer
            toks_np, out_len, active, firsts, cache_len = jax.device_get(
                fetch + (self.cache["len"],))
        else:
            toks_np, out_len, active, firsts = jax.device_get(fetch)
            cache_len = None
        self.host_syncs += 1
        now = self._clock()   # one host clock read stamps every token
        self.chunks += 1      # chunk sequence number for this drain
        if self.tracer is not None:
            self._trace("chunk", ts=now, chunk=self.chunks,
                        queue_depth=len(self.scheduler.queue),
                        pages_in_use=self.scheduler.pages_in_use,
                        live_slots=sum(r is not None
                                       for r in self._slot_req))
        watchdog: List[int] = []
        for slot in range(self.slots):
            req = self._slot_req[slot]
            if req is None:
                continue
            if self.chaos is not None and self.chaos.stalled(slot):
                # injected straggler: the slot reported nothing this
                # boundary.  Progress stalls host-side until the watchdog
                # preempts it; tokens lost in between regenerate on
                # resume (token-identical at temperature 0).
                self._slot_stale[slot] += 1
                if self.stall_patience \
                        and self._slot_stale[slot] >= self.stall_patience:
                    watchdog.append(slot)
                continue
            progressed = False
            if self.chunked_prefill:
                # prefill cursor: past the prompt end, len counts decoded
                # tokens — those drain through out_len as usual
                plen0 = self._slot_plen[slot]
                seen = min(int(cache_len[slot]), plen0)
                if seen > self._slot_seen_len[slot]:
                    progressed = True   # mid-prefill progress ≠ a stall
                    prev = self._slot_seen_len[slot]
                    self._slot_seen_len[slot] = seen
                    if prev < plen0:
                        self._trace("prefill", rid=req.rid, slot=slot,
                                    ts=now, seen=seen, plen=plen0,
                                    chunk=self.chunks)
                    if prev < plen0 <= seen:
                        # prefill completed this chunk: NOW every prompt
                        # page is written, so the prompt becomes visible
                        # to the radix prefix index (deferred insert)
                        self.scheduler.index_slot(slot, req, plen0)
            if self._slot_first_pending[slot]:
                # prefill-sampled token (resumes arrive with a non-empty
                # out_tokens, so presence of output cannot gate this)
                req.out_tokens.append(int(firsts[slot][0]))
                req.token_times.append(now)
                req.token_chunks.append(self.chunks)
                if req.first_token_time is None:
                    req.first_token_time = now
                self._slot_first_pending[slot] = False
            k = int(out_len[slot]) - len(req.out_tokens)
            if k > 0:
                # the serving loop drains every chunk, so the whole gap is
                # in this history; a caller draining a partial history
                # (benchmarks) just gets what it carries
                vals = [int(t) for t in toks_np[:, slot] if t >= 0]
                assert len(vals) <= k, (slot, len(vals), k)
                req.out_tokens.extend(vals[-k:])
                req.token_times.extend([now] * len(vals[-k:]))
                req.token_chunks.extend([self.chunks] * len(vals[-k:]))
                if req.first_token_time is None and req.token_times:
                    # TTFT from the ORIGINAL submit_time — a request
                    # preempted mid-prefill and resumed later keeps its
                    # submit stamp, so the wait is charged to it
                    req.first_token_time = now
                self._slot_stale[slot] = 0
            elif self.stall_patience and not progressed:
                self._slot_stale[slot] += 1
                if self._slot_stale[slot] >= self.stall_patience:
                    watchdog.append(slot)
                    continue
            if not active[slot]:
                req.status = RequestStatus.FINISHED
                req.done = True
                req.finish_time = now
                self.finished.append(req)
                self._trace("finish", rid=req.rid, slot=slot, ts=now,
                            status=req.status,
                            tokens=len(req.out_tokens))
                self._slot_req[slot] = None
                self._slot_first_tok[slot] = None
                self._slot_first_pending[slot] = False
                self._slot_stale[slot] = 0
                self.scheduler.release(slot)
                self.cache = self.executor.free_slot(self.cache,
                                                     jnp.int32(slot))
        for slot in watchdog:
            # straggler recovery: treat the unresponsive slot as lost and
            # resume its request from the last drained token
            self._preempt_slot(slot, "watchdog")

    def _live(self) -> bool:
        return any(r is not None for r in self._slot_req)

    def step(self) -> None:
        """One reap + admit + fused-chunk + drain round
        (``sync_interval`` decode steps per call).  All policy — deadline
        reaping, cancellation, preemption, admission — runs on the host
        at this boundary; the chunk itself stays one sync-free
        executable."""
        self._reap()
        if self.chaos is not None:
            live = [i for i in range(self.slots)
                    if self._slot_req[i] is not None]
            self.chaos.tick(live)
            for slot in self.chaos.storm_victims(live):
                if self.policy == "slo":
                    # chaos decides THAT a preemption storm hits; under
                    # the SLO policy the class-aware victim rule decides
                    # WHO — interactive slots are evicted last, exactly
                    # as under genuine pool pressure
                    picked = self._pick_victim()
                    if picked is None:
                        continue
                    slot = picked
                if self._slot_req[slot] is not None:
                    self._preempt_slot(slot, "chaos")
        self._admit()
        self._update_prefill_budgets()
        if not self._live():
            if not self.scheduler.can_progress(0, now=self._clock()):
                head = self.queue[0]
                raise PagePoolExhausted(
                    f"wedged: rid={head.rid} cannot be admitted "
                    f"({self.scheduler.pool.free_pages} pages free) and no "
                    "slot is live to release more")
            return
        self._drain(self.step_chunk())

    def run(self, max_steps: int = 1000) -> List[Request]:
        while (self.queue or self._live()) and self.steps < max_steps:
            self.step()
        return self.finished
