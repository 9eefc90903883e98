"""On-device token sampling for the serving fast path — including the
speculative-decoding accept/reject sampler.

Everything here is shape-stable and jit-friendly: no host round trips, no
data-dependent shapes.  Greedy vs. stochastic is selected *per slot* with a
``temperature`` vector (0 == greedy) via ``jnp.where``, so one compiled
decode step serves mixed greedy/sampled batches.  The PRNG key is threaded
through the engine's device-side slot state — the host never touches it.

The speculative half (``spec_probs`` / ``spec_accept`` / ``spec_update``)
implements standard rejection sampling over ``K`` drafted tokens verified
by one multi-token target dispatch (``models/transformer.forward_verify``):
draft ``d_i`` is accepted with probability ``min(1, p(d_i)/q(d_i))``; the
first rejection resamples from the residual ``norm(max(p - q, 0))``, and a
fully-accepted draft earns a bonus token from the target's last-position
distribution.  At temperature 0 both ``p`` and ``q`` collapse to point
masses, so the rule degenerates to "accept while the draft matches the
target argmax, then emit the target argmax" — output is token-identical to
non-speculative greedy decoding; at temperature > 0 the emitted
distribution equals the target's (the standard speculative-sampling
guarantee), whatever the drafter proposes."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def sample(logits: jax.Array, key: jax.Array, *,
           temperature: jax.Array, top_k: int = 0) -> jax.Array:
    """Sample next tokens from ``logits`` [B, V] -> [B] int32.

    temperature: [B] float32, 0.0 selects argmax for that row.
    top_k: static int; 0 disables the top-k filter.  Rows share one key but
    draw independent categoricals (jax.random.categorical is per-row).
    """
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temperature = jnp.asarray(temperature, jnp.float32)
    # safe divisor for greedy rows (their sampled value is discarded)
    safe_t = jnp.where(temperature > 0.0, temperature, 1.0)[:, None]
    if top_k and top_k < logits.shape[-1]:
        vals, idx = jax.lax.top_k(logits, top_k)        # [B,K], [B,K]
        draw = jax.random.categorical(key, vals / safe_t, axis=-1)
        sampled = jnp.take_along_axis(idx, draw[:, None], axis=-1)[:, 0]
    else:
        sampled = jax.random.categorical(key, logits / safe_t, axis=-1)
    sampled = sampled.astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


def make_slot_state(slots: int, seed: int = 0, hist_cap: int = 0,
                    spec: bool = False, prompt_cap: int = 0,
                    prefill_budget: int = 0) -> dict:
    """Device-side per-slot bookkeeping for the fused decode step.

    tokens:   last token fed/emitted per slot (decode input)
    out_len:  generated tokens so far (incl. the prefill-sampled one)
    max_new:  generation budget per slot
    eos:      per-slot EOS id, -1 for none
    active:   slot is decoding a live request
    temp:     per-slot sampling temperature (0 == greedy)
    key:      threaded PRNG key (split inside the compiled step)

    ``spec`` adds the speculative telemetry counters (``spec_steps``
    active slot-steps, ``spec_drafted`` proposed tokens,
    ``spec_accepted`` accepted drafts, ``spec_emitted`` delivered
    tokens).  ``hist_cap > 0`` (n-gram drafter only — a model drafter
    has no use for it) adds ``hist`` [slots, hist_cap + 1], each slot's
    full token history (prompt + emitted — the lookup corpus; the extra
    column is a spill cell that absorbs masked/overflow scatter writes)
    with ``hist_len`` valid entries.

    ``prompt_cap > 0`` (fused chunked-prefill engines) adds ``prompt``
    [slots, prompt_cap] — the slot's full (effective) prompt, fed to the
    fused chunk a budgeted slice at a time — and ``plen``, its length.
    The prefill cursor itself is the cache ``len``; a slot is mid-prefill
    while ``len < plen``.  ``prefill_budget > 0`` additionally adds
    ``pbudget`` [slots] — the per-slot cap on prompt tokens per
    micro-step, initialized to the compiled chunk width.  The fused chunk
    clamps it to ``[1, S]``, so the SLO policy can shrink a batch slot's
    budget at a chunk boundary (one host->device value update) without
    retracing: ``S`` stays the static shape."""
    # one buffer per leaf: the engine donates the state to its
    # executables, and a buffer donated twice in one call is an error
    state = {
        "tokens": jnp.zeros((slots,), jnp.int32),
        "out_len": jnp.zeros((slots,), jnp.int32),
        "max_new": jnp.zeros((slots,), jnp.int32),
        "eos": jnp.full((slots,), -1, jnp.int32),
        "active": jnp.zeros((slots,), bool),
        "temp": jnp.zeros((slots,), jnp.float32),
        "key": jax.random.PRNGKey(seed),
    }
    if spec or hist_cap:
        for c in ("spec_steps", "spec_drafted", "spec_accepted",
                  "spec_emitted"):
            state[c] = jnp.int32(0)
    if hist_cap:
        state["hist"] = jnp.zeros((slots, hist_cap + 1), jnp.int32)
        state["hist_len"] = jnp.zeros((slots,), jnp.int32)
    if prompt_cap:
        state["prompt"] = jnp.zeros((slots, prompt_cap), jnp.int32)
        state["plen"] = jnp.zeros((slots,), jnp.int32)
    if prefill_budget:
        state["pbudget"] = jnp.full((slots,), prefill_budget, jnp.int32)
    return state


def decode_update(state: dict, nxt: jax.Array, new_key: jax.Array,
                  commit: Optional[jax.Array] = None) -> tuple:
    """One step of on-device slot bookkeeping.

    ``nxt`` [B] are freshly sampled tokens.  Returns ``(state', emitted)``
    where ``emitted`` is ``nxt`` for committing slots and -1 elsewhere —
    the host decodes the batched [T, B] history after the fact, so no
    per-token sync is needed for EOS/max-token termination.

    ``commit`` [B] bool narrows which slots take the token this step
    (default: every active slot).  The fused chunked-prefill step passes
    ``active & (pure decode | prefill just completed)`` so mid-prefill
    slots — whose row-``S-1`` logits predict a mid-prompt continuation,
    not an output token — advance their cursor without emitting.  When a
    drafting history buffer is present the committed token is appended to
    it (the fused path has no separate admission-time seeding step for
    the first sampled token)."""
    active = state["active"]
    if commit is None:
        commit = active
    out_len = state["out_len"] + commit.astype(jnp.int32)
    hit_eos = commit & (nxt == state["eos"])
    exhausted = out_len >= state["max_new"]
    done = commit & (hit_eos | exhausted)
    tokens = jnp.where(commit, nxt, state["tokens"])
    emitted = jnp.where(commit, nxt, -1)
    new_state = dict(state, tokens=tokens, out_len=out_len,
                     active=active & ~done, key=new_key)
    if "hist" in state:    # n-gram drafter corpus: append committed token
        hist, cap = state["hist"], state["hist"].shape[1] - 1
        b = hist.shape[0]
        pos = jnp.where(commit, jnp.minimum(state["hist_len"], cap), cap)
        new_state["hist"] = hist.at[jnp.arange(b), pos].set(
            jnp.maximum(jnp.where(commit, nxt, 0), 0))
        new_state["hist_len"] = state["hist_len"] + commit.astype(jnp.int32)
    return new_state, emitted


# ---------------------------------------------------------------------------
# Speculative decoding: accept/reject sampler + multi-token bookkeeping
# ---------------------------------------------------------------------------

def spec_probs(logits: jax.Array, temperature: jax.Array,
               top_k: int = 0) -> jax.Array:
    """Per-position sampling distributions the engine's ``sample`` would
    draw from: logits [B,S,V] -> probs [B,S,V].

    Greedy rows (temperature 0) yield a one-hot point mass at the argmax,
    which is what makes the rejection-sampling rule degenerate to exact
    greedy equivalence; sampled rows yield ``softmax(logits/T)`` over the
    ``top_k``-filtered support (the same support ``sample`` uses)."""
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    temperature = jnp.asarray(temperature, jnp.float32)
    safe_t = jnp.where(temperature > 0.0, temperature, 1.0)[:, None, None]
    z = logits / safe_t
    if top_k and top_k < v:
        kth = jax.lax.top_k(z, top_k)[0][..., -1:]
        z = jnp.where(z >= kth, z, -jnp.inf)
    p = jax.nn.softmax(z, axis=-1)
    greedy = jax.nn.one_hot(jnp.argmax(logits, axis=-1), v,
                            dtype=jnp.float32)
    return jnp.where(temperature[:, None, None] > 0.0, p, greedy)


def spec_accept(logits: jax.Array, drafts: jax.Array,
                qprobs: Optional[jax.Array], temperature: jax.Array,
                top_k: int, key: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Rejection-sample ``K`` drafted tokens against the target's verify
    logits.

    logits [B,K+1,V] from ``forward_verify`` — ``logits[:, i]`` is the
    target distribution of the token *after* verify input ``i`` (input 0
    is the committed current token, input ``i >= 1`` is draft ``i``).
    drafts [B,K]; qprobs [B,K,V] is the drafter's per-position proposal
    distribution, or None for a deterministic (point-mass) drafter such
    as the n-gram lookup.  Returns ``(cand [B,K+1], n_acc [B])``: position
    ``j < n_acc`` of ``cand`` holds accepted draft ``j+1``, position
    ``n_acc`` holds the resampled correction (or the bonus token when all
    ``K`` drafts were accepted); entries past ``n_acc`` are meaningless —
    ``spec_update`` masks them via its emit count."""
    b, s, v = logits.shape
    k = s - 1
    p = spec_probs(logits, temperature, top_k)            # [B,K+1,V]
    q = (jax.nn.one_hot(drafts, v, dtype=jnp.float32) if qprobs is None
         else qprobs.astype(jnp.float32))
    pd = jnp.take_along_axis(p[:, :k], drafts[..., None], axis=-1)[..., 0]
    qd = jnp.take_along_axis(q, drafts[..., None], axis=-1)[..., 0]
    kacc, kcorr = jax.random.split(key)
    u = jax.random.uniform(kacc, (b, k))
    accept = u * qd < pd                    # u < min(1, p/q), div-free
    acc_prefix = jnp.cumprod(accept.astype(jnp.int32), axis=1)
    n_acc = jnp.sum(acc_prefix, axis=1)                   # [B] in 0..K
    resid = jnp.maximum(p[:, :k] - q, 0.0)
    rsum = jnp.sum(resid, axis=-1, keepdims=True)
    # a rejection implies the residual has mass; the fallback to the raw
    # target distribution only guards numerics on never-taken branches
    resid = jnp.where(rsum > 1e-9, resid / jnp.maximum(rsum, 1e-30),
                      p[:, :k])
    dists = jnp.concatenate([resid, p[:, k:]], axis=1)    # [B,K+1,V]
    corr = jnp.take_along_axis(dists, n_acc[:, None, None], axis=1)[:, 0]
    sampled = jax.random.categorical(
        kcorr, jnp.log(corr + 1e-30), axis=-1).astype(jnp.int32)
    greedy = jnp.argmax(corr, axis=-1).astype(jnp.int32)
    tok_corr = jnp.where(jnp.asarray(temperature) > 0.0, sampled, greedy)
    idx = jnp.arange(k + 1)[None, :]
    cand = jnp.concatenate(
        [drafts, jnp.zeros((b, 1), drafts.dtype)], axis=1)
    cand = jnp.where(idx == n_acc[:, None], tok_corr[:, None], cand)
    return cand.astype(jnp.int32), n_acc


def spec_update(state: dict, cand: jax.Array, n_acc: jax.Array,
                new_key: jax.Array,
                commit: Optional[jax.Array] = None) -> tuple:
    """Multi-token analogue of ``decode_update``: commit up to ``n_acc+1``
    tokens per active slot, clamped to the remaining generation budget and
    truncated at the first EOS.  Appends the committed tokens to the
    drafting history and advances the telemetry counters.  Returns
    ``(state', emitted [B,K+1], n_emit [B])`` where ``emitted`` carries the
    committed tokens left-aligned with -1 padding (what the scan stacks
    for the host drain) and ``n_emit`` is how far the cache ``len`` may
    advance — rejected drafts roll back simply by not being counted.

    ``commit`` [B] bool narrows which slots take this round's verdict
    (default: every active slot).  The fused chunked-prefill step passes
    ``active & ~prefilling`` — drafting stays disabled for a slot until
    its prefill cursor reaches the prompt end, and a mid-prefill slot
    contributes nothing to the speculative telemetry counters."""
    active = state["active"]
    if commit is None:
        commit = active
    b, k1 = cand.shape
    idx = jnp.arange(k1)[None, :]
    rem = jnp.maximum(state["max_new"] - state["out_len"], 0)
    n0 = jnp.where(commit, jnp.minimum(n_acc + 1, rem), 0)
    iseos = (cand == state["eos"][:, None]) & (idx < n0[:, None])
    big = k1 + 1
    epos = jnp.min(jnp.where(iseos, idx, big), axis=1)
    n_emit = jnp.minimum(n0, epos + 1)
    emitted = jnp.where(idx < n_emit[:, None], cand, -1)
    out_len = state["out_len"] + n_emit
    hit_eos = epos + 1 <= n0
    done = commit & (hit_eos | (out_len >= state["max_new"]))
    last = jnp.take_along_axis(
        cand, jnp.clip(n_emit - 1, 0)[:, None], axis=1)[:, 0]
    tokens = jnp.where(commit & (n_emit > 0), last, state["tokens"])
    n_active = jnp.sum(commit.astype(jnp.int32))
    # acceptance accounting over USABLE drafts: a budget-clamped final
    # step can emit at most ``rem`` tokens, so drafts past that could
    # never be used and should not count as rejections
    usable = jnp.where(commit, jnp.minimum(k1 - 1, rem), 0)
    new_state = dict(
        state, tokens=tokens, out_len=out_len, active=active & ~done,
        key=new_key,
        spec_steps=state["spec_steps"] + n_active,
        spec_drafted=state["spec_drafted"] + jnp.sum(usable),
        spec_accepted=state["spec_accepted"]
        + jnp.sum(jnp.where(commit, jnp.minimum(n_acc, n_emit), 0)),
        spec_emitted=state["spec_emitted"] + jnp.sum(n_emit))
    if "hist" in state:    # n-gram drafter: append to the lookup corpus
        hist, cap = state["hist"], state["hist"].shape[1] - 1
        pos = jnp.where(idx < n_emit[:, None],
                        state["hist_len"][:, None] + idx, cap)
        pos = jnp.minimum(pos, cap)         # overflow -> spill column
        new_state["hist"] = hist.at[jnp.arange(b)[:, None], pos].set(
            jnp.maximum(emitted, 0))
        new_state["hist_len"] = state["hist_len"] + n_emit
    return new_state, emitted, n_emit
