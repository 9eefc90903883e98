"""The work a served step needs, counted from shapes and live lengths.

These functions count what the algorithm needs, whatever implements
it: a query row attends to the keys before it and no others, a decode
step reads each live key once, and only the rows whose next token is
sampled need the LM head.  Padding rows, trash pages and page-table
entries past a slot's length are not work.  So a kernel that stops
reading what it does not need reads as nearer its roofline, and every
kernel that ever stands in this place is held to the same count.

``model`` is the ``"model"`` object of a configuration file.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple


def layer_matmul_params(model: Dict[str, int]) -> int:
    """Weights one token multiplies through in one layer."""
    d, h, hkv = model["d_model"], model["num_heads"], model["num_kv_heads"]
    dh, ff = model["head_dim"], model["d_ff"]
    return d * (h + 2 * hkv) * dh + h * dh * d + 3 * d * ff


def token_flops(model: Dict[str, int], ctx: int, logits: bool) -> float:
    """FLOPs of one token row at context ``ctx`` (keys it attends to,
    itself included): its matmuls in every layer, attention's scores and
    weighted sum, and the LM head when its next token is sampled."""
    n_layers, d = model["num_layers"], model["d_model"]
    f = 2.0 * n_layers * layer_matmul_params(model)
    f += 4.0 * n_layers * model["num_heads"] * model["head_dim"] * ctx
    if logits:
        f += 2.0 * d * model["vocab_size"]
    return f


def attention_call(model: Dict[str, int], row_ctx: Iterable[int],
                   slot_ctx: Iterable[int], kv_bytes: int,
                   act_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's paged-attention call.

    ``row_ctx``: the context of every real query row; ``slot_ctx``: the
    keys each slot reads (its newest row's context).  Bytes are the live
    K and V it reads from the pools, plus the real query rows read and
    their outputs written."""
    h, hkv, dh = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    rows = list(row_ctx)
    flops = 4.0 * h * dh * sum(rows)
    nbytes = (2.0 * hkv * dh * kv_bytes * sum(slot_ctx)
              + 2.0 * h * dh * act_bytes * len(rows))
    return flops, nbytes


@dataclass
class StepWork:
    """One micro-step of the fused chunk, over all slots."""
    row_ctx: List[int] = field(default_factory=list)
    logit_rows: int = 0          # rows whose next token is sampled
    slot_ctx: List[int] = field(default_factory=list)


@dataclass
class _Slot:
    pos: int                     # tokens whose keys are written
    plen: int                    # prompt length at admission


class ChunkReplay:
    """Replays the fused chunk's schedule from what the host saw.

    At each boundary the engine admits requests into slots, each with
    the number of prompt tokens already in cached pages; each chunk then
    runs ``micro_steps`` steps in which a slot still prefilling takes up
    to ``budget`` prompt rows and a decoding slot one row.  The tokens a
    slot emitted in a chunk are known from the drain, which ends a
    decoding slot's work inside the chunk.  The replay yields the live
    context of every row, never the page table's width."""

    def __init__(self, micro_steps: int, budget: int):
        self.micro_steps = micro_steps
        self.budget = budget
        self.slots: Dict[int, _Slot] = {}
        self.unmatched = 0        # tokens the replay could not place

    def admit(self, slot: int, start: int, plen: int) -> None:
        self.slots[slot] = _Slot(pos=start, plen=plen)

    def release(self, slot: int) -> None:
        self.slots.pop(slot, None)

    def chunk(self, emitted: Dict[int, int]) -> List[StepWork]:
        """Work of one chunk; ``emitted[slot]`` = tokens drained for it."""
        left = dict(emitted)
        steps = []
        for _ in range(self.micro_steps):
            w = StepWork()
            for slot, s in self.slots.items():
                if s.pos < s.plen:
                    n = min(s.plen - s.pos, self.budget)
                    w.row_ctx += [s.pos + i + 1 for i in range(n)]
                    s.pos += n
                    w.slot_ctx.append(s.pos)
                    if s.pos == s.plen:
                        w.logit_rows += 1
                        left[slot] = left.get(slot, 0) - 1
                elif left.get(slot, 0) > 0:
                    w.row_ctx.append(s.pos + 1)
                    w.slot_ctx.append(s.pos + 1)
                    w.logit_rows += 1
                    s.pos += 1
                    left[slot] -= 1
            steps.append(w)
        self.unmatched += sum(abs(v) for v in left.values())
        return steps


def step_flops(model: Dict[str, int], step: StepWork) -> float:
    """Useful model FLOPs of one micro-step."""
    flops = sum(token_flops(model, c, logits=False) for c in step.row_ctx)
    return flops + step.logit_rows * 2.0 * model["d_model"] \
        * model["vocab_size"]


def attention_least_s(model: Dict[str, int], step: StepWork, kv_bytes: int,
                      peak_flops: float, peak_bw: float
                      ) -> Tuple[float, float, float]:
    """(least seconds, compute-bound seconds, memory-bound seconds) of
    the paged-attention calls of one micro-step, one call per layer."""
    if not step.row_ctx:
        return 0.0, 0.0, 0.0
    f, b = attention_call(model, step.row_ctx, step.slot_ctx, kv_bytes)
    n = model["num_layers"]
    tf, tb = n * f / peak_flops, n * b / peak_bw
    return max(tf, tb), tf, tb


def kv_bytes_of(kv_dtype: str) -> int:
    return {"fp32": 4, "bf16": 2, "int8": 1, "fp8_e4m3": 1}[kv_dtype]


def least_time(model: Dict[str, int], steps: Iterable[StepWork],
               kv_bytes: int, peak_flops: float,
               peak_bw: float) -> Optional[Dict[str, float]]:
    """Least seconds of all paged-attention calls in ``steps``, split by
    which bound sets each call; None when no call had a row."""
    least = compute = memory = 0.0
    any_rows = False
    for s in steps:
        t, tf, tb = attention_least_s(model, s, kv_bytes, peak_flops,
                                      peak_bw)
        any_rows |= bool(s.row_ctx)
        least += t
        if tf >= tb:
            compute += t
        else:
            memory += t
    if not any_rows:
        return None
    return {"least_s": least, "compute_bound_s": compute,
            "memory_bound_s": memory}


@dataclass
class Replay:
    chunks: List[Tuple[float, List[StepWork]]]   # (drain time, steps)
    unmatched: int                               # tokens not placed

    def steps(self, lo: float, hi: float) -> List[StepWork]:
        """Micro-steps of the chunks drained in ``(lo, hi]``."""
        return [s for ts, steps in self.chunks if lo < ts <= hi
                for s in steps]


def replay_events(events: List, served: List, micro_steps: int,
                  budget: int) -> Replay:
    """Replay the engine's lifecycle events (``admit``, ``chunk``,
    ``preempt``, ``finish``) and the drained tokens of every request."""
    per_chunk: Dict[int, Dict[int, int]] = {}
    for s in served:
        if s.req is None:
            continue
        for c in s.req.token_chunks:
            d = per_chunk.setdefault(c, {})
            d[s.req.rid] = d.get(s.req.rid, 0) + 1
    replay = ChunkReplay(micro_steps, budget)
    slot_rid: Dict[int, int] = {}
    chunks: List[Tuple[float, List[StepWork]]] = []
    for e in events:
        if e.kind == "admit":
            replay.admit(e.slot, e.attrs["suffix_start"], e.attrs["plen"])
            slot_rid[e.slot] = e.rid
        elif e.kind in ("preempt", "finish") and e.slot is not None:
            replay.release(e.slot)
            slot_rid.pop(e.slot, None)
        elif e.kind == "chunk":
            drained = per_chunk.get(e.attrs["chunk"], {})
            emitted = {slot: drained.get(rid, 0)
                       for slot, rid in slot_rid.items()}
            chunks.append((e.ts, replay.chunk(emitted)))
    return Replay(chunks=chunks, unmatched=replay.unmatched)
