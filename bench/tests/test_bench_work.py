"""FLOP and byte counts against hand arithmetic, and the chunk replay."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import work  # noqa: E402


def model(config):
    return json.loads((BENCH / "configs" / f"{config}.json").read_text())[
        "model"]


INTERNLM2 = model("internlm2-1.8b")
MISTRAL = model("mistral-large-123b-3layer")


def test_matmul_params_by_hand():
    # d (H + 2 Hkv) dh + H dh d + 3 d ff
    assert work.layer_matmul_params(INTERNLM2) == (
        2048 * 32 * 128 + 16 * 128 * 2048 + 3 * 2048 * 8192) == 62_914_560
    assert 24 * work.layer_matmul_params(INTERNLM2) == 1_509_949_440
    assert work.layer_matmul_params(MISTRAL) == (
        12288 * 112 * 128 + 96 * 128 * 12288 + 3 * 12288 * 28672) \
        == 1_384_120_320


def test_token_flops_by_hand():
    f = work.token_flops(INTERNLM2, ctx=1000, logits=True)
    assert f == 2 * 24 * 62_914_560 + 4 * 24 * 16 * 128 * 1000 \
        + 2 * 2048 * 92544 == 3_595_567_104
    assert work.token_flops(INTERNLM2, 1000, logits=False) == f - 379_060_224
    g = work.token_flops(MISTRAL, ctx=10, logits=False)
    assert g == 2 * 3 * 1_384_120_320 + 4 * 3 * 96 * 128 * 10


@pytest.mark.parametrize("m,kv_heads,heads", [(INTERNLM2, 8, 16),
                                              (MISTRAL, 8, 96)])
def test_attention_call_counts_live_keys(m, kv_heads, heads):
    """One decode row at context 1000 reads 1000 keys and values in fp32,
    however wide the slot's page table is."""
    f, b = work.attention_call(m, [1000], [1000], kv_bytes=4)
    assert f == 4 * heads * 128 * 1000
    assert b == 2 * kv_heads * 128 * 4 * 1000 + 2 * heads * 128 * 2
    f2, b2 = work.attention_call(m, [1000, 1000], [1000, 1000], kv_bytes=4)
    assert (f2, b2) == (2 * f, 2 * b)
    f3, b3 = work.attention_call(m, [10], [10], kv_bytes=4)
    assert b3 < b / 50      # a short context reads little


def test_least_time_names_its_bound():
    step = work.StepWork(row_ctx=[1000], logit_rows=1, slot_ctx=[1000])
    lt = work.least_time(INTERNLM2, [step], kv_bytes=4, peak_flops=197e12,
                         peak_bw=819e9)
    b = 24 * (2 * 8 * 128 * 4 * 1000 + 2 * 16 * 128 * 2) / 819e9
    assert lt["least_s"] == pytest.approx(b)
    assert lt["memory_bound_s"] == lt["least_s"] and not lt["compute_bound_s"]
    slow = work.least_time(INTERNLM2, [step], 4, peak_flops=1e6,
                           peak_bw=819e9)
    assert slow["compute_bound_s"] == slow["least_s"]
    assert work.least_time(INTERNLM2, [work.StepWork()], 4, 1, 1) is None


def test_replay_of_one_chunk_by_hand():
    r = work.ChunkReplay(micro_steps=4, budget=8)
    r.admit(0, start=0, plen=20)       # 20-token prompt, nothing cached
    r.admit(1, start=30, plen=30)      # admitted earlier, now decoding
    r.slots[1].pos = 40
    steps = r.chunk({0: 2, 1: 3})
    assert steps[0].row_ctx == list(range(1, 9)) + [41]
    assert steps[1].row_ctx == list(range(9, 17)) + [42]
    assert steps[2].row_ctx == [17, 18, 19, 20] + [43]
    assert steps[3].row_ctx == [21]     # slot 1 emitted its 3 tokens
    assert [s.logit_rows for s in steps] == [1, 1, 2, 1]
    assert steps[2].slot_ctx == [20, 43]
    assert r.unmatched == 0
    r.chunk({0: 0, 1: 5})
    assert r.unmatched == 1             # slot 1 had only 4 steps


def test_replay_events_keeps_the_window():
    def ev(kind, ts, slot=None, rid=None, **attrs):
        return NS(kind=kind, ts=ts, slot=slot, rid=rid, attrs=attrs)
    events = [ev("admit", 0.0, 0, 7, suffix_start=0, plen=4, resume=False),
              ev("chunk", 1.0, chunk=1), ev("chunk", 2.0, chunk=2),
              ev("finish", 2.0, 0, 7)]
    req = NS(rid=7, token_chunks=[1, 1, 2, 2])
    rep = work.replay_events(events, [NS(req=req)], micro_steps=2, budget=4)
    assert rep.unmatched == 0
    assert [s.row_ctx for s in rep.steps(0.0, 1.0)] == [[1, 2, 3, 4], [5]]
    assert [s.row_ctx for s in rep.steps(1.0, 2.0)] == [[6], [7]]
    assert rep.steps(2.0, 3.0) == []


def test_mfu_counts_no_padding():
    step = work.StepWork(row_ctx=[5, 6], logit_rows=1, slot_ctx=[6])
    assert work.step_flops(INTERNLM2, step) == (
        work.token_flops(INTERNLM2, 5, False)
        + work.token_flops(INTERNLM2, 6, True))
