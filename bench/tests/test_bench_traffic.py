"""The traffic generator: seeded, stratified, and true to each mix file."""

import hashlib
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import traffic  # noqa: E402

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
MAX_LEN = {"docqa-sat": 4096}


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def gen(name, seed, rate=2.0, horizon=120.0):
    return traffic.generate(mix(name), rate=rate, seed=seed,
                            horizon_s=horizon, vocab=1000,
                            max_len=MAX_LEN.get(name, 2048))


def digest(reqs):
    h = hashlib.sha256()
    for a in reqs:
        h.update(repr((a.rid, a.due, a.prompt, a.max_new, a.session,
                       a.prefix_len)).encode())
    return h.hexdigest()


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_bytes(name):
    big = 2 ** 33 + 12345
    assert digest(gen(name, big)) == digest(gen(name, big))
    assert digest(gen(name, big)) != digest(gen(name, big + 1))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_within_the_mix(name):
    m = mix(name)
    reqs = gen(name, 7)
    for a in reqs:
        lo, hi = m["output"].get("lo", 1), m["output"].get("hi", 10 ** 9)
        assert lo <= a.max_new <= hi
        own = len(a.prompt) - a.prefix_len
        assert m["prompt"]["lo"] <= own <= m["prompt"]["hi"]
        if m["prefix"] is None:
            assert a.prefix_len == 0
        else:
            plen = m["prefix"]["len"]
            assert plen["lo"] <= a.prefix_len <= plen["hi"]
        assert all(1 <= t < 1000 for t in a.prompt)
    assert [a.rid for a in reqs] == list(range(len(reqs)))
    assert all(x.due <= y.due for x, y in zip(reqs, reqs[1:]))


@pytest.mark.parametrize("name", MIXES)
def test_rate_is_requests_per_second(name):
    rate, horizon = 3.0, 400.0
    n = len(gen(name, 11, rate=rate, horizon=horizon))
    # sessions whose later turns fall past the horizon are cut, so a
    # multi-turn mix runs a little short of the rate
    assert 0.85 * rate * horizon <= n <= 1.05 * rate * horizon


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_sizes(name):
    """Every seed offers the same sizes, in another order: over whole
    blocks of sessions the multisets of output lengths agree."""
    a, b = gen(name, 1, horizon=300.0), gen(name, 2, horizon=300.0)
    block = mix(name)["block"]
    n = min(len(a), len(b)) // block * block // 2
    sa = sorted(x.max_new for x in sorted(a, key=lambda r: r.session)[:n])
    sb = sorted(x.max_new for x in sorted(b, key=lambda r: r.session)[:n])
    assert abs(sum(sa) - sum(sb)) <= 0.05 * sum(sa)


def test_median_and_clip_of_lognormal():
    d = {"dist": "lognormal", "median": 128, "sigma": 0.9, "lo": 16,
         "hi": 512}
    vals = traffic.strata(d, 1000)
    assert vals[500] == 128 and min(vals) >= 16 and max(vals) == 512


def test_zipf_picks_favour_the_first():
    picks = traffic.zipf_strata(8, 1.0, 1000)
    counts = [picks.count(k) for k in range(8)]
    assert counts == sorted(counts, reverse=True) and counts[0] > 300


def test_pool_prefixes_are_shared():
    reqs = gen("chat-p80", 3)
    heads = {tuple(a.prompt[:a.prefix_len]) for a in reqs}
    assert len(heads) <= 8 and len(reqs) > 8 * 4


def test_session_turns_share_their_document():
    reqs = gen("docqa-sat", 4)
    by = {}
    for a in reqs:
        by.setdefault(a.session, []).append(a)
    multi = [v for v in by.values() if len(v) > 1]
    assert multi
    for v in multi:
        assert len({tuple(a.prompt[:a.prefix_len]) for a in v}) == 1


def test_over_max_len_raises():
    with pytest.raises(ValueError, match="max_len"):
        traffic.generate(mix("chat-p80"), rate=2.0, seed=1, horizon_s=60.0,
                         vocab=100, max_len=512)


def test_order_seed_fixes_the_schedule():
    """With ``order_seed`` every run offers the same sizes at the same
    times; the run's seed changes only the token ids."""
    fixed = [n for n in MIXES if "order_seed" in mix(n)]
    assert "docqa-sat" in fixed
    for name in fixed:
        a, b = gen(name, 1), gen(name, 2 ** 33 + 2)
        assert [(x.due, len(x.prompt), x.max_new) for x in a] == \
            [(x.due, len(x.prompt), x.max_new) for x in b]
        assert a[0].prompt != b[0].prompt


PROCESSES = sorted(p.stem for p in (BENCH / "arrivals").glob("*.py"))


@pytest.mark.parametrize("process", PROCESSES)
def test_arrival_process_keeps_its_mean(process):
    import numpy as np
    gaps = traffic.arrival_process(process)(np.random.default_rng(3), 4000,
                                            0.5, {})
    assert len(gaps) == 4000 and min(gaps) > 0
    assert abs(sum(gaps) / 4000 - 0.5) <= 0.025


def test_unknown_arrival_process_raises():
    with pytest.raises(FileNotFoundError, match="no arrival process"):
        traffic.arrival_process("no-such-process")


def test_poisson_blocks_burst_and_stratified_blocks_do_not():
    """Stratified gaps give every block of sessions the same span;
    Poisson gaps let a block run long or short, as bursts do."""
    import numpy as np
    rng = np.random.default_rng(5)
    spans = {p: [sum(traffic.arrival_process(p)(rng, 32, 1.0, {}))
                 for _ in range(200)] for p in ("poisson", "stratified")}
    assert max(spans["stratified"]) - min(spans["stratified"]) < 1e-9
    assert np.std(spans["poisson"]) / np.mean(spans["poisson"]) > 0.1
