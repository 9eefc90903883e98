"""Faults planted under the timed path must turn ``correct`` false.

The tiny cell runs whole on the CPU, past the harness's look for a chip,
with the engine broken underneath in one of the ways a serving cell can
be: a token altered where it is produced, or a step that hands back the
key/value pools it was given, so nothing it wrote is kept.  (The
training faults, and the exchange between chips, do not arise in a
one-chip serving cell.)"""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from repro.serve import engine as engine_mod  # noqa: E402

ARGS = ("--seconds", "2", "--trace", "0")


def test_sound_run_is_correct(tmp_path):
    res = bench_tiny.run(bench_tiny.make(tmp_path), "--seed", "5000000001",
                         *ARGS)
    assert res["correct"] is True


def test_token_altered_where_produced(tmp_path, monkeypatch):
    drain = engine_mod.Engine._drain
    hit = []

    def altered(self, toks):
        drain(self, toks)
        for req in self._slot_req + self.finished:
            if req is not None and len(req.out_tokens) > 2 and not hit:
                req.out_tokens[1] = (req.out_tokens[1] + 1) \
                    % self.cfg.vocab_size
                hit.append(req.rid)

    monkeypatch.setattr(engine_mod.Engine, "_drain", altered)
    bench = bench_tiny.make(tmp_path)
    res = bench_tiny.run(bench, "--seed", "5000000002", *ARGS)
    assert hit and res["correct"] is False
    assert res["check"]["max_gap"]["value"] > res["check"]["max_gap"]["limit"]


def test_step_returns_the_pools_unchanged(tmp_path, monkeypatch):
    chunk = engine_mod.Executor.chunk

    def forgetful(self, params, draft_params, cache, state):
        toks, new, state = chunk(self, params, draft_params, cache, state)
        return toks, dict(new, layers=cache["layers"]), state

    monkeypatch.setattr(engine_mod.Executor, "chunk", forgetful)
    res = bench_tiny.run(bench_tiny.make(tmp_path), "--seed", "5000000003",
                         *ARGS)
    assert res["correct"] is False
