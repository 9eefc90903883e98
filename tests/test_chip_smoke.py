"""``chip_smoke.py``'s phases, run as functions at ``reduced()`` size on
CPU: the engine run, the teacher-forced parity check, the contract line,
and the refusal to run without a TPU."""

import importlib.util
import json
from pathlib import Path

import pytest

from repro.configs import get_config, reduced

ROOT = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_chip_smoke()


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config(cs.ARCH))
    return cfg, cs.build_model(cfg, seed=0)        # bf16, as on the chip


def test_engine_run_and_parity(model):
    """The smoke's engine phase (8 requests, 128-512-token prompts behind
    a shared head, 64 new tokens) finishes cleanly, and every token
    passes the teacher-forced parity check."""
    cfg, params = model
    eng = cs.build_engine(cfg, params)
    # off TPU "auto" keeps gather-then-attend and no Pallas kernel
    with pytest.raises(RuntimeError, match="pool-direct"):
        cs.check_pool_direct(eng)
    assert cs.warm(eng) > 0
    with pytest.raises(RuntimeError, match="tpu_custom_call"):
        cs.check_decode_kernel(eng)
    done, secs = cs.serve(eng, seed=0)
    assert [r.rid for r in done] == list(range(cs.N_REQUESTS))
    assert all(len(r.out_tokens) == cs.MAX_NEW for r in done)
    assert all(cs.PROMPT_LO <= len(r.prompt) <= cs.PROMPT_HI for r in done)
    heads = {tuple(r.prompt[:cs.SHARED_HEAD]) for r in done}
    assert len(heads) == 1
    assert secs > 0
    stats = cs.reference_parity(cfg, params, done)
    assert stats["tokens"] == cs.N_REQUESTS * cs.MAX_NEW
    assert stats["argmax_match_rate"] >= 0.9
    # the check has teeth: a request whose tokens ignore the model fails
    done[0].out_tokens = [0] * cs.MAX_NEW
    with pytest.raises(RuntimeError, match="beyond tolerance"):
        cs.reference_parity(cfg, params, done)


def test_pool_direct_check(model):
    cfg, params = model
    cs.check_pool_direct(cs.build_engine(cfg, params, paged_kernel=True))


def test_requests_are_seeded():
    a = cs.make_requests(3, 1000)
    b = cs.make_requests(3, 1000)
    c = cs.make_requests(4, 1000)
    assert [r.prompt for r in a] == [r.prompt for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]


def test_contract_line():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = cs.contract_line(device)
    assert line == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 1}}')
    assert json.loads(line) == {"ok": True, "device": device}


def test_main_refuses_a_machine_without_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        cs.main([])
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code)
    assert '"ok": true' not in capsys.readouterr().out
