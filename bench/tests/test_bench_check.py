"""The reference against the program's own forward, and the control:
the reference computed in fp8 must fail what the served tokens pass."""

import ast
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
import reference  # noqa: E402
import weights  # noqa: E402


@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (12, 1)])
def test_reference_matches_the_program_forward(heads, kv_heads):
    """At float32 weights the plain reference and the program's dense
    forward agree to float32 rounding at every position (GQA groups of
    2 and of 12)."""
    import dataclasses
    from repro.configs import get_config
    from repro.configs.base import uniform_blocks
    from repro.models import forward_dense_logits, model_defs
    from repro.models import module as m
    cfg = dataclasses.replace(
        get_config("internlm2-1.8b"), num_layers=2, d_model=96,
        num_heads=heads, num_kv_heads=kv_heads, head_dim=16, d_ff=160,
        vocab_size=300, blocks=uniform_blocks(2))
    params = weights.make_weights(
        m.abstract_params(model_defs(cfg), jnp.float32), 5, jnp.float32)
    model = {"num_layers": 2, "num_heads": heads, "num_kv_heads": kv_heads,
             "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}
    tokens = np.random.default_rng(0).integers(1, 300, 37).tolist()
    with jax.default_matmul_precision("highest"):
        want = forward_dense_logits(params, cfg,
                                    {"tokens": jnp.asarray([tokens])})[0]
    got = reference.logits_at(weights.flat_weights(params), model, tokens,
                              list(range(37)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    fp8 = reference.logits_at(weights.flat_weights(params), model, tokens,
                              list(range(37)), fp8=True)
    assert float(jnp.max(jnp.abs(fp8 - got))) > 1e-2


def test_bf16_weights_take_exact_products():
    """A bfloat16 weight times a float32 input, as three exact bfloat16
    products, matches the float32 product at HIGHEST."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(k1, (8, 256), jnp.float32)
    w = jax.random.normal(k2, (256, 64), jnp.float32).astype(jnp.bfloat16)
    got = reference._linear(x, w, fp8=False)
    want = jnp.dot(x, w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_control_fails_where_the_program_passes(tmp_path):
    """The tiny cell on the CPU: the served tokens sit within the limit;
    the fp8 control, put in their place at the same positions, lies
    well beyond it, and the run reads ``correct: false``."""
    bench = bench_tiny.make(tmp_path, limit=0.05)
    res = bench_tiny.run(bench, "--seed", "4000000007", "--seconds", "2",
                         "--trace", "0", "--control", "1")
    line = next(x for x in res["log"] if x.startswith("check: {"))
    readings = ast.literal_eval(line[len("check: "):line.rindex("}") + 1])
    assert res["correct"] is False
    assert readings["max_gap"] <= 0.05
    assert readings["control_max_gap"] > 0.05
    assert res["check"]["max_gap"]["value"] == readings["control_max_gap"]
