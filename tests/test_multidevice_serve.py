"""Multi-device serving smoke: the ``Engine(rules=...)`` sharded path,
end-to-end in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` so the rest of the
suite keeps seeing ONE device (dry-run isolation rule, same convention as
tests/test_distribution.py)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run_sub(body: str) -> str:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, jax.numpy as jnp, numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_engine_matches_unsharded_tokens():
    """Slot state + per-group page pools sharded over a 2-way data mesh
    must serve token-identical outputs to the unsharded engine — through
    continuous batching, paged splice, prefix sharing (shared-prefix
    prompts included), and the fused decode chunk."""
    out = _run_sub("""
        from repro.configs import get_config, reduced
        from repro.launch.mesh import make_serving_mesh
        from repro.models import model_defs
        from repro.models import module as m
        from repro.parallel import sharding as sh
        from repro.serve.engine import Engine, Request

        cfg = reduced(get_config("internlm2-1.8b"))
        params = m.init_params(model_defs(cfg), jax.random.PRNGKey(0),
                               jnp.float32)
        prefix = [(3 * j) % 200 + 1 for j in range(10)]

        def load(eng):
            for i in range(6):
                tail = [(7 * i + j) % 150 + 1 for j in range(1 + i % 3)]
                eng.submit(Request(rid=i, prompt=prefix + tail,
                                   max_new_tokens=6))
            return {r.rid: r.out_tokens for r in eng.run()}

        mesh = make_serving_mesh(2)
        rules = sh.Rules(table={sh.BATCH: "data", sh.PAGES: "data"},
                         mesh=mesh)
        sharded = Engine(cfg, params, slots=2, max_len=64, rules=rules)
        got = load(sharded)
        plain = Engine(cfg, params, slots=2, max_len=64)
        want = load(plain)
        assert got == want, (got, want)
        assert len(got) == 6
        # the sharded engine exercised the prefix-sharing admission path
        ps = sharded.prefix_stats()
        assert ps["prefix_hits"] > 0 and ps["prefill_tokens_skipped"] > 0
        # slot batch really lands on the data axis
        table = sharded.cache["page_tables"][
            sharded.spec.widest_group.key]
        assert "data" in str(table.sharding), table.sharding
        print("OK", ps["prefix_hit_rate"])
    """)
    assert "OK" in out


def test_chip_smoke_sharded_phase_matches_one_device():
    """``chip_smoke.py --chips 4``'s phase at reduced size on a 2-way
    data mesh: fp32 tokens identical to the one-device engine, bf16
    tokens within the reference parity tolerance, page tables sharded
    over ``data``."""
    out = _run_sub(f"""
        import importlib.util
        from repro.configs import get_config, reduced

        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(Path(SRC).parent / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        cfg = reduced(get_config(cs.ARCH))
        eng = cs.sharded_matches_one_device(cfg, 0, 2)
        assert len(eng.finished) == cs.N_REQUESTS
        print("OK")
    """)
    assert "OK" in out


def test_paged_kernel_under_mesh_rules_matches_one_device():
    """Under sharding rules with a 2-device mesh the Pallas paged-attention
    kernel runs under shard_map (a Mosaic kernel cannot be partitioned
    automatically): slot rows split over ``data``, whole pools — and the
    output equals the one-device kernel's, fp32 and int8 pools alike."""
    out = _run_sub("""
        from repro.kernels.paged_attention import paged_attention
        from repro.launch.mesh import make_serving_mesh
        from repro.models.attention import quantize_pages
        from repro.parallel import sharding as sh

        rules = sh.Rules(table={sh.BATCH: "data", sh.PAGES: "data"},
                         mesh=make_serving_mesh(2))
        k = jax.random.PRNGKey(0)
        b, npg = 4, 20
        q = jax.random.normal(k, (b, 3, 4, 16))
        pk = jax.random.normal(jax.random.fold_in(k, 1), (npg + 1, 4, 2, 16))
        pv = jax.random.normal(jax.random.fold_in(k, 2), (npg + 1, 4, 2, 16))
        pt = jnp.asarray(np.random.RandomState(0).permutation(npg)[:b * 4]
                         .reshape(b, 4), jnp.int32)
        cl = jnp.asarray([3, 7, 12, 16], jnp.int32)
        qk, sk = quantize_pages(pk, jnp.int8)
        qv, sv = quantize_pages(pv, jnp.int8)

        def attend(q, pk, pv, pt, cl, sc):
            with sh.axis_rules(rules):
                return paged_attention(q, pk, pv, pt, cl, interpret=True,
                                       **sc)

        for pools, sc in (((pk, pv), {}),
                          ((qk, qv), dict(k_scale=sk, v_scale=sv))):
            want = paged_attention(q, *pools, pt, cl, interpret=True, **sc)
            got = jax.jit(attend)(q, *pools, pt, cl, sc)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-6, atol=1e-6)
            assert "manual_computation" in jax.jit(attend).lower(
                q, *pools, pt, cl, sc).as_text()
        print("OK")
    """)
    assert "OK" in out
