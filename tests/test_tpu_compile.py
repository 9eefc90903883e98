"""Compile the paged-attention kernel for a described (not attached) TPU
v5e chip at internlm2-1.8b widths, so the TPU compiler's refusals (tile
alignment, VMEM limits) surface here instead of on the chip.

Nothing runs: these compiles say nothing about results or times.  The
topology is described inside a fixture, never at import time — only one
process at a time may load the TPU compiler library, so every test that
needs it lives in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention.kernel import paged_decode_attention

CFG = get_config("internlm2-1.8b")
SLOTS, PAGE, RING_PAGES, NUM_PAGES = 8, 16, 128, 1024    # max_len 2048


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - report why, skip the file
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.mark.parametrize("kv_dtype,q_len,q_dtype", [
    ("fp32", 1, jnp.bfloat16),     # plain decode
    ("fp32", 5, jnp.bfloat16),     # speculative verify, K = 4
    ("fp32", 32, jnp.bfloat16),    # fused chunk, prefill_budget = 32
    ("fp32", 1, jnp.float32),
    ("int8", 1, jnp.bfloat16),
    ("int8", 5, jnp.float32),
    ("fp8_e4m3", 1, jnp.bfloat16),
    ("fp8_e4m3", 5, jnp.float32),
])
def test_paged_attention_compiles_for_v5e(one_chip, kv_dtype, q_len,
                                          q_dtype):
    from repro.serve.cache import kv_pool_dtype

    dh, hkv = CFG.resolved_head_dim, CFG.num_kv_heads

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    pool = shape((NUM_PAGES + 1, PAGE, hkv, dh), kv_pool_dtype(kv_dtype))
    args = [shape((SLOTS, q_len, CFG.num_heads, dh), q_dtype), pool, pool,
            shape((SLOTS, RING_PAGES), jnp.int32),
            shape((SLOTS,), jnp.int32)]
    if kv_dtype != "fp32":
        args += [shape((NUM_PAGES + 1, hkv), jnp.float32)] * 2

    def attend(q, pk, pv, pt, cl, ks=None, vs=None):
        return paged_decode_attention(q, pk, pv, pt, cl, k_scale=ks,
                                      v_scale=vs)

    compiled = jax.jit(attend).lower(*args).compile()
    calls = [line.strip() for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # the kernel's name is the custom call's, which a device trace shows
    assert len(calls) == 1 and calls[0].startswith("%paged_attention")
