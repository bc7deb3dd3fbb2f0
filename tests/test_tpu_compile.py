"""Ahead-of-time compiles of the served decode kernel for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described v5e device,
so a layout or VMEM budget the chip's compiler would refuse fails here.
Interpret-mode tests cannot see that. Nothing runs, so these say nothing
about results or speed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under pytest-xdist every worker
imports this file. Keep every such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ragged_decode_attn import ragged_decode_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library otherwise writes its logs under /tmp
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to compile for
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described device cannot be read back from the
    persistent cache without the chip: keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


# (batch, heads, kv heads, head_dim, arena rows, arena length, dtype):
# llama3.2-1b's widths at the smoke's arena (16 layers x 32 slots, 1024
# tokens) in the engine's f32 and in bf16, and mistral-nemo-12b's
# head_dim 128 (40 layers x 16 slots).
CASES = {
    "llama3.2-1b-f32": (8, 32, 8, 64, 16 * 32, 1024, jnp.float32),
    "llama3.2-1b-bf16": (8, 32, 8, 64, 16 * 32, 1024, jnp.bfloat16),
    "mistral-nemo-12b-bf16": (8, 32, 8, 128, 40 * 16, 1024, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_ragged_decode_attention_compiles_for_v5e(case, one_chip,
                                                  no_persistent_cache):
    B, H, KV, D, rows, T, dtype = CASES[case]

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    lowered = ragged_decode_attention.lower(
        spec((B, H, D), dtype), spec((rows, T, KV, D), dtype),
        spec((rows, T, KV, D), dtype), spec((B,), jnp.int32),
        slots=spec((B,), jnp.int32), interpret=False)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
