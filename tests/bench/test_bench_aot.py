"""Size rehearsal of each benchmark configuration for a TPU v5e, with no
chip: compile the deepest decode megastep (top batch bucket, context at
``max_len``) and the largest prefill bucket at the top batch bucket, at
the configuration's depth, slots, ``max_len`` and dtype, for a described
v5e, and check that each fits the chip's memory beside the weights and
the arena. These fix the sizes in ``bench/configs``.

The topology is described inside a module fixture, never at import (one
process at a time may load the TPU library; every pytest-xdist worker
imports this file). The engine is built under ``jax.eval_shape``, so no
weight or arena is allocated here.
"""
import json
import os
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import harness, layout

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
HBM = layout.load_json(layout.BENCH / "peaks.json")["devices"][
    "TPU v5 lite"]["hbm_bytes"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def shaped_engine(config, one_chip):
    """The served engine built from shapes only, and its pytrees as
    ShapeDtypeStructs on the described chip."""
    from repro.serving.engine import JaxEngine
    cfg = harness.program_config(config)
    s = config["serving"]
    box = {}

    def build():
        # pallas=True: the engine built on the chip takes the kernel path
        e = JaxEngine(cfg, max_len=s["max_len"], seed=0,
                      dtype=getattr(jnp, config["dtype"]),
                      n_slots=s["slots"], max_slots=s["slots"], pallas=True)
        box["e"] = e
        return e.params, e._span_params, e.arenas

    trees = jax.eval_shape(build)
    on = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), t)
    return box["e"], [on(t) for t in trees]


def total_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("name", CELLS)
def test_cell_fits_one_v5e(name, one_chip, no_persistent_cache):
    spec = layout.cell(name)
    config, mix = spec["config"], spec["mix"]
    s = config["serving"]
    engine, (params, span_params, arenas) = shaped_engine(config, one_chip)
    layers = config["num_hidden_layers"]
    offs = [jax.ShapeDtypeStruct((hi - lo + 1,), jnp.int32,
                                 sharding=one_chip)
            for (_, _, lo, hi) in engine._spans]
    batch = harness.pow2(s["max_batch"])
    vec = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)

    # deepest decode megastep: embed, every layer, head; the engine picks
    # the Pallas kernel on a TPU backend, which this host is not
    decode = engine._fn_mega(0, layers - 1, True, s["max_len"])
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = decode.lower(params, span_params, arenas, vec, vec, vec,
                               offs)
    if config["attention"] == "gqa":
        assert "tpu_custom_call" in lowered.as_text()
    dec = total_bytes(lowered.compile())

    bucket = min(harness.pow2(mix["prompt"]["max"] - 1), s["max_len"])
    toks = jax.ShapeDtypeStruct((batch, bucket), jnp.int32,
                                sharding=one_chip)
    prefill = engine._fn_prefill_run(0, layers - 1, True)
    pre = total_bytes(prefill.lower(params, span_params, arenas, toks, vec,
                                    offs).compile())
    assert dec < HBM, f"decode megastep needs {dec / 1e9:.2f} GB"
    assert pre < HBM, f"prefill {batch} x {bucket} needs {pre / 1e9:.2f} GB"
