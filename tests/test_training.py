"""Training substrate: optimizer math, checkpointing, loss descent."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="install the [test] extra")
from hypothesis import given, settings, strategies as st

from repro.training import (OptimizerConfig, adamw_update, checkpoint,
                            clip_by_global_norm, cosine_lr, global_norm,
                            init_adamw)


def test_adamw_single_step_matches_analytic():
    """One step from zero moments: delta = lr * (g/|g|... ) analytic check."""
    cfg = OptimizerConfig(lr=1e-2, betas=(0.9, 0.999), eps=1e-8,
                          weight_decay=0.0, grad_clip=1e9,
                          warmup_steps=0, total_steps=10**9)
    p = {"w": jnp.array([1.0, -2.0], jnp.float32)}
    g = {"w": jnp.array([0.5, -0.25], jnp.float32)}
    state = init_adamw(p)
    new_p, new_state, m = adamw_update(cfg, p, g, state)
    # bias-corrected first step: mhat = g, vhat = g^2 -> delta = lr * sign(g)
    expect = p["w"] - cfg.lr * jnp.sign(g["w"])
    np.testing.assert_allclose(np.asarray(new_p["w"]), np.asarray(expect),
                               rtol=1e-4)
    assert int(new_state.step) == 1


def test_weight_decay_only_on_matrices():
    cfg = OptimizerConfig(lr=1e-2, weight_decay=0.5, grad_clip=1e9,
                          warmup_steps=0, total_steps=10**9)
    p = {"w": jnp.ones((2,), jnp.float32), "scale": jnp.ones((2,), jnp.float32)}
    g = {"w": jnp.zeros((2,)), "scale": jnp.zeros((2,))}
    new_p, _, _ = adamw_update(cfg, p, g, init_adamw(p))
    assert float(new_p["w"][0]) < 1.0          # decayed
    np.testing.assert_allclose(np.asarray(new_p["scale"]), 1.0)  # exempt


def test_cosine_schedule_shape():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=110,
                          min_lr_ratio=0.1)
    lrs = [float(cosine_lr(cfg, jnp.asarray(s))) for s in range(0, 120, 5)]
    assert lrs[0] == 0.0
    assert abs(max(lrs) - 1.0) < 0.1
    assert abs(lrs[-1] - 0.1) < 0.05            # decayed to min ratio
    peak = int(np.argmax(lrs))
    assert all(lrs[i] >= lrs[i + 1] - 1e-9 for i in range(peak, len(lrs) - 1))


@settings(max_examples=30, deadline=None)
# float32 values without subnormals: XLA flushes those to zero, numpy
# keeps them
@given(st.lists(st.floats(-100, 100, allow_nan=False, allow_subnormal=False,
                          width=32),
                min_size=1, max_size=8),
       st.floats(0.1, 10))
def test_clip_bounds_global_norm(vals, max_norm):
    g = {"x": jnp.asarray(vals, jnp.float32)}
    clipped, norm = clip_by_global_norm(g, max_norm)
    new_norm = float(global_norm(clipped))
    assert new_norm <= max_norm * (1 + 1e-3)
    if float(norm) <= max_norm:                 # no-op when under the bound
        np.testing.assert_allclose(np.asarray(clipped["x"]),
                                   np.asarray(g["x"], np.float32), rtol=1e-5)


def test_checkpoint_roundtrip():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ck.npz")
        checkpoint.save(path, tree, step=7)
        restored, step = checkpoint.restore(path, tree)
        assert step == 7
        np.testing.assert_array_equal(np.asarray(restored["a"]),
                                      np.asarray(tree["a"]))
        assert restored["b"]["c"].dtype == jnp.bfloat16

        bad = {"a": jnp.zeros((3, 2)), "b": {"c": jnp.ones((4,))}}
        with pytest.raises(ValueError):
            checkpoint.restore(path, bad)


def test_train_loop_reduces_loss():
    import dataclasses
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.models.model import Model, RuntimeFlags
    from repro.training import train_loop

    cfg = dataclasses.replace(get_config("llama3.2-1b").reduced(),
                              d_model=64, vocab_size=256, d_ff=128)
    model = Model(cfg, RuntimeFlags(dtype=jnp.float32))
    data = TokenPipeline(DataConfig(vocab_size=256, seq_len=64, batch_size=4))
    opt = OptimizerConfig(lr=3e-3, warmup_steps=5, total_steps=30)
    _, log = train_loop(model, opt, iter(data), 30, log_every=29,
                        verbose=False)
    assert log.losses[-1] < log.losses[0]
