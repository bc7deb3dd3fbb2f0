"""The benchmark's ``correct`` comes out false when the timed path is
broken underneath it, once for each fault a served cell can have: a
token altered where it is produced, and a decode step that returns its
cache unchanged. (One chip: there is no exchange between chips to drop;
serving takes no mean over a batch to halve.) The cells run at a CPU
size past the harness's look for a chip; the limit is the committed one
of the architecture's first cell."""
import json
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
LIMITS = {"mistral-nemo-12b": "nemo12b-chat-bursty"}


def limit(arch):
    cell = json.loads((ROOT / "bench" / "cells"
                       / f"{LIMITS[arch]}.json").read_text())
    return cell["max_logit_gap"]


@pytest.mark.parametrize("arch", sorted(LIMITS))
def test_sound_program_is_correct(arch, tiny_cell):
    out = tiny_cell(arch, 2**31 + 11, limit(arch))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("arch", sorted(LIMITS))
def test_altered_token_is_caught(arch, tiny_cell, monkeypatch):
    from repro.models.model import Model
    unembed = Model.unembed
    monkeypatch.setattr(Model, "unembed", lambda self, params, x: jnp.roll(
        unembed(self, params, x), 1, axis=-1))
    out = tiny_cell(arch, 12, limit(arch))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("arch", sorted(LIMITS))
def test_unchanged_decode_state_is_caught(arch, tiny_cell, monkeypatch):
    from repro.models import layers as L
    step = L.apply_attention_decode

    def stale(p, x, cache, *a, **kw):
        y, _ = step(p, x, cache, *a, **kw)
        return y, cache                  # the step's cache write is lost

    monkeypatch.setattr(L, "apply_attention_decode", stale)
    out = tiny_cell(arch, 13, limit(arch))
    assert not out["correct"], out["checks"]
