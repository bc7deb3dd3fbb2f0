"""Pieces a reference is built from: weight draws, norms, rotary
embedding, the SwiGLU MLP, the lower-precision control and the pass over
the whole model."""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def draw(key, shape, scale: float, dtype) -> jax.Array:
    """One weight: a standard normal draw times ``scale``, rounded to the
    served dtype and widened back to float32 for the reference."""
    return (jax.random.normal(key, shape) * scale).astype(dtype).astype(
        jnp.float32)


def fake_fp8(x: jax.Array, axis: int) -> jax.Array:
    """``x`` through float8 e4m3 with one scale per slice along ``axis``
    (the largest magnitude maps to the format's largest finite value)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(FP8).astype(jnp.float32) * s


class Linear:
    """Matrix products of the reference, or of its fp8 control: with
    ``fp8`` every projection's weight is quantised per output channel and
    its input per token before a float32 product."""

    def __init__(self, fp8: bool):
        self.fp8 = fp8

    def __call__(self, spec: str, x: jax.Array, w: jax.Array) -> jax.Array:
        if self.fp8:
            x = fake_fp8(x, axis=-1)
            n_in = spec.split(",")[1].split("->")[0]
            # contracted axes of w: those its input letters share with x
            x_letters = spec.split(",")[0]
            red = tuple(i for i, c in enumerate(n_in) if c in x_letters)
            w = fake_fp8(w, axis=red)
        return jnp.einsum(spec, x, w, precision=HI)


def rms_norm(x: jax.Array, eps: float) -> jax.Array:
    """RMSNorm with unit gain (the served model initialises gains to 1)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding, rotate-half layout: x (S, ..., D), positions (S,)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * freqs
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def causal_softmax_av(scores: jax.Array, v: jax.Array, n_valid: int):
    """scores (H, S, S) -> softmax over keys <= query position, times
    v (S, H, Dv) -> (S, H, Dv). Padding keys past ``n_valid`` lie after
    every valid query, so the causal mask already hides them."""
    S = scores.shape[-1]
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    scores = jnp.where(mask[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hst,thd->shd", p, v, precision=HI)


def mlp_weights(key, d: int, ff: int, dtype) -> Dict[str, jax.Array]:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"gate": draw(k1, (d, ff), 1 / math.sqrt(d), dtype),
            "up": draw(k2, (d, ff), 1 / math.sqrt(d), dtype),
            "down": draw(k3, (ff, d), 1 / math.sqrt(ff), dtype)}


def mlp(lin: Linear, w, x):
    h = jax.nn.silu(lin("sd,df->sf", x, w["gate"])) \
        * lin("sd,df->sf", x, w["up"])
    return lin("sf,fd->sd", h, w["down"])


def forward_logits(dims: dict, seed: int, seqs: Sequence[np.ndarray],
                   want: Sequence[np.ndarray], layer_weights: Callable,
                   block: Callable, fp8: bool = False,
                   pad_to: int = 512) -> List[np.ndarray]:
    """Float32 logits of each sequence at the positions in ``want``.

    The model is rebuilt from ``seed`` one layer at a time (the served
    model's key tree: embed, blocks, head split four ways from the seed,
    one key per layer, two per block), and every sequence is carried
    through that layer before the next is drawn. Sequences are padded at
    the end to a multiple of ``pad_to``; causal attention keeps padding
    out of every real position."""
    dtype = DTYPES[dims["dtype"]]
    d, eps = dims["hidden_size"], dims["rms_norm_eps"]
    k_emb, k_blocks, k_head, _ = jax.random.split(jax.random.key(seed), 4)
    lin = Linear(fp8)
    table = draw(k_emb, (dims["vocab_size"], d), 1 / math.sqrt(d), dtype)
    xs = []
    for s in seqs:
        n = len(s)
        padded = np.zeros(-(-n // pad_to) * pad_to, np.int32)
        padded[:n] = s
        xs.append(jnp.take(table, jnp.asarray(padded), axis=0))
    if dims["tie_word_embeddings"]:
        head = table.T
    else:
        del table
        head = draw(k_head, (d, dims["vocab_size"]), 1 / math.sqrt(d), dtype)
    step = jax.jit(lambda w, x: block(lin, w, x, dims))
    for bk in jax.random.split(k_blocks, dims["num_hidden_layers"]):
        w = layer_weights(bk, dims, dtype)
        xs = [step(w, x) for x in xs]
        del w
    out = []
    for x, pos in zip(xs, want):
        h = rms_norm(x[jnp.asarray(pos)], eps)
        out.append(np.asarray(lin("sd,dv->sv", h, head)))
    return out
