"""Dense decoder with grouped-query attention (Mistral-Nemo): pre-norm
residual blocks of causal GQA with rotary embedding and a SwiGLU MLP."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import causal_softmax_av, draw, mlp, mlp_weights, rms_norm, rope


def layer_weights(key, dims: dict, dtype, layer: int) -> dict:
    d, h = dims["hidden_size"], dims["num_attention_heads"]
    kv, hd = dims["num_key_value_heads"], dims["head_dim"]
    k_attn, k_mlp = jax.random.split(key)
    ks = jax.random.split(k_attn, 4)
    s = 1 / math.sqrt(d)
    return {"wq": draw(ks[0], (d, h, hd), s, dtype),
            "wk": draw(ks[1], (d, kv, hd), s, dtype),
            "wv": draw(ks[2], (d, kv, hd), s, dtype),
            "wo": draw(ks[3], (h, hd, d), 1 / math.sqrt(h * hd), dtype),
            "mlp": mlp_weights(k_mlp, d, dims["intermediate_size"], dtype)}


def block(lin, w, x, dims: dict, layer: int):
    """One layer over a whole sequence x (S, d); every layer alike."""
    eps, theta = dims["rms_norm_eps"], dims["rope_theta"]
    h, kv = dims["num_attention_heads"], dims["num_key_value_heads"]
    pos = jnp.arange(x.shape[0])
    a = rms_norm(x, eps)
    q = rope(lin("sd,dhk->shk", a, w["wq"]), pos, theta)
    k = rope(lin("sd,dhk->shk", a, w["wk"]), pos, theta)
    v = lin("sd,dhk->shk", a, w["wv"])
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    scores = jnp.einsum("shk,thk->hst", q, k,
                        precision=jax.lax.Precision.HIGHEST)
    o = causal_softmax_av(scores / math.sqrt(dims["head_dim"]), v,
                          x.shape[0])
    x = x + lin("shk,hkd->sd", o, w["wo"])
    return x + mlp(lin, w["mlp"], rms_norm(x, eps))
