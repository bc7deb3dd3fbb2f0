"""Operations and bytes that the algorithm needs, counted from shapes.

These count the work itself, not the grid that some kernel launches, so
they stay the same whatever implements it: decode attention reads each
live row's query, the keys and values of its valid context and writes its
output; a decode step multiplies every weight matrix once per row and
attends over each row's context.

``dims`` is a configuration file (``bench/configs``). Its ``attention``
(``gqa`` or ``mla``) picks the attention counts; a file that states
``n_routed_experts`` has expert layers after its first
``first_k_dense_replace`` dense ones. Which experts a row reaches is data,
not shape, so an expert layer is counted at its expected value: each row
takes ``num_experts_per_tok`` of the ``E`` routed experts (the published
count, the router's width), of which ``held / E`` live on this chip.
"""
from __future__ import annotations

from typing import Iterable, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def published(dims: dict, key: str):
    """The published value of ``key``: as stated in the file, or as the
    file's ``reduced`` records it where the file holds a cut of it."""
    cut = dims.get("reduced", {}).get(key)
    return cut["published"] if cut else dims[key]


def is_expert_layer(dims: dict, layer: int) -> bool:
    return "n_routed_experts" in dims \
        and layer >= dims.get("first_k_dense_replace", 0)


def attn_matmul_params(dims: dict) -> int:
    """Attention weights one decode row multiplies in one layer.

    GQA: q, k, v and o. MLA, in the absorbed form: ``q_proj`` (or its two
    low-rank halves), ``kv_a_proj`` (latent and shared rope key),
    ``kv_b_proj`` (folded into the query and the output, the same weights
    once each) and ``o_proj``."""
    d, h = dims["hidden_size"], dims["num_attention_heads"]
    if dims["attention"] == "mla":
        nope, rope = dims["qk_nope_head_dim"], dims["qk_rope_head_dim"]
        kvl, v, ql = dims["kv_lora_rank"], dims["v_head_dim"], \
            dims["q_lora_rank"]
        q = d * h * (nope + rope) if ql is None \
            else d * ql + ql * h * (nope + rope)
        return q + d * (kvl + rope) + kvl * h * (nope + v) + h * v * d
    kv, hd = dims["num_key_value_heads"], dims["head_dim"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def ffn_matmul_params(dims: dict, layer: int = 0):
    """Feed-forward weights one decode row multiplies in layer ``layer``:
    the SwiGLU MLP of a dense layer; in an expert layer the router (over
    all ``E`` experts), the shared experts, and the expected share of a
    row's routed experts that lies on this chip, ``k * held / E``."""
    d = dims["hidden_size"]
    if not is_expert_layer(dims, layer):
        return 3 * d * dims["intermediate_size"]
    e = published(dims, "n_routed_experts")
    per_expert = 3 * d * dims["moe_intermediate_size"]
    routed = dims["num_experts_per_tok"] * dims["n_routed_experts"] / e
    return d * e + (dims.get("n_shared_experts", 0) + routed) * per_expert


def layer_matmul_params(dims: dict, layer: int = 0):
    """Weights one decode row multiplies in layer ``layer``."""
    return attn_matmul_params(dims) + ffn_matmul_params(dims, layer)


def row_matmul_params(dims: dict):
    """Weights one decode row multiplies in one step: every layer and the
    head."""
    return sum(layer_matmul_params(dims, i)
               for i in range(dims["num_hidden_layers"])) \
        + dims["hidden_size"] * dims["vocab_size"]


def attention_flops(dims: dict, ctx: int) -> int:
    """Scores and weighted values of one row over ``ctx`` keys, one
    layer. GQA: 2 * heads * ctx * (key width + value width). MLA, absorbed:
    each head scores the latent and rope key (kv_lora + rope wide) and
    sums the latent (kv_lora wide), 2 * heads * ctx * (2 kv_lora + rope)."""
    h = dims["num_attention_heads"]
    if dims["attention"] == "mla":
        return 2 * h * ctx * (2 * dims["kv_lora_rank"]
                              + dims["qk_rope_head_dim"])
    return 4 * h * dims["head_dim"] * ctx


def decode_step_flops(dims: dict, ctxs: Iterable[int]):
    """Model FLOPs of one decode step (every layer and the head) for rows
    whose valid contexts are ``ctxs``."""
    per_row = 2 * row_matmul_params(dims)
    n = dims["num_hidden_layers"]
    return sum(per_row + n * attention_flops(dims, c) for c in ctxs)


def decode_attn_work(dims: dict, ctxs: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) of one decode-attention call, one layer, over rows
    with valid contexts ``ctxs``, in the served dtype. GQA: q, K and V of
    each row's valid context, and the output. MLA, absorbed: the cached
    latent and rope key of each row's valid context, ``ctx * (kv_lora +
    rope)`` elements, the absorbed query (heads x (kv_lora + rope)) and
    the latent output (heads x kv_lora)."""
    h = dims["num_attention_heads"]
    b = DTYPE_BYTES[dims["dtype"]]
    flops = bytes_ = 0
    for c in ctxs:
        flops += attention_flops(dims, c)
        if dims["attention"] == "mla":
            kvl, rope = dims["kv_lora_rank"], dims["qk_rope_head_dim"]
            bytes_ += (c * (kvl + rope) + h * (kvl + rope) + h * kvl) * b
        else:
            kv, hd = dims["num_key_value_heads"], dims["head_dim"]
            bytes_ += (2 * c * kv * hd + 2 * h * hd) * b
    return flops, bytes_


def moe_decode_work(dims: dict, rows: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one expert layer's feed-forward for ``rows``
    decode rows, in the served dtype. FLOPs: every row's router, shared
    experts and expected routed share (``ffn_matmul_params``). Bytes: the
    router and shared experts' weights, the weights of the held experts
    that at least one row reaches, ``held * (1 - (1 - k/E) ** rows)`` in
    expectation, and each row's input and output."""
    d, b = dims["hidden_size"], DTYPE_BYTES[dims["dtype"]]
    e, held = published(dims, "n_routed_experts"), dims["n_routed_experts"]
    k = dims["num_experts_per_tok"]
    per_expert = 3 * d * dims["moe_intermediate_size"]
    layer = dims.get("first_k_dense_replace", 0)
    flops = 2 * rows * ffn_matmul_params(dims, layer)
    touched = held * (1 - (1 - k / e) ** rows)
    weights = d * e + (dims.get("n_shared_experts", 0) + touched) \
        * per_expert
    return flops, (weights + 2 * rows * d) * b


def roofline_seconds(flops: float, bytes_: float, peak: dict) -> float:
    """Least time on the chip: the larger of the compute and memory
    bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               bytes_ / peak["hbm_bytes_per_s"])
