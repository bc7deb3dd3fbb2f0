"""Operations and bytes that the algorithm needs, counted from shapes.

These count the work itself, not the grid that some kernel launches, so
they stay the same whatever implements it: decode attention reads each
live row's query, the keys and values of its valid context and writes its
output; a decode step multiplies every weight matrix once per row and
attends over each row's context.
"""
from __future__ import annotations

from typing import Iterable, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def layer_matmul_params(dims: dict) -> int:
    """Weights one decode row multiplies in one layer."""
    d, h = dims["hidden_size"], dims["num_attention_heads"]
    kv, hd = dims["num_key_value_heads"], dims["head_dim"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d \
        + 3 * d * dims["intermediate_size"]


def attention_flops(dims: dict, ctx: int) -> int:
    """Scores and weighted values of one row over ``ctx`` keys, one
    layer: 2 * heads * ctx * (key width + value width)."""
    return 4 * dims["num_attention_heads"] * dims["head_dim"] * ctx


def decode_step_flops(dims: dict, ctxs: Iterable[int]) -> int:
    """Model FLOPs of one decode step (every layer and the head) for rows
    whose valid contexts are ``ctxs``."""
    per_row = 2 * (dims["num_hidden_layers"] * layer_matmul_params(dims)
                   + dims["hidden_size"] * dims["vocab_size"])
    return sum(per_row + dims["num_hidden_layers"] * attention_flops(dims, c)
               for c in ctxs)


def decode_attn_work(dims: dict, ctxs: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) of one GQA decode-attention call over rows with
    valid contexts ``ctxs``: q, K and V of each row's valid context, and
    the output, in the served dtype."""
    h, kv, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                 dims["head_dim"])
    b = DTYPE_BYTES[dims["dtype"]]
    flops = bytes_ = 0
    for c in ctxs:
        flops += 4 * h * hd * c
        bytes_ += (2 * c * kv * hd + 2 * h * hd) * b
    return flops, bytes_


def roofline_seconds(flops: float, bytes_: float, peak: dict) -> float:
    """Least time on the chip: the larger of the compute and memory
    bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               bytes_ / peak["hbm_bytes_per_s"])
