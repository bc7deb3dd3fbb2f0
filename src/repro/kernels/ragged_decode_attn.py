"""Ragged decode attention — the LazyBatching decode hot-spot.

Lazily merged sub-batches have *ragged* per-request progress: each request
joined the batch at a different time, so each row of the merged decode batch
attends over a different KV length. On GPU the paper's prototype replays
per-request kernels; the TPU-native adaptation (DESIGN.md §3) executes the
whole merged sub-batch as ONE kernel:

  * grid = (batch, T // block_t): each step consumes one KV block of one row,
  * per-row ``lengths`` (scalar-prefetched into SMEM) masks invalid
    positions; rows with short KV skip whole blocks via a cheap
    ``all-masked`` early-out on the accumulate,
  * online softmax (m, l, acc) carried in f32 VMEM scratch across KV blocks,
  * GQA: queries are processed per KV group, so every score/PV product is a
    plain (G, D) x (D, block_t) MXU matmul (no head-repeat
    materialization in HBM).

VMEM budget per step: q (H·D) + k,v blocks (2·block_t·KV·D) + scratch
(H·(D+2)) f32 — with block_t=512, KV=8, D=128, H=32 that is ~1.3 MB,
comfortably inside the ~16 MB/core VMEM of TPU v5e. MXU alignment: D and
block_t are multiples of 128 in production configs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(slot_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc_ref, *, block_t: int, scale: float):
    del slot_ref          # consumed by the BlockSpec index maps only
    b = pl.program_id(0)
    j = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                   # (H, D)
    k = k_ref[0]                                   # (block_t, KV, D)
    v = v_ref[0]
    H, D = q.shape
    KV = k.shape[1]
    G = H // KV

    length = len_ref[b]
    tpos = j * block_t + jax.lax.iota(jnp.int32, block_t)
    valid = tpos < length                          # (block_t,)

    m_prev = m_ref[...]                            # (H, 1) f32
    l_prev = l_ref[...]
    acc_prev = acc_ref[...]                        # (H, D) f32

    scores = jnp.concatenate([
        jax.lax.dot_general(q[g * G:(g + 1) * G].astype(jnp.float32),
                            k[:, g, :].astype(jnp.float32),
                            (((1,), (1,)), ((), ())))      # (G, block_t)
        for g in range(KV)], axis=0) * scale
    scores = jnp.where(valid[None, :], scores, -1e30)

    m_cur = jnp.max(scores, axis=1, keepdims=True)          # (H, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.where(valid[None, :], jnp.exp(scores - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)

    pv = jnp.concatenate([
        jax.lax.dot_general(p[g * G:(g + 1) * G],
                            v[:, g, :].astype(jnp.float32),
                            (((1,), (0,)), ((), ())))       # (G, D)
        for g in range(KV)], axis=0)
    acc_new = acc_prev * corr + pv

    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(j == nt - 1)
    def _done():
        o_ref[0] = (acc_new / jnp.maximum(l_new, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def ragged_decode_attention(q, k, v, lengths, *, slots=None,
                            block_t: int = 512,
                            interpret: bool | None = None):
    """q: (B, H, D); k, v: (N, T, KV, D); lengths: (B,) int32 — row i attends
    to k[row_i, :lengths[i]]. Returns (B, H, D).

    ``slots`` ((B,) int32, optional) maps query row i to K/V arena row
    ``slots[i]`` — the zero-copy path for the serving engine's persistent
    slot arena (N = n_slots >= B): the scalar-prefetched slot vector drives
    the K/V BlockSpec index maps, so each grid step DMAs exactly the KV
    block of its request's slot and no (B, T, KV, D) gather is ever
    materialized. Without ``slots``, row i reads k[i] (N == B).
    """
    B, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    if H % KV != 0:
        raise ValueError(
            f"ragged_decode_attention: query heads H={H} must be a "
            f"multiple of kv heads KV={KV} (grouped-query repeat factor)")
    block_t = min(block_t, T)
    if T % block_t != 0:
        raise ValueError(
            f"ragged_decode_attention: kv length T={T} must be a "
            f"multiple of block_t={block_t} — pad the arena length or "
            f"pass a divisor block")
    if slots is None:
        slots = jnp.arange(B, dtype=jnp.int32)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_kernel, block_t=block_t, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, T // block_t),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, j, slot, lens: (b, 0, 0)),
            pl.BlockSpec((1, block_t, KV, D),
                         lambda b, j, slot, lens: (slot[b], j, 0, 0)),
            pl.BlockSpec((1, block_t, KV, D),
                         lambda b, j, slot, lens: (slot[b], j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, j, slot, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
        name="ragged_decode_attention",
    )(slots, lengths, q, k, v)
