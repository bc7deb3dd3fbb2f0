"""The harness beyond dense GQA: ``harness.program_config`` holds a
configuration file of latent attention (MLA) or experts (MoE) to the
program, key by key and kind by kind; the reference passes each layer's
index, so layers may differ; and ``bench.work`` counts MLA and expert
layers from shapes."""
import dataclasses
import json
import math
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, work
from bench.reference import common, dense_gqa

ROOT = Path(__file__).resolve().parents[2]

# the program's minicpm3-4b and granite-moe-3b-a800m, as a file states them
MINICPM3 = {"arch": "minicpm3-4b", "attention": "mla", "hidden_size": 2560,
            "num_attention_heads": 40, "num_key_value_heads": 40,
            "q_lora_rank": 768, "kv_lora_rank": 256, "qk_nope_head_dim": 64,
            "qk_rope_head_dim": 32, "v_head_dim": 64,
            "intermediate_size": 6400, "vocab_size": 73448,
            "rope_theta": 10000, "rope_scaling": None, "rms_norm_eps": 1e-5,
            "tie_word_embeddings": True, "num_hidden_layers": 2,
            "hidden_act": "silu"}
GRANITE = {"arch": "granite-moe-3b-a800m", "attention": "gqa",
           "hidden_size": 1536, "num_attention_heads": 24,
           "num_key_value_heads": 8, "head_dim": 64, "vocab_size": 49155,
           "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
           "tie_word_embeddings": True, "num_hidden_layers": 4,
           "n_routed_experts": 40, "num_experts_per_tok": 8,
           "moe_intermediate_size": 512, "n_shared_experts": 0,
           "first_k_dense_replace": 0, "scoring_func": "softmax",
           "norm_topk_prob": True, "routed_scaling_factor": 1}
NEMO_FILE = json.loads((ROOT / "bench" / "configs"
                        / "mistral-nemo-12b.json").read_text())

# DeepSeek-V2-Lite (huggingface.co/deepseek-ai/DeepSeek-V2-Lite, config.json)
# with 8 of its 64 routed experts held, as eight chips would share a layer
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
DSV2_LITE = {"arch": "deepseek-v2-lite", "attention": "mla",
             "attention_bias": False, "first_k_dense_replace": 1,
             "hidden_act": "silu", "hidden_size": 2048,
             "intermediate_size": 10944, "kv_lora_rank": 512,
             "max_position_embeddings": 163840, "model_type": "deepseek_v2",
             "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
             "n_routed_experts": 8, "n_shared_experts": 2,
             "norm_topk_prob": False, "num_attention_heads": 16,
             "num_experts_per_tok": 6, "num_hidden_layers": 27,
             "num_key_value_heads": 16, "q_lora_rank": None,
             "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
             "rms_norm_eps": 1e-06, "rope_scaling": YARN, "rope_theta": 10000,
             "routed_scaling_factor": 1, "scoring_func": "softmax",
             "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1,
             "topk_method": "greedy", "v_head_dim": 128, "vocab_size": 102400,
             "dtype": "bfloat16",
             "reduced": {"n_routed_experts": {"published": 64, "here": 8}}}


@dataclasses.dataclass(frozen=True)
class HeldMoE:
    """An expert layer as a program that holds a share of the experts,
    with shared experts and leading dense layers, would describe it."""
    num_experts: int
    experts_per_token: int
    experts_held: int
    n_shared_experts: int
    first_k_dense_replace: int
    dense_intermediate_size: int
    norm_topk_prob: bool


def _future_program():
    """DeepSeek-V2-Lite as a program with the attributes it lacks today
    (YaRN, no q_lora, shared experts, a dense first layer, a held share)
    would give it."""
    from repro.configs import MLAConfig, ModelConfig

    @dataclasses.dataclass(frozen=True)
    class Config(ModelConfig):
        rope_scaling: Optional[dict] = None

    return Config(
        name="deepseek-v2-lite", family="moe", num_layers=27, d_model=2048,
        num_heads=16, num_kv_heads=16, head_dim=192, d_ff=1408,
        vocab_size=102400, attention="mla", rope_theta=1e4, norm_eps=1e-6,
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512,
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128),
        moe=HeldMoE(num_experts=64, experts_per_token=6, experts_held=8,
                    n_shared_experts=2, first_k_dense_replace=1,
                    dense_intermediate_size=10944, norm_topk_prob=False),
        rope_scaling=dict(YARN))


_DROP = object()


def _with(base, **kw):
    """``base`` with the keys in ``kw`` set, or dropped where ``_DROP``."""
    out = dict(base, **kw)
    return {k: v for k, v in out.items() if v is not _DROP}


# ---------------------------------------------------------------------------
# program_config
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("config", [NEMO_FILE, MINICPM3, GRANITE],
                         ids=["gqa-nemo", "mla-minicpm3", "moe-granite"])
def test_program_config_accepts_a_matching_file(config):
    cfg = harness.program_config(config)
    assert cfg.num_layers == config["num_hidden_layers"]
    assert cfg.d_model == config["hidden_size"]


def test_program_config_accepts_deepseek_once_the_program_holds_it(
        monkeypatch):
    """Every key of DeepSeek-V2-Lite's published config is checked, and
    a program that holds what it states passes with no harness edit."""
    import repro.configs

    monkeypatch.setattr(repro.configs, "get_config",
                        lambda arch: _future_program())
    assert harness.program_config(DSV2_LITE).num_layers == 27


REFUSED = {
    # widths that differ from the program's
    "gqa-head-dim": (NEMO_FILE, {"head_dim": 160}, "differs"),
    "mla-kv-lora": (MINICPM3, {"kv_lora_rank": 512}, "differs"),
    "mla-no-q-lora": (MINICPM3, {"q_lora_rank": None}, "differs"),
    "moe-expert-width": (GRANITE, {"moe_intermediate_size": 1024},
                         "differs"),
    "moe-top-k": (GRANITE, {"num_experts_per_tok": 6}, "differs"),
    # keys the program has no attribute for, at values it does not compute
    "moe-renormalise": (GRANITE, {"norm_topk_prob": False}, "has no"),
    # a held share of the experts the program cannot hold (it holds all)
    "moe-held-share": (GRANITE, {"n_routed_experts": 8, "reduced": {
        "n_routed_experts": {"published": 40, "here": 8}}}, "has no"),
    "moe-router-width": (GRANITE, {"n_routed_experts": 8}, "has no"),
    "moe-shared-experts": (GRANITE, {"n_shared_experts": 2}, "has no"),
    "moe-dense-lead": (GRANITE, {"first_k_dense_replace": 1}, "has no"),
    "moe-dense-width": (GRANITE, {"intermediate_size": 10944}, "has no"),
    "mla-yarn": (MINICPM3, {"rope_scaling": YARN}, "has no"),
    # keys foreign to the file's kinds, or unknown to every kind
    "mla-head-dim": (MINICPM3, {"head_dim": 64}, "foreign"),
    "gqa-kv-lora": (NEMO_FILE, {"kv_lora_rank": 512}, "foreign"),
    "dense-expert-key": (NEMO_FILE, {"num_experts_per_tok": 2}, "foreign"),
    "moe-dense-key-only": (NEMO_FILE, {"first_k_dense_replace": 0},
                           "foreign"),
    "unknown-key": (MINICPM3, {"sliding_window": 4096}, "unknown"),
    # kinds that do not match the program's
    "moe-file-dense-program": (NEMO_FILE, {"n_routed_experts": 8}, "moe"),
    "dense-file-moe-program": (GRANITE, {"n_routed_experts": _DROP,
                                         "intermediate_size": 512}, "moe"),
    "attention-kind": (MINICPM3, {"attention": "gqa"}, "attention"),
    "required-width-missing": (MINICPM3, {"v_head_dim": _DROP}, "lacks"),
    "hybrid-program": ({"arch": "recurrentgemma-9b", "attention": "gqa"}, {},
                       "hybrid"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_program_config_refuses(case):
    base, change, why = REFUSED[case]
    with pytest.raises(ValueError, match=why):
        harness.program_config(_with(base, **change))


# ---------------------------------------------------------------------------
# the reference's layer index
# ---------------------------------------------------------------------------

DIMS = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "intermediate_size": 96, "vocab_size": 128,
        "num_hidden_layers": 3, "rope_theta": 1e4, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": False, "dtype": "bfloat16"}


def _inputs():
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, 128, n).astype(np.int32) for n in (5, 13)]
    want = [np.arange(1, 5), np.arange(6, 13)]
    return seqs, want


def _forward_without_index(dims, seed, seqs, want, fp8, pad_to):
    """``forward_logits`` as it was before layers got their index."""
    dtype = common.DTYPES[dims["dtype"]]
    d, eps = dims["hidden_size"], dims["rms_norm_eps"]
    k_emb, k_blocks, k_head, _ = jax.random.split(jax.random.key(seed), 4)
    lin = common.Linear(fp8)
    table = common.draw(k_emb, (dims["vocab_size"], d), 1 / math.sqrt(d),
                        dtype)
    xs = []
    for s in seqs:
        padded = np.zeros(-(-len(s) // pad_to) * pad_to, np.int32)
        padded[:len(s)] = s
        xs.append(jnp.take(table, jnp.asarray(padded), axis=0))
    head = common.draw(k_head, (d, dims["vocab_size"]), 1 / math.sqrt(d),
                       dtype)
    step = jax.jit(lambda w, x: dense_gqa.block(lin, w, x, dims, None))
    for bk in jax.random.split(k_blocks, dims["num_hidden_layers"]):
        w = dense_gqa.layer_weights(bk, dims, dtype, None)
        xs = [step(w, x) for x in xs]
    return [np.asarray(lin("sd,dv->sv", common.rms_norm(
        x[jnp.asarray(p)], eps), head)) for x, p in zip(xs, want)]


@pytest.mark.parametrize("fp8", [False, True], ids=["reference", "control"])
def test_dense_gqa_logits_unchanged_by_the_layer_index(fp8):
    seqs, want = _inputs()
    with jax.default_matmul_precision("highest"):
        new = common.forward_logits(DIMS, 3, seqs, want,
                                    dense_gqa.layer_weights,
                                    dense_gqa.block, fp8=fp8, pad_to=8)
        old = _forward_without_index(DIMS, 3, seqs, want, fp8, 8)
    for a, b in zip(new, old):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_reference_may_give_layer_zero_its_own_block():
    """A module whose first layer is dense and the others something else
    (here: attention alone), chosen by the index, without any edit of
    ``common``: each layer gets its own key and its own equation."""
    seen = []

    def layer_weights(key, dims, dtype, layer):
        w = dense_gqa.layer_weights(key, dims, dtype, layer)
        if layer > 0:
            del w["mlp"]
        return w

    def block(lin, w, x, dims, layer):
        seen.append((layer, "mlp" in w))
        if layer == 0:
            return dense_gqa.block(lin, w, x, dims, layer)
        w = dict(w, mlp={"gate": jnp.zeros((64, 1)), "up": jnp.zeros((64, 1)),
                         "down": jnp.zeros((1, 64))})
        return dense_gqa.block(lin, w, x, dims, layer)

    seqs, want = _inputs()
    with jax.default_matmul_precision("highest"):
        got = common.forward_logits(DIMS, 3, seqs, want, layer_weights,
                                    block, pad_to=8)
        # by hand: the same keys, layer 0 whole, layers 1 and 2 with their
        # MLP's output zeroed
        dtype = jnp.bfloat16
        k_emb, k_blocks, k_head, _ = jax.random.split(jax.random.key(3), 4)
        lin = common.Linear(False)
        table = common.draw(k_emb, (128, 64), 1 / 8, dtype)
        head = common.draw(k_head, (64, 128), 1 / 8, dtype)
        ref = []
        for s, p in zip(seqs, want):
            x = jnp.take(table, jnp.asarray(np.pad(s, (0, -len(s) % 8))),
                         axis=0)
            for i, bk in enumerate(jax.random.split(k_blocks, 3)):
                w = dense_gqa.layer_weights(bk, DIMS, dtype, i)
                if i:
                    w["mlp"]["down"] = jnp.zeros_like(w["mlp"]["down"])
                x = dense_gqa.block(lin, w, x, DIMS, i)
            ref.append(np.asarray(lin("sd,dv->sv", common.rms_norm(
                x[jnp.asarray(p)], 1e-5), head)))
    assert sorted(set(seen)) == [(0, True), (1, False), (2, False)]
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# work counts
# ---------------------------------------------------------------------------

# 27 layers: q 2048 x 16 x 192, kv_a 2048 x 576, kv_b 512 x 16 x 256,
# o 2048 x 2048 = 13,762,560 a layer; layer 0's MLP 3 x 2048 x 10944;
# layers 1-26: router 2048 x 64, 2 shared experts and 6 x 8 / 64 routed
# ones of 3 x 2048 x 1408; the head 2048 x 102400
DSV2_COUNTS = {
    "row_matmul_params": (lambda: work.row_matmul_params(DSV2_LITE),
                          1_270_480_896),
    "flops_before_attention": (lambda: work.decode_step_flops(DSV2_LITE, [0]),
                               2_540_961_792),
    "attention_flops_per_ctx": (lambda: work.attention_flops(DSV2_LITE, 1),
                                34_816),
    "attention_flops_4k": (lambda: work.attention_flops(DSV2_LITE, 4096),
                           34_816 * 4096),
    "attn_params": (lambda: work.attn_matmul_params(DSV2_LITE), 13_762_560),
    "dense_layer": (lambda: work.layer_matmul_params(DSV2_LITE, 0),
                    13_762_560 + 3 * 2048 * 10944),
    "expert_layer": (lambda: work.layer_matmul_params(DSV2_LITE, 1),
                     13_762_560 + 2048 * 64 + (2 + 0.75) * 3 * 2048 * 1408),
    "step_flops_two_rows": (
        lambda: work.decode_step_flops(DSV2_LITE, [100, 300]),
        2 * 2_540_961_792 + 27 * 34_816 * 400),
    "mla_attn_work_1k": (
        lambda: work.decode_attn_work(DSV2_LITE, [1000]),
        (34_816 * 1000, (1000 * 576 + 16 * 576 + 16 * 512) * 2)),
    "moe_work_one_row": (
        lambda: work.moe_decode_work(DSV2_LITE, 1),
        (2 * (2048 * 64 + 2.75 * 3 * 2048 * 1408),
         (2048 * 64 + (2 + 8 * 6 / 64) * 3 * 2048 * 1408 + 2 * 2048) * 2)),
    "moe_work_many_rows_touch_all_held": (
        lambda: work.moe_decode_work(DSV2_LITE, 4000)[1],
        (2048 * 64 + 10 * 3 * 2048 * 1408 + 2 * 4000 * 2048) * 2),
}


@pytest.mark.parametrize("count", sorted(DSV2_COUNTS))
def test_work_counts_at_deepseek_v2_lite_dims(count):
    fn, want = DSV2_COUNTS[count]
    assert fn() == pytest.approx(want, rel=1e-12)


def _old_decode_step_flops(dims, ctxs):
    """``decode_step_flops`` and ``decode_attn_work`` of dense GQA, as
    they were before the counts learnt other kinds."""
    d, h = dims["hidden_size"], dims["num_attention_heads"]
    kv, hd = dims["num_key_value_heads"], dims["head_dim"]
    layer = d * h * hd + 2 * d * kv * hd + h * hd * d \
        + 3 * d * dims["intermediate_size"]
    per_row = 2 * (dims["num_hidden_layers"] * layer
                   + dims["hidden_size"] * dims["vocab_size"])
    return sum(per_row + dims["num_hidden_layers"] * 4 * h * hd * c
               for c in ctxs)


def _old_decode_attn_work(dims, ctxs):
    h, kv, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                 dims["head_dim"])
    b = work.DTYPE_BYTES[dims["dtype"]]
    return (sum(4 * h * hd * c for c in ctxs),
            sum((2 * c * kv * hd + 2 * h * hd) * b for c in ctxs))


@pytest.mark.parametrize("ctxs", [[1], [512], [100, 3000], [2048] * 8,
                                  list(range(1, 4097, 511))])
def test_nemo_counts_as_before(ctxs):
    got_f = work.decode_step_flops(NEMO_FILE, ctxs)
    assert type(got_f) is int and got_f == _old_decode_step_flops(
        NEMO_FILE, ctxs)
    assert work.decode_attn_work(NEMO_FILE, ctxs) == _old_decode_attn_work(
        NEMO_FILE, ctxs)
