"""Operations and bytes from shapes, against hand counts."""
import json

import pytest

from bench import work
from bench.spec import BENCH

MINI = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 1,
        "num_attention_heads": 4, "vocab_size": 256}


def test_sign_gemm_decode_hand_count():
    # wq, wk, wv, wo: 64x64; w_gate, w_up: 128x64; w_down: 64x128.
    # per (N, K) and 2 rows of one variant: GEMM 2*2*N*K; plane N*K/8,
    # vector 2*min(N, K), rows of x 2*2*K and of the fp32 output 2*4*N
    flops, byts = work.sign_gemm_decode(MINI, {"v0": 2})
    assert flops == 4 * 16384 + 2 * 32768 + 32768
    assert byts == 4 * (512 + 128 + 768) + 2 * (1024 + 128 + 1280) + (
        1024 + 128 + 1024)
    assert (flops, byts) == (163840, 12672)


def test_sign_gemm_decode_per_variant_and_none_for_base():
    one = work.sign_gemm_decode(MINI, {"v0": 1})
    two = work.sign_gemm_decode(MINI, {"v0": 1, "v1": 1})
    assert two[0] == 2 * one[0] and two[1] == 2 * one[1]
    assert work.sign_gemm_decode(MINI, {}) == (0, 0)


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_seconds(1000, 50, peak) == (10.0, "flops")
    assert work.roofline_seconds(100, 50, peak) == (5.0, "bytes")


def test_flops_per_token_hand_count():
    # projections 2*64*(2*64+2*64), attention 2*2*ctx*64, gated MLP
    # 2*3*64*128, unembedding 2*64*256
    assert work.flops_per_token(MINI, 10) == 2 * (16384 + 1280) + 49152 \
        + 32768


def test_moe_flops_count_top_k_and_shared_experts_only():
    moe = dict(MINI, num_hidden_layers=2, first_k_dense_replace=1,
               n_routed_experts=8, n_shared_experts=1,
               num_experts_per_tok=2, moe_intermediate_size=32)
    dense = dict(MINI, num_hidden_layers=1)
    extra_layer = 2 * (16384 + 2 * 1 * 64) + 2 * ((2 + 1) * 3 * 64 * 32
                                                  + 8 * 64)
    assert work.flops_per_token(moe, 1) == pytest.approx(
        work.flops_per_token(dense, 1) + extra_layer)


def _conf(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_deployment_bytes_match_the_program_memory_plan():
    """The copied arithmetic agrees with launch/build.memory_plan at the
    published width (shapes only, nothing allocated)."""
    from bench.system import program_config
    from repro.launch import build as B
    from repro.models import build_model
    conf = _conf("deepseek-7b.int8")
    model = build_model(program_config(conf))
    plan = B.memory_plan(model, base_dtype="int8", bank_size=1, kv_tokens=1)
    assert work.slot_bytes(conf) == plan["slot"]
    assert work.base_bytes(conf) == plan["base"]
    assert work.kv_bytes_per_token(conf) == plan["kv_per_token"]


# Moonlight-16B-A3B's published config.json (moonshotai/Moonlight-16B-A3B),
# as the model-configs catalog has it, model_type left out
MOONLIGHT = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 163840}
# per layer: q 3072x2048, kv_a 576x2048, kv_b 4096x512, o 2048x2048
MLA_ATTN = 16 * 192 * 2048 + 576 * 2048 + 16 * 256 * 512 + 2048 * 16 * 128
DENSE_MLP = 3 * 2048 * 11264
EXPERT = 3 * 2048 * 1408
ROUTERS = 26 * 64 * 2048
VOCAB = 163840 * 2048


def _params(conf):
    return sum(c * n * k for c, n, k in work.targets(conf))


def test_moonlight_published_counts_16b_a3b():
    assert MLA_ATTN == 13_762_560
    assert _params(MOONLIGHT) == 27 * MLA_ATTN + DENSE_MLP + 26 * (
        64 + 2) * EXPERT == 15_285_485_568
    assert _params(MOONLIGHT) + ROUTERS + 2 * VOCAB == 15_959_982_080
    # active a token: the top 6 and 2 shared experts, the embedding row
    active = work.flops_per_token(MOONLIGHT, 0) / 2 + VOCAB
    assert active == 27 * MLA_ATTN + DENSE_MLP + 26 * 8 * EXPERT \
        + ROUTERS + 2 * VOCAB == 2_914_648_064


def test_mla_cache_holds_the_latent_and_rotary_key():
    assert work.kv_bytes_per_token(MOONLIGHT) == 27 * (512 + 64) * 2 == 31_104


def test_mla_attention_flops_at_context():
    # QK^T over 16 heads of 128 + 64, PV over 16 heads of 128, per key
    grow = work.flops_per_token(MOONLIGHT, 100) - work.flops_per_token(
        MOONLIGHT, 0)
    assert grow == 27 * 100 * (2 * 16 * 192 + 2 * 16 * 128)


def test_mla_with_a_query_rank_counts_q_a_and_q_b():
    low = dict(MOONLIGHT, q_lora_rank=1536)
    assert _params(low) - _params(MOONLIGHT) == 27 * (
        1536 * 2048 + 16 * 192 * 1536 - 16 * 192 * 2048)
    assert work.base_bytes(low) - work.base_bytes(MOONLIGHT) == 27 * (
        1536 * 2048 + 16 * 192 * 1536 - 16 * 192 * 2048    # int8
        + 2 * (1536 + 3072 - 3072)                          # fp16 scales
        + 2 * 1536)                                         # q_a norm


def test_expert_share_counts_16_of_64():
    share = dict(MOONLIGHT, n_routed_experts=16, reduced=["n_routed_experts"],
                 published={"n_routed_experts": 64},
                 deployment={"expert_parallel": 4})
    assert _params(share) == 27 * MLA_ATTN + DENSE_MLP + 26 * (
        16 + 2) * EXPERT
    # this chip's expected part of the top 6: 6 x 16/64; routers at 64
    assert work.flops_per_token(share, 0) == 2 * (
        27 * MLA_ATTN + DENSE_MLP + 26 * (1.5 + 2) * EXPERT + ROUTERS + VOCAB)
    assert work.base_bytes(MOONLIGHT) - work.base_bytes(share) == 26 * 48 * (
        EXPERT + 2 * (1408 + 1408 + 2048))
    # one chip of a 4-chip expert-parallel deployment: 5.84 GB of int8
    # base, 2.86 GB for 5 bank slots, 0.76 GB for 6 lanes of 4096
    assert round(work.base_bytes(share) / 1e9, 2) == 5.84
    assert round(5 * work.slot_bytes(share) / 1e9, 2) == 2.86
    assert round(6 * 4096 * work.kv_bytes_per_token(share) / 1e9, 2) == 0.76


@pytest.mark.parametrize("key, value", [
    ("sliding_window", 4096), ("moe_layer_freq", 2), ("hidden_act", "gelu"),
    ("attention_bias", True), ("num_nextn_predict_layers", 1),
    ("linear_attn_heads", 8)])
def test_work_refuses_a_key_it_does_not_count(key, value):
    with pytest.raises(ValueError, match=key):
        work.flops_per_token(dict(MINI, **{key: value}), 1)
