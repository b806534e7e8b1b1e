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
