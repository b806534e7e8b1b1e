"""A configuration file reaches the program whole: every key is handed to
``ModelConfig`` or refused by name before a weight is made, and leaves get
their roles from a table the file can extend."""
import copy
import dataclasses
import hashlib
import json

import jax
import numpy as np
import pytest

from bench import system
from bench import weights as W
from bench.spec import BENCH
from bench.tests.test_harness import CELL, SEED, tiny_cell


def _conf(name="deepseek-7b.int8"):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _tiny():
    return copy.deepcopy(tiny_cell(CELL, "deepseek-7b.int8",
                                   "mixed.open").config)


def _tiny_moe(**kw):
    """The tiny dense file with a first dense layer and 8 routed experts."""
    conf = _tiny()
    conf["program"] = {"arch": "deepseek-moe-16b"}
    conf.update(first_k_dense_replace=1, n_routed_experts=8,
                n_shared_experts=1, num_experts_per_tok=2,
                moe_intermediate_size=32, norm_topk_prob=True)
    conf.update(kw)
    return conf


def _refused(conf) -> set:
    """The keys ``program_config`` refuses, one per line of its error."""
    with pytest.raises(ValueError) as e:
        system.program_config(conf)
    lines = str(e.value).splitlines()[1:]
    return {line.split()[0].rstrip(":") for line in lines}


def test_the_one_cell_builds_the_config_it_built_before():
    from repro.configs.base import ModelConfig
    assert system.program_config(_conf()) == ModelConfig(
        name="deepseek-7b", family="dense", num_layers=30, d_model=4096,
        num_heads=32, num_kv_heads=32, d_ff=11008, vocab_size=102400,
        head_dim=128, max_seq_len=4096, rope_theta=10000.0, qk_norm=False,
        sliding_window=0, local_global_pattern=0, rope_theta_local=10000.0,
        attn_logit_softcap=0.0, num_experts=0, num_shared_experts=0,
        top_k=0, moe_first_dense=0, moe_d_ff=0, capacity_factor=1.25,
        router_aux_weight=0.01, ssm_state=0, ssm_heads=0, ssm_conv=4,
        mlstm_ratio=0, attn_every=0, concat_embed=False, encoder_layers=0,
        encoder_frames=0, cross_attention=False, num_image_tokens=0,
        param_dtype="float32", compute_dtype="bfloat16", norm_eps=1e-06,
        tie_embeddings=False, remat=True, scan_layers=True)


def _digest(tree) -> str:
    h = hashlib.sha256()
    for p in sorted(tree):
        leaf = tree[p]
        for k, a in (sorted(leaf.items()) if isinstance(leaf, dict)
                     else [("", leaf)]):
            a = np.asarray(jax.device_get(a))
            h.update(f"{p}.{k}:{a.dtype}:{a.shape};".encode())
            h.update(a.tobytes())
    return h.hexdigest()


def test_tiny_weights_and_variants_are_the_bits_they_were():
    # digests of make_base and make_variant at this size and seed, as
    # recorded before leaves had roles
    from repro.models import build_model
    conf = _tiny()
    shapes = system.served_shapes(build_model(system.program_config(conf)))
    assert _digest(W.make_base(shapes, SEED)) == (
        "bfbdbfe5434b0850eb5051cb92c1fed9ffd2a796bb6fba0c1ebc89abaa4296e5")
    assert [_digest(W.make_variant(shapes, SEED, i)) for i in range(2)] == [
        "de1b46a791bb38d78412a5b89f87d3ac82e8188ff2291891bed56eb02acbc50e",
        "10cf017350a7a861d1e770b2d5fe4792cbb4fd5a0f23dd51c3cb8f18a843d330"]


def test_keys_at_the_values_the_program_implements_pass():
    conf = dict(_tiny(), **system.IMPLIED)
    assert system.program_config(conf) == system.program_config(_tiny())


def test_head_dim_is_taken_from_the_file_where_stated():
    assert system.program_config(dict(_tiny(), head_dim=32)).head_dim == 32
    assert system.program_config(_tiny()).head_dim == 16


def test_an_moe_file_reaches_the_program():
    conf = _tiny_moe()
    conf["deployment"]["routing"] = "dropless"
    cfg = system.program_config(conf)
    assert (cfg.num_experts, cfg.top_k, cfg.num_shared_experts,
            cfg.moe_d_ff, cfg.moe_first_dense) == (8, 2, 1, 32, 1)
    assert cfg.capacity_factor == 8 / 2 + 1


@pytest.mark.parametrize("extra, key", [
    ({"scoring_func": "sigmoid"}, "scoring_func"),
    ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"frobnicate": 3}, "frobnicate"),
    ({"d_model": 64}, "d_model"),
    ({"remat": False}, "remat"),
    ({"attention_bias": 0}, "attention_bias"),
], ids=["scoring_func_sigmoid", "rope_scaling_non_null", "unknown_key",
        "a_field_set_twice", "a_runtime_field", "a_number_for_a_boolean"])
def test_program_config_refuses_by_name(extra, key):
    assert _refused(dict(_tiny(), **extra)) == {key}


def test_program_config_refuses_norm_topk_prob_false():
    assert _refused(_tiny_moe(norm_topk_prob=False)) == {"norm_topk_prob"}
    conf = _tiny_moe()
    del conf["norm_topk_prob"]
    assert _refused(conf) == {"norm_topk_prob"}


def test_program_config_refuses_a_registry_field_the_file_leaves_out():
    conf = _tiny()
    conf["program"] = {"arch": "gemma3-12b"}
    assert "sliding_window" in _refused(conf)


def test_program_config_refuses_a_reduced_key_without_its_published_value():
    conf = _tiny()
    conf["reduced"] = ["num_hidden_layers"]
    assert _refused(conf) == {"num_hidden_layers"}
    conf["published"] = {"num_hidden_layers": 30, "vocab_size": 102400}
    assert _refused(conf) == {"vocab_size"}
    conf["published"] = {"num_hidden_layers": 30}
    assert system.program_config(conf).num_layers == 2


def _share(held=2, chips=4):
    conf = _tiny_moe(n_routed_experts=held)
    conf["reduced"] = ["n_routed_experts"]
    conf["published"] = {"n_routed_experts": 8}
    conf["deployment"]["expert_parallel"] = chips
    return conf


def test_program_config_refuses_an_expert_share_without_experts_held():
    assert _refused(_share()) == {"experts_held"}
    assert _refused(_share(chips=2)) == {"n_routed_experts", "experts_held"}
    conf = _tiny_moe()
    conf["deployment"]["expert_parallel"] = 4
    assert _refused(conf) == {"deployment.expert_parallel"}


def test_an_expert_share_reaches_a_program_with_experts_held(monkeypatch):
    import repro.configs
    from repro.configs import base

    @dataclasses.dataclass(frozen=True)
    class WithShare(base.ModelConfig):
        experts_held: int = 0

    registry = repro.configs.get_config
    monkeypatch.setattr(base, "ModelConfig", WithShare)
    monkeypatch.setattr(repro.configs, "get_config", lambda arch: WithShare(
        **dataclasses.asdict(registry(arch))))
    cfg = system.program_config(_share())
    assert (cfg.num_experts, cfg.experts_held) == (8, 2)


def test_a_refused_file_makes_no_weight(monkeypatch):
    def made(*a, **k):
        raise AssertionError("a weight was made")
    monkeypatch.setattr(W, "make_base", made)
    with pytest.raises(ValueError, match="scoring_func = 'sigmoid'"):
        system.System(dict(_tiny(), scoring_func="sigmoid"), SEED)


SHAPES = {"embed": (256, 64), "final_norm": (64,), "unembed": (256, 64),
          "layers.ln1": (2, 64), "layers.attn.wq": (2, 64, 64)}


def test_leaf_roles_add_a_target_and_a_bias_leaf():
    shapes = dict(SHAPES, **{"layers.attn.wkv_a": (2, 40, 64),
                             "layers.moe.e_bias": (2, 8)})
    roles = {"wkv_a": "target", "e_bias": "bias"}
    base = W.make_base(shapes, SEED, roles)
    assert base["layers.attn.wkv_a"]["q"].dtype == np.int8
    assert base["layers.attn.wkv_a"]["scale"].shape == (2, 40)
    bias = np.asarray(base["layers.moe.e_bias"])
    assert bias.dtype == np.float32 and 0.05 < bias.std() < 0.2
    assert W.variant_paths(shapes, roles) == ["layers.attn.wkv_a",
                                              "layers.attn.wq"]
    variant = W.make_variant(shapes, SEED, 0, roles)
    assert variant["layers.attn.wkv_a"]["packed"].shape == (2, 40, 8)


@pytest.mark.parametrize("extra, roles, what", [
    ({"layers.attn.wkv_b": (2, 64, 32)}, None, "wkv_b"),
    ({}, {"wq": "norm"}, "wq"),
    ({}, {"kv_norm": "scale"}, "kv_norm"),
    ({"layers.attn.w_odd": (2, 64, 12)}, {"w_odd": "target"}, "w_odd"),
], ids=["a_leaf_with_no_role", "a_default_name_reassigned", "an_unknown_role",
        "a_target_that_does_not_pack"])
def test_leaf_roles_refuse_by_name(extra, roles, what):
    with pytest.raises(ValueError, match=what):
        W.make_base(dict(SHAPES, **extra), SEED, roles)
