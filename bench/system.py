"""The system under test: a ``Deployment`` built from a configuration file.

This is the one file that knows the program's entry points: its model
registry and ``ModelConfig``, its parameter tree and ``QuantWeight``, the
``DeltaModel`` a variant is published as, and ``Deployment`` itself.  A
configuration states its architecture under the published names, and
``program_config`` hands every key to the program or refuses it by name.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from bench import weights as W
from bench.spec import HARNESS_KEYS

PUBLISHED_TO_PROGRAM = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len",
    "moe_intermediate_size": "moe_d_ff", "n_routed_experts": "num_experts",
    "n_shared_experts": "num_shared_experts",
    "num_experts_per_tok": "top_k",
    "first_k_dense_replace": "moe_first_dense",
    "tie_word_embeddings": "tie_embeddings",
}

# published keys the program implements at one value, without a field:
# SwiGLU, no attention bias, softmax gates renormalized over the top-k,
# plain top-k over ungrouped experts, an MoE block in every layer after the
# dense ones, full-rank queries, unscaled RoPE, no multi-token head, and a
# load-balancing loss over the batch (models/moe.py)
IMPLIED = {
    "hidden_act": "silu", "attention_bias": False, "norm_topk_prob": True,
    "scoring_func": "softmax", "topk_method": "greedy",
    "routed_scaling_factor": 1, "n_group": 1, "topk_group": 1,
    "moe_layer_freq": 1, "q_lora_rank": None, "rope_scaling": None,
    "num_nextn_predict_layers": 0, "ep_size": 1, "seq_aux": False,
}

# fields of ModelConfig that say how the program runs, not what it computes
RUNTIME_FIELDS = frozenset({"name", "family", "remat", "scan_layers",
                            "param_dtype", "compute_dtype",
                            "capacity_factor", "router_aux_weight"})


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file.

    Every architecture key of the file reaches it (``spec.py``), and the
    registry entry ``program.arch`` gives only the runtime fields.  Raises
    ``ValueError`` naming every key refused, one a line, before anything
    is built."""
    from repro.configs import get_config
    from repro.configs.base import ModelConfig
    fields = {f.name: f for f in dataclasses.fields(ModelConfig)}
    refused, kw = [], {}
    for key, value in conf.items():
        if key in HARNESS_KEYS:
            continue
        field = PUBLISHED_TO_PROGRAM.get(key, key)
        if field in fields and field not in RUNTIME_FIELDS:
            if field in kw:
                refused.append(f"{key} = {value!r}: sets {field}, which "
                               "another key of the file sets")
            kw[field] = value
        elif key not in IMPLIED:
            refused.append(f"{key} = {value!r}: no architecture field of "
                           "the program's ModelConfig takes it")
        elif not _same(value, IMPLIED[key]):
            refused.append(f"{key} = {value!r}: the program implements "
                           f"{IMPLIED[key]!r} only")
    if kw.get("head_dim") is None:
        kw["head_dim"] = conf["hidden_size"] // conf["num_attention_heads"]

    reduced, published = set(conf.get("reduced", [])), conf.get(
        "published", {})
    refused += [f"{k}: in reduced, with no published value"
                for k in sorted(reduced - set(published))]
    refused += [f"{k}: in published, not in reduced"
                for k in sorted(set(published) - reduced)]
    share = int(conf["deployment"].get("expert_parallel", 1))
    if "n_routed_experts" in published:
        # this chip holds experts [0, held) of the router's published width
        held, width = conf["n_routed_experts"], published["n_routed_experts"]
        if held * share != width:
            refused.append(f"n_routed_experts = {held}: not the share of "
                           f"{width} published experts that each of "
                           f"deployment.expert_parallel = {share} chips holds")
        kw["num_experts"] = width
        if "experts_held" in fields:
            kw["experts_held"] = held
        else:
            refused.append(f"experts_held = {held}: an expert share needs "
                           "this ModelConfig field")
    elif share != 1:
        refused.append(f"deployment.expert_parallel = {share}: an expert "
                       "share lists n_routed_experts in reduced")
    if "n_routed_experts" in conf:
        if "norm_topk_prob" not in conf:
            refused.append("norm_topk_prob: an MoE configuration states it")
        if conf["deployment"].get("routing") == "dropless" and "top_k" in kw:
            # capacity = group size: n·k/E·cf >= n
            kw["capacity_factor"] = kw["num_experts"] / kw["top_k"] + 1

    arch = conf["program"]["arch"]
    registry = get_config(arch)
    refused += [f"{f.name} = {getattr(registry, f.name)!r}: set by the "
                f"registry entry {arch!r}, not stated in the file"
                for f in fields.values()
                if f.name not in RUNTIME_FIELDS and f.name not in kw
                and getattr(registry, f.name) != f.default]
    if refused:
        raise ValueError(f"configuration {conf['name']!r} refused:\n  "
                         + "\n  ".join(refused))
    return dataclasses.replace(registry, **kw)


def _same(value, implied) -> bool:
    """Equal, with booleans kept apart from numbers."""
    return (isinstance(value, bool) == isinstance(implied, bool)
            and value == implied)


def served_shapes(model) -> dict:
    """{dot-path -> shape} of every leaf the served model reads."""
    from repro.core.calibration import flatten_params
    from repro.models.param import split
    abstract, _ = split(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    return {p: tuple(l.shape) for p, l in flatten_params(abstract).items()}


def program_params(model, flat: dict):
    """The benchmark's flat weights as the program's parameter tree."""
    from repro.core.calibration import unflatten_like
    from repro.core.quantize import QuantWeight
    from repro.models.param import split
    abstract, _ = split(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    leaves = {p: (QuantWeight(q=w["q"], scale=w["scale"])
                  if isinstance(w, dict) else w) for p, w in flat.items()}
    return unflatten_like(abstract, leaves)


def delta_model(variant: dict):
    """A benchmark-made variant as the program's publishable DeltaModel."""
    from repro.core.calibration import DeltaEntry, DeltaModel
    return DeltaModel(deltas={
        p: DeltaEntry(packed=e["packed"], v_row=e["v_row"],
                      v_col=e["v_col"], use_row=e["use_row"])
        for p, e in variant.items()}, extras={})


class System:
    """One configuration deployed from one seed: base made on the device,
    variants published into the overlay bank.  ``warm_traffic`` then
    compiles (or reads from the compile cache) every program the window
    runs.  The engine's own ``warmup`` is not used: its abstract bank
    gives every non-target leaf a banked copy (embedding and unembedding
    included), which is not the bank these variants fill and at
    deepseek-7b's width does not fit the chip."""

    def __init__(self, conf: dict, seed: int, log=print):
        from repro.models import build_model
        from repro.serving import Deployment
        dep_conf = conf["deployment"]
        self.conf = conf
        self.lanes = int(dep_conf["lanes"])
        self.prompt_len = int(dep_conf["prompt_len"])
        self.max_len = int(dep_conf["max_len"])
        self.model = build_model(program_config(conf))
        shapes = served_shapes(self.model)
        self.phases = {}
        t = _clock()
        roles = conf.get("leaf_roles")
        self.weights = W.make_base(shapes, seed, roles)
        jax.block_until_ready(self.weights)
        self.phases["base_s"] = _clock() - t
        t = _clock()
        self.dep = Deployment(
            self.model, program_params(self.model, self.weights),
            mode="fused", scheduler="continuous", batch_size=self.lanes,
            prompt_len=self.prompt_len, max_len=self.max_len,
            bank_size=int(dep_conf["bank_size"]), base_dtype="int8")
        self.phases["deployment_s"] = _clock() - t
        t = _clock()
        self.variants = {}
        for i in range(int(dep_conf["variants"])):
            name = f"v{i}"
            self.variants[name] = W.make_variant(shapes, seed, i, roles)
            self.dep.publish(name, delta_model(self.variants[name]),
                             wait=True)
        self.phases["variants_s"] = _clock() - t
        log(f"set-up: {self.phases}")

    @property
    def engine(self):
        return self.dep.engine

    def warm_traffic(self, vocab: int) -> None:
        """Two admission waves through the served path (the second merges
        into the running batch), so that the banked prefill and decode
        step, the merge and every eager op the window runs are compiled
        before it opens."""
        t = _clock()
        rng = np.random.default_rng(0)
        names = ["__base__"] + sorted(self.variants)
        for i in range(self.lanes + 1):
            self.dep.submit(rng.integers(1, vocab, self.prompt_len),
                            variant=names[i % len(names)],
                            max_new_tokens=2 if i < self.lanes else 3)
        self.dep.drain()
        self.phases["warm_traffic_s"] = _clock() - t

    def close(self) -> None:
        self.dep.close()
        del self.dep


def _clock() -> float:
    import time
    return time.perf_counter()
