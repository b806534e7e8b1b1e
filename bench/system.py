"""The system under test: a ``Deployment`` built from a configuration file.

This is the one file that knows the program's entry points: its model
registry and ``ModelConfig``, its parameter tree and ``QuantWeight``, the
``DeltaModel`` a variant is published as, and ``Deployment`` itself.  A
configuration states the published sizes under their published names; the
table below maps each to the program's field.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from bench import weights as W

PUBLISHED_TO_PROGRAM = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "max_position_embeddings": "max_seq_len",
    "moe_intermediate_size": "moe_d_ff", "n_routed_experts": "num_experts",
    "n_shared_experts": "num_shared_experts",
    "num_experts_per_tok": "top_k",
    "first_k_dense_replace": "moe_first_dense",
    "tie_word_embeddings": "tie_embeddings",
}


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs import get_config
    cfg = get_config(conf["program"]["arch"])
    kw = {field: conf[key] for key, field in PUBLISHED_TO_PROGRAM.items()
          if key in conf}
    kw["head_dim"] = conf["hidden_size"] // conf["num_attention_heads"]
    if "n_routed_experts" in conf:
        if not conf.get("norm_topk_prob", False):
            raise ValueError("the program renormalizes the top-k gates; "
                             "a configuration it serves states "
                             "norm_topk_prob true")
        if conf["deployment"].get("routing") == "dropless":
            # capacity = group size: n·k/E·cf >= n
            kw["capacity_factor"] = (conf["n_routed_experts"]
                                     / conf["num_experts_per_tok"] + 1)
    return dataclasses.replace(cfg, **kw)


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
        self.weights = W.make_base(shapes, seed)
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
            self.variants[name] = W.make_variant(shapes, seed, i)
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
