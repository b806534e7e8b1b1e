"""Logical-axis sharding: rules, divisibility fallback, constraint helper.

Models annotate parameters and activations with *logical* axis names
(models/param.py docstring lists the vocabulary).  This module maps them to
mesh axes:

* every logical axis has an ordered candidate list of mesh axes (or axis
  tuples); the first candidate whose size divides the dimension and whose
  mesh axes are still unused in this spec wins — this is the divisibility
  fallback that lets e.g. starcoder2's 2 KV heads fall through to a
  head_dim shard instead of failing to lower;
* rule sets differ per workload (train / prefill / decode / long-context
  decode) — long_500k swaps batch-sharding for sequence-sharding of the KV
  cache (DESIGN.md §5);
* ``activation_rules`` are applied inside model code through
  :func:`logical_constraint`, which is a no-op outside an active mesh
  context, so smoke tests on CPU run the same code paths unsharded.
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Candidate = Union[str, tuple]

# ---------------------------------------------------------------------------
# rule sets
# ---------------------------------------------------------------------------

# Parameters: TP on the natural axis + FSDP over data on the other axis.
PARAM_RULES = {
    "vocab": ["model"],
    "embed": ["data"],
    "ffn": ["model"],
    "ffn_small": [],          # replicated over model (tiny shared experts)
    "q_heads": ["model"],
    "kv_heads": ["model"],
    "experts": ["model"],
    "ssm": ["model"],
    "conv": [],
    "layers": [],
    # overlay-bank slot axis (models/delta_overlay.py): replicated — every
    # device holds all bank slots of its own weight shard, so per-row slot
    # gathers in the banked delta GEMM stay device-local and bank admission
    # needs no collectives (DESIGN.md §11).  Pod-local banks
    # (rules_for(..., pod_banks=True)) shard this axis over "pod" instead:
    # each pod holds only its own slot range and admission scatters touch
    # one pod's devices (DESIGN.md §17)
    "bank": [],
}

# Pod-local overlay banks (DESIGN.md §17): the bank axis shards over the
# pod axis — slot p*S..(p+1)*S-1 lives only on pod p's devices, so an
# admission scatter writes one pod's shard and crosses no pod boundary.
# resolve_spec's divisibility fallback makes this degrade to replicated
# on meshes without a "pod" axis (single-pod serving, tier-1 CPU runs).
BANK_RULE_POD = ["pod"]

# Pure tensor-parallel params (serving: no FSDP; weights replicated over
# data so decode GEMVs need no weight all-gathers).
PARAM_RULES_SERVE = {**PARAM_RULES, "embed": []}


def _act_rules(seq_sharded: bool) -> dict:
    return {
        "act_batch": [] if seq_sharded else [("pod", "data"), "data"],
        "act_seq": [("pod", "data"), "data"] if seq_sharded else [],
        "act_seq_tp": ["model"],    # context-parallel attention (heads < TP)
        "act_embed": [],
        "act_heads": ["model"],
        "act_kv": ["model"],
        "act_hd": ["model"],        # fallback target when head counts don't divide
        "act_ffn": ["model"],
        "act_vocab": ["model"],
        "act_experts": ["model"],
        "act_groups": [("pod", "data"), "data"],
        "act_ssm": ["model"],
    }

ACT_RULES_TRAIN = _act_rules(seq_sharded=False)
ACT_RULES_DECODE = _act_rules(seq_sharded=False)
ACT_RULES_LONG = _act_rules(seq_sharded=True)


def rules_for(kind: str, long_context: bool = False,
              pod_banks: bool = False) -> dict:
    """(param_rules, act_rules) merged dict for a workload kind.

    "_forward_only" marks gradient-free workloads: sequence-TP attention
    is safe there (its backward pathology — per-chunk KV re-gathers — can't
    occur), and it beats flat-q sharding for indivisible head counts.

    ``pod_banks`` (serving kinds only) swaps the overlay-bank slot rule
    from replicated to pod-sharded (DESIGN.md §17): every consumer of the
    rule set — bank allocation, engine in_shardings, the shard_map kernel
    dispatch — then agrees that slot s lives on pod s // slots_per_pod."""
    if kind == "train":
        return {**PARAM_RULES, **ACT_RULES_TRAIN}
    if kind in ("prefill", "decode"):
        act = ACT_RULES_LONG if long_context else ACT_RULES_DECODE
        rules = {**PARAM_RULES_SERVE, **act, "_forward_only": True}
        if pod_banks:
            rules["bank"] = BANK_RULE_POD
        return rules
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# spec resolution with divisibility fallback
# ---------------------------------------------------------------------------

def _axis_size(mesh: Mesh, name: str) -> Optional[int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name)


def resolve_spec(shape: Sequence[int], axes: Sequence[Optional[str]],
                 rules: dict, mesh: Mesh) -> P:
    """Map logical axes of one array to a PartitionSpec."""
    parts, used = [], set()
    for dim, ax in zip(shape, axes):
        if ax is None:
            parts.append(None)
            continue
        cands: Sequence[Candidate] = rules.get(ax, [])
        chosen = None
        for cand in cands:
            names = cand if isinstance(cand, tuple) else (cand,)
            sizes = [_axis_size(mesh, n) for n in names]
            if any(s is None for s in sizes):        # axis absent (single-pod)
                continue
            if any(n in used for n in names):
                continue
            if dim % math.prod(sizes) == 0:
                chosen = names
                used.update(names)
                break
        if chosen is None:
            parts.append(None)
        else:
            parts.append(chosen if len(chosen) > 1 else chosen[0])
    return P(*parts)


def _axes_leaf(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None)))
                                        for e in x)


def tree_shardings(tree_shapes, tree_axes, rules: dict, mesh: Mesh):
    """Shape-tree (arrays or ShapeDtypeStructs) + logical-axes tree ->
    NamedSharding tree.

    Mapped over the *axes* tree (axis tuples are pytree nodes, so they must
    drive the flattening) with the shape tree as the second operand.
    """
    def one(axes, x):
        return NamedSharding(mesh, resolve_spec(x.shape, axes, rules, mesh))
    return jax.tree.map(one, tree_axes, tree_shapes, is_leaf=_axes_leaf)


def tree_pspecs(tree_shapes, tree_axes, rules: dict, mesh: Mesh):
    """Same as tree_shardings but returns raw PartitionSpecs."""
    def one(axes, x):
        return resolve_spec(x.shape, axes, rules, mesh)
    return jax.tree.map(one, tree_axes, tree_shapes, is_leaf=_axes_leaf)


# ---------------------------------------------------------------------------
# activation constraints (mesh context)
# ---------------------------------------------------------------------------

_ctx = threading.local()


@contextlib.contextmanager
def shard_ctx(mesh: Mesh, rules: dict):
    """Activate a mesh + rule set so model-internal ``logical_constraint``
    calls become with_sharding_constraint; no-op otherwise."""
    prev = getattr(_ctx, "state", None)
    _ctx.state = (mesh, rules)
    try:
        yield
    finally:
        _ctx.state = prev


def active_mesh() -> Optional[Mesh]:
    st = getattr(_ctx, "state", None)
    return st[0] if st else None


def active_rules() -> Optional[dict]:
    """Rule set of the active shard_ctx (None when inactive).  The kernel
    dispatch layer (kernels/dispatch.py) reads the pair (active_mesh,
    active_rules) at trace time to decide whether a delta GEMM lowers as a
    per-shard shard_map'd kernel or stays on the global GSPMD path."""
    st = getattr(_ctx, "state", None)
    return st[1] if st else None


def ctx_axis_size(name: str) -> Optional[int]:
    """Size of a mesh axis in the active context (None when inactive or the
    axis is absent).  Lets model code pick sharding strategy by
    divisibility (e.g. head-TP vs sequence-TP attention)."""
    mesh = active_mesh()
    if mesh is None:
        return None
    return _axis_size(mesh, name)


def ctx_forward_only() -> bool:
    """True inside a serving (gradient-free) rules context."""
    st = getattr(_ctx, "state", None)
    return bool(st and st[1].get("_forward_only"))


def logical_constraint(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    st = getattr(_ctx, "state", None)
    if st is None:
        return x
    mesh, rules = st
    spec = resolve_spec(x.shape, axes, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def local_top_k(score: jax.Array, k: int, axes: Sequence[Optional[str]]
                ) -> tuple:
    """top_k over the last dim, forced shard-local via shard_map.

    XLA's sort partitioner all-gathers the operand even when the sort dim
    is unsharded (measured ~50 GB/step on the MoE train cell); wrapping in
    shard_map keeps each shard's top_k local.  ``axes`` are the logical
    axes of ``score`` (last must be None).  No-op outside a mesh context.
    """
    st = getattr(_ctx, "state", None)
    if st is None:
        return jax.lax.top_k(score, k)
    mesh, rules = st
    spec = resolve_spec(score.shape, axes, rules, mesh)
    out_spec = P(*(list(spec)[:-1] + [None]))
    from jax import shard_map
    return shard_map(lambda s: tuple(jax.lax.top_k(s, k)), mesh=mesh,
                     in_specs=(spec,), out_specs=(out_spec, out_spec),
                     check_vma=False)(score)
