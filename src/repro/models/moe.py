"""Mixture-of-Experts: fine-grained routed experts + shared experts.

Routing: group-limited capacity dispatch (GShard-style), formulated so that
GSPMD inserts the expert-parallel all-to-alls from sharding constraints:

1. tokens reshaped to (G, N, D) groups; G follows the batch sharding
   ("act_groups" → data axis), so routing decisions are shard-local;
2. per (group, expert) top-C token selection — C = N·top_k/E·capacity —
   gives static shapes (no sort over the global token stream);
3. the gathered dispatch tensor (G, E, C, D) is constraint-resharded with
   experts on the "model" axis (→ all-to-all), grouped-GEMM'd against the
   expert stacks, and scatter-added back.

Tokens overflowing an expert's capacity within their group are dropped
(standard capacity-factor semantics); the aux load-balancing loss keeps
overflow rare.  Shared experts (DeepSeek-MoE / Moonlight) run densely.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import logical_constraint
from repro.models.delta_overlay import oget
from repro.models.layers import linear
from repro.models.param import Param, dense_init


def moe_init(key, cfg) -> dict:
    d, e_ff, e = cfg.d_model, cfg.expert_d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (e, d), ("experts", "embed"), scale=0.02),
        "w_gate": dense_init(ks[1], (e, e_ff, d), ("experts", "ffn", "embed")),
        "w_up": dense_init(ks[2], (e, e_ff, d), ("experts", "ffn", "embed")),
        "w_down": dense_init(ks[3], (e, d, e_ff), ("experts", "embed", "ffn")),
    }
    if cfg.num_shared_experts:
        # shared experts are tiny (num_shared·e_ff hidden): REPLICATE them
        # over the model axis ("ffn_small" rule) — their full-residual TP
        # psums (one fwd + one bwd per layer) cost far more wire than the
        # replicated compute (≈0.04 s/step vs ≈2 s of collectives)
        sh_ff = cfg.expert_d_ff * cfg.num_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(k1, (sh_ff, d), ("ffn_small", "embed")),
            "w_up": dense_init(k2, (sh_ff, d), ("ffn_small", "embed")),
            "w_down": dense_init(k3, (d, sh_ff), ("embed", "ffn_small")),
        }
    return p


def _group_tokens(x: jax.Array, target_group: int = 4096
                  ) -> tuple[jax.Array, tuple]:
    """(B, S, D) -> (G, N, D); groups follow batch sharding when possible."""
    b, s, d = x.shape
    t = b * s
    n = min(target_group, t)
    while t % n:
        n -= 1
    g = t // n
    return x.reshape(g, n, d), (b, s, d)


def _expert_mm(xe: jax.Array, w: jax.Array, ent,
               waxes=("experts", "ffn", "embed")) -> jax.Array:
    """Per-expert matmul: xe (E, M, D) · w (E, F, D) -> (E, M, F).

    The base term is the shared ``ops.base_matmul`` per expert (an int8
    base factors its per-output-channel scales out exactly); with a
    delta-overlay entry (stacked over the expert dim) the sign-plane delta
    of each expert is added.  Inside an active mesh the delta lowers as
    ONE shard_map over the expert-sharded axis — shard_map(vmap(kernel)),
    each device running the kernels for its own experts — because vmap
    over a shard_map'd kernel is not a supported composition; a stack the
    dispatcher declines runs the global vmapped kernel and is counted."""
    from repro.kernels import dispatch as D
    from repro.kernels import ops as K
    base = jax.vmap(K.base_matmul)(xe, w)
    if ent is None:
        return base
    st = D.state()
    y = None
    if st is not None:
        y = D.sign_delta_stacked(st, xe, ent, waxes)
        if y is None:
            D.note_decline()
    if y is None:
        zeros = jnp.zeros(xe.shape[1:2], jnp.int32)
        y = jax.vmap(lambda x_, p_, r_, c_: K.sign_delta_local(
            x_, zeros, p_[None], r_[None], c_[None]))(
                xe, ent.packed, ent.v_row, ent.v_col)
    return base + y.astype(base.dtype)


def moe_apply(p: dict, x: jax.Array, cfg, ov=None, vidx=None
              ) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss).

    ``vidx`` (B,) enables mixed-variant batches over a BANKED overlay: the
    router (an uncompressed extra) is applied per token by masked select
    over the bank, and the grouped expert GEMMs fall back to masked
    per-variant application (DESIGN.md §9) — V fused passes with
    non-matching rows zeroed, jittable and exact per row.  Note capacity
    dispatch couples rows: a token's survival can depend on which other
    variants share its group, exactly as it depends on batch composition
    in single-variant serving.
    """
    b, s, _ = x.shape
    e, k = cfg.num_experts, cfg.top_k
    xg, orig = _group_tokens(x)
    g, n, d = xg.shape
    cap = max(1, int(n * k / e * cfg.capacity_factor))
    cap = min(cap, n)
    # per-token variant indices in group layout (tokens are row-major)
    vidx_gn = (None if vidx is None
               else jnp.broadcast_to(vidx[:, None], (b, s)).reshape(g, n))

    xg = logical_constraint(xg, "act_groups", None, None)
    rb = oget(ov, "router")
    if rb is None or vidx_gn is None:
        logits = (xg @ p["router"].T.astype(x.dtype)).astype(jnp.float32)
    else:
        # banked router: identical matmul per bank slot, rows select their
        # own variant's routing scores (slot 0 = base)
        logits = xg @ rb[0].T.astype(x.dtype)
        for vi in range(1, rb.shape[0]):
            lv = xg @ rb[vi].T.astype(x.dtype)
            logits = jnp.where((vidx_gn == vi)[..., None], lv, logits)
        logits = logits.astype(jnp.float32)                         # (G,N,E)
    probs = jax.nn.softmax(logits, axis=-1)

    # shard-local top_k: XLA's sort partitioning otherwise all-gathers the
    # full score tensors (measured ~50 GB/step on the moonshot train cell)
    from repro.distributed.sharding import local_top_k
    top_val, top_idx = local_top_k(probs, k, ("act_groups", None, None))
    top_val = top_val / jnp.maximum(top_val.sum(-1, keepdims=True), 1e-9)

    # score[g, e, n] = normalized gate prob if e in token n's top-k else 0
    sel = jax.nn.one_hot(top_idx, e, dtype=jnp.float32) * top_val[..., None]
    score = jnp.swapaxes(sel.sum(axis=2), 1, 2)                 # (G,E,N)

    c_val, c_idx = local_top_k(score, cap, ("act_groups", None, None))

    # dispatch gather: (G,E,C,D), experts resharded onto the model axis
    xd = jnp.take_along_axis(
        xg[:, None, :, :], c_idx[..., None], axis=2)            # (G,E,C,D)
    xd = logical_constraint(xd, "act_groups", "act_experts", None, None)

    # grouped expert GEMMs (gated SwiGLU), expert-major (E, G·C, ·): one
    # (M, K) GEMM per expert stack entry, with or without an overlay, so a
    # base row computes the same matmuls on every path
    xe = xd.transpose(1, 0, 2, 3).reshape(e, g * cap, d)
    has_delta = ov is not None and any(oget(ov, k_) is not None
                                       for k_ in ("w_gate", "w_up", "w_down"))
    if has_delta and vidx_gn is not None:
        # mixed-variant banked overlay: masked per-variant application —
        # banking the per-row gather inside the grouped (E, M, ·) GEMMs is
        # awkward (rows are dispatch slots, not batch lanes), so run the
        # per-variant pass once per bank slot with non-matching rows
        # zeroed and select (slot 0 = base weights)
        from repro.models.delta_overlay import entry_slot
        vd = jnp.take_along_axis(vidx_gn[:, None, :], c_idx, axis=2)  # (G,E,C)
        vidx_e = vd.transpose(1, 0, 2).reshape(e, g * cap)
        ents = {k_: oget(ov, k_) for k_ in ("w_gate", "w_up", "w_down")}
        nbank = next(v.packed.shape[0] for v in ents.values()
                     if v is not None)
        ye = jnp.zeros((e, g * cap, d), x.dtype)
        for vi in range(nbank):
            mask = (vidx_e == vi)[..., None]
            xv = jnp.where(mask, xe, 0)
            sl = {k_: entry_slot(v, vi) for k_, v in ents.items()}
            hv = (jax.nn.silu(_expert_mm(xv, p["w_gate"], sl["w_gate"]))
                  * _expert_mm(xv, p["w_up"], sl["w_up"]))
            yv = _expert_mm(hv, p["w_down"], sl["w_down"],
                            waxes=("experts", "embed", "ffn"))
            ye = jnp.where(mask, yv, ye)
    else:
        he = (jax.nn.silu(_expert_mm(xe, p["w_gate"], oget(ov, "w_gate")))
              * _expert_mm(xe, p["w_up"], oget(ov, "w_up")))
        ye = _expert_mm(he, p["w_down"], oget(ov, "w_down"),
                        waxes=("experts", "embed", "ffn"))
    yd = ye.reshape(e, g, cap, d).transpose(1, 0, 2, 3)
    yd = yd * c_val[..., None].astype(x.dtype)                  # combine weight
    # mask out capacity slots that hold zero-score (unrouted) tokens
    yd = jnp.where((c_val > 0)[..., None], yd, 0)
    yd = logical_constraint(yd, "act_groups", "act_experts", None, None)

    # combine scatter-add back to token order
    y = jnp.zeros((g, n, d), x.dtype)
    flat_idx = c_idx.reshape(g, e * cap)
    y = jax.vmap(lambda yt, it, vt: yt.at[it].add(vt))(
        y, flat_idx, yd.reshape(g, e * cap, d))
    y = logical_constraint(y, "act_groups", None, None)

    # shared experts: weights replicated over `model`, computed dense on
    # each rank's batch shard.  Measured alternatives (moonshot train):
    # TP-sharded = +2 full-residual psums/layer (bound 8.8 s); sequence-TP
    # = cheaper compute but gather/scatter wire dominates (bound 7.9 s);
    # replication wins on the dominant term (bound 6.1 s) despite 16×
    # redundant shared-expert FLOPs.
    if "shared" in p:
        from repro.models.layers import mlp_apply
        y = y + mlp_apply(p["shared"], xg, ov=oget(ov, "shared"),
                          vidx=vidx_gn, ffn_ax="ffn_small")

    # load-balancing aux loss (Switch-style): f_i · P_i summed over experts
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_idx, e, dtype=jnp.float32).sum(2), axis=(0, 1)) / k
    frac_probs = jnp.mean(probs, axis=(0, 1))               # (E,)
    aux = e * jnp.sum(frac_tokens * frac_probs)

    return y.reshape(orig), aux.astype(jnp.float32)
