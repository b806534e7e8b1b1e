"""Operations and bytes the algorithm needs, counted from shapes.

Everything here reads the configuration file's published sizes; nothing
comes from the program.  The per-matrix byte arithmetic follows
``launch/build.memory_plan``: an int8 target is its payload plus one fp16
scale per output channel, a bank slot of one target is its packed sign
plane (one bit per weight) plus fp16 ``v_row`` and ``v_col``.

Attention is what the file's keys say: latent attention (MLA) where it
states ``kv_lora_rank``, else heads of ``head_dim`` (hidden / heads where
not stated) with ``num_key_value_heads`` shared keys and values.  Under
an expert share (``spec.py``) the routed experts are those this chip
holds, and the router keeps its published width.  A key whose effect on
the counts is not modelled here is refused by name.
"""
from __future__ import annotations

from bench.spec import HARNESS_KEYS

PACK = 8

# keys whose value enters a count below
COUNTED = frozenset({
    "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "vocab_size",
    "tie_word_embeddings", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "moe_intermediate_size", "first_k_dense_replace",
    "topk_method", "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim"})
# keys that change no count, whatever their value
UNCOUNTED = frozenset({
    "rms_norm_eps", "rope_theta", "rope_scaling", "max_position_embeddings",
    "scoring_func", "routed_scaling_factor", "norm_topk_prob", "n_group",
    "topk_group", "ep_size", "seq_aux"})
# keys counted at these values only: a gated MLP, no biases, an MoE block
# in every layer after the dense ones, no multi-token head, full attention
COUNTED_AT = {"hidden_act": ("silu",), "attention_bias": (False,),
              "moe_layer_freq": (1,), "num_nextn_predict_layers": (0,),
              "sliding_window": (None, 0)}


def _refuse_uncounted(conf: dict) -> None:
    for key, value in conf.items():
        if key in HARNESS_KEYS or key in COUNTED or key in UNCOUNTED:
            continue
        if not any(isinstance(value, bool) == isinstance(v, bool)
                   and value == v for v in COUNTED_AT.get(key, ())):
            raise ValueError(f"{key} = {value!r}: bench/work.py does not "
                             "count its effect")


def dims(conf: dict) -> dict:
    """Published sizes under short names."""
    _refuse_uncounted(conf)
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    hd = conf.get("head_dim") or d // h
    kvh = conf.get("num_key_value_heads", h)
    n_layers = conf["num_hidden_layers"]
    moe = "n_routed_experts" in conf
    n_dense = conf.get("first_k_dense_replace", 0) if moe else n_layers
    experts = conf.get("n_routed_experts", 0)
    mla = None
    if conf.get("kv_lora_rank") is not None:
        mla = {"kv_rank": conf["kv_lora_rank"],
               "q_rank": conf.get("q_lora_rank") or 0,
               "nope": conf["qk_nope_head_dim"],
               "rope": conf["qk_rope_head_dim"], "v": conf["v_head_dim"]}
    return {"d": d, "heads": h, "hd": hd, "kv_heads": kvh,
            "q_dim": h * hd, "kv_dim": kvh * hd, "mla": mla,
            "ff": conf["intermediate_size"], "vocab": conf["vocab_size"],
            "vocab_matrices": 1 if conf.get("tie_word_embeddings") else 2,
            "layers": n_layers, "dense_layers": n_dense,
            "moe_layers": n_layers - n_dense if moe else 0,
            "experts": experts,
            "router_width": conf.get("published", {}).get(
                "n_routed_experts", experts),
            "selection_bias": conf.get("topk_method") == "noaux_tc",
            "shared": conf.get("n_shared_experts", 0),
            "top_k": conf.get("num_experts_per_tok", 0),
            "expert_ff": conf.get("moe_intermediate_size", 0)}


def _attention(m: dict) -> tuple:
    """(projections, qk, v) of one layer: its projections as (N, K), and
    the widths of the query-key and value products per key attended."""
    d, h, a = m["d"], m["heads"], m["mla"]
    if a is None:
        return ([(m["q_dim"], d), (m["kv_dim"], d), (m["kv_dim"], d),
                 (d, m["q_dim"])], m["q_dim"], m["q_dim"])
    qk = h * (a["nope"] + a["rope"])
    q = ([(a["q_rank"], d), (qk, a["q_rank"])] if a["q_rank"]
         else [(qk, d)])
    return (q + [(a["kv_rank"] + a["rope"], d),
                 (h * (a["nope"] + a["v"]), a["kv_rank"]),
                 (d, h * a["v"])], qk, h * a["v"])


def targets(conf: dict) -> list:
    """Every delta-target matrix of one forward, as (count, N, K): the
    attention and MLP projections of every layer (the experts held and the
    shared experts of an MoE layer included)."""
    m = dims(conf)
    d, L = m["d"], m["layers"]
    attn = [(L, n, k) for n, k in _attention(m)[0]]
    mlp = [(m["dense_layers"], m["ff"], d)] * 2 + [(m["dense_layers"], d,
                                                    m["ff"])]
    out = attn + [t for t in mlp if t[0]]
    if m["moe_layers"]:
        e, f = m["experts"], m["expert_ff"]
        sf = m["shared"] * f
        n = m["moe_layers"]
        out += [(n * e, f, d), (n * e, f, d), (n * e, d, f)]
        if sf:
            out += [(n, sf, d), (n, sf, d), (n, d, sf)]
    return out


def slot_bytes(conf: dict) -> int:
    """One bank slot: packed planes plus fp16 v_row and v_col of every
    target."""
    return sum(c * (n * k // PACK + 2 * (n + k)) for c, n, k in
               targets(conf))


def base_bytes(conf: dict) -> int:
    """The int8 base: targets as int8 + fp16 per-channel scales; the
    embedding, unembedding, norms (with MLA's latent norms) and routers in
    bf16, a router's selection bias in float32."""
    m = dims(conf)
    tgt = sum(c * (n * k + 2 * n) for c, n, k in targets(conf))
    emb = m["vocab_matrices"] * m["vocab"] * m["d"] * 2
    latent = m["mla"]["kv_rank"] + m["mla"]["q_rank"] if m["mla"] else 0
    norms = ((2 * m["layers"] + 1) * m["d"] + m["layers"] * latent) * 2
    router = m["moe_layers"] * m["router_width"] * m["d"] * 2
    bias = m["moe_layers"] * m["router_width"] * 4 * m["selection_bias"]
    return tgt + emb + norms + router + bias


def kv_bytes_per_token(conf: dict) -> int:
    """bf16 K and V of every layer plus the int32 slot position; under
    MLA, the bf16 latent and the rotary key of every layer."""
    m = dims(conf)
    if m["mla"]:
        return m["layers"] * (m["mla"]["kv_rank"] + m["mla"]["rope"]) * 2
    return m["layers"] * (2 * m["kv_dim"] * 2 + 4)


# ---------------------------------------------------------------------------
# the sign-delta GEMM of one decode step
# ---------------------------------------------------------------------------

def sign_gemm_decode(conf: dict, rows_per_slot: dict) -> tuple:
    """(flops, bytes) the sign-delta GEMMs of one decode step need.

    ``rows_per_slot`` maps each NON-base variant used by the step's active
    lanes to its number of rows (one per lane).  Per target matrix (N, K)
    and used variant: its packed plane (N·K/8 bytes), the selected fp16
    vector (counted at the shorter of N and K, so the count never
    overstates), the variant's rows of x in bf16 and of the fp32 output,
    and one GEMM of 2·rows·N·K.  Base rows need no sign work: a step with
    no variant row needs nothing."""
    flops = byts = 0
    for count, n, k in targets(conf):
        for rows in rows_per_slot.values():
            flops += count * 2 * rows * n * k
            byts += count * (n * k // PACK + 2 * min(n, k)
                             + rows * (2 * k + 4 * n))
    return flops, byts


def roofline_seconds(flops: float, byts: float, peak: dict) -> tuple:
    """Least time on the chip and which bound sets it."""
    t_flops = flops / peak["bf16_flops"]
    t_bytes = byts / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("flops" if t_flops > t_bytes
                                   else "bytes")


# ---------------------------------------------------------------------------
# model FLOPs per token
# ---------------------------------------------------------------------------

def flops_per_token(conf: dict, ctx: int) -> float:
    """Dense-equivalent matmul FLOPs of one token at context ``ctx`` (the
    keys it attends to, itself included): every projection it uses, the
    router at its published width, the top-k routed experts (under an
    expert share, this chip's expected part of them: top_k·held/published)
    plus the shared experts of an MoE layer, the unembedding, and
    attention's QKᵀ and PV at that context."""
    m = dims(conf)
    d = m["d"]
    proj, qk, v = _attention(m)
    attn_proj = sum(n * k for n, k in proj)
    attn_ctx = ctx * (qk + v)
    dense_mlp = 3 * d * m["ff"]
    per_layer = 2 * (attn_proj + attn_ctx)
    total = m["layers"] * per_layer + m["dense_layers"] * 2 * dense_mlp
    if m["moe_layers"]:
        routed = m["top_k"] * m["experts"] / m["router_width"]
        experts = (routed + m["shared"]) * 3 * d * m["expert_ff"]
        total += m["moe_layers"] * 2 * (experts + m["router_width"] * d)
    return float(total + 2 * d * m["vocab"])


def prompt_flops(conf: dict, prompt_len: int) -> float:
    """A whole prompt through the model: position p attends p + 1 keys."""
    return float(sum(flops_per_token(conf, p + 1)
                     for p in range(prompt_len)))


def params_bytes_summary(conf: dict, bank_size: int, kv_tokens: int) -> dict:
    """Device bytes of a deployment, from shapes (no activations)."""
    b, s, kv = base_bytes(conf), slot_bytes(conf), kv_bytes_per_token(conf)
    return {"base": b, "slot": s, "kv_per_token": kv,
            "total": b + bank_size * s + kv_tokens * kv}

