"""Operations and bytes the algorithm needs, counted from shapes.

Everything here reads the configuration file's published sizes; nothing
comes from the program.  The per-matrix byte arithmetic follows
``launch/build.memory_plan``: an int8 target is its payload plus one fp16
scale per output channel, a bank slot of one target is its packed sign
plane (one bit per weight) plus fp16 ``v_row`` and ``v_col``.
"""
from __future__ import annotations

PACK = 8


def dims(conf: dict) -> dict:
    """Published sizes under short names."""
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    hd = conf.get("head_dim", d // h)
    kvh = conf.get("num_key_value_heads", h)
    n_layers = conf["num_hidden_layers"]
    moe = "n_routed_experts" in conf
    n_dense = conf.get("first_k_dense_replace", 0) if moe else n_layers
    return {"d": d, "heads": h, "hd": hd, "kv_heads": kvh,
            "q_dim": h * hd, "kv_dim": kvh * hd,
            "ff": conf["intermediate_size"], "vocab": conf["vocab_size"],
            "layers": n_layers, "dense_layers": n_dense,
            "moe_layers": n_layers - n_dense if moe else 0,
            "experts": conf.get("n_routed_experts", 0),
            "shared": conf.get("n_shared_experts", 0),
            "top_k": conf.get("num_experts_per_tok", 0),
            "expert_ff": conf.get("moe_intermediate_size", 0)}


def targets(conf: dict) -> list:
    """Every delta-target matrix of one forward, as (count, N, K): the
    attention and MLP projections of every layer (experts and shared
    experts of an MoE layer included)."""
    m = dims(conf)
    d, L = m["d"], m["layers"]
    attn = [(L, m["q_dim"], d), (L, m["kv_dim"], d), (L, m["kv_dim"], d),
            (L, d, m["q_dim"])]
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
    embedding, unembedding, norms and routers in bf16."""
    m = dims(conf)
    tgt = sum(c * (n * k + 2 * n) for c, n, k in targets(conf))
    emb = 2 * m["vocab"] * m["d"] * 2
    norms = (2 * m["layers"] + 1) * m["d"] * 2
    router = m["moe_layers"] * m["experts"] * m["d"] * 2
    return tgt + emb + norms + router


def kv_bytes_per_token(conf: dict) -> int:
    """bf16 K and V of every layer plus the int32 slot position."""
    m = dims(conf)
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
    router, the top-k routed plus the shared experts of an MoE layer, the
    unembedding, and attention's QKᵀ and PV at that context."""
    m = dims(conf)
    d = m["d"]
    attn_proj = d * (2 * m["q_dim"] + 2 * m["kv_dim"])
    attn_ctx = 2 * ctx * m["q_dim"]
    dense_mlp = 3 * d * m["ff"]
    per_layer = 2 * (attn_proj + attn_ctx)
    total = m["layers"] * per_layer + m["dense_layers"] * 2 * dense_mlp
    if m["moe_layers"]:
        routed = (m["top_k"] + m["shared"]) * 3 * d * m["expert_ff"]
        total += m["moe_layers"] * 2 * (routed + m["experts"] * d)
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

