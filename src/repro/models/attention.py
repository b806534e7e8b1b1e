"""Attention: GQA with chunked (flash-style) softmax, KV caches, decode.

Memory discipline: full (S×T) logits are never materialised for long
sequences — ``flash_attention`` scans over KV chunks with an online
softmax, so live memory is O(S·chunk).  Decode-time attention computes
(B,H,T) logits directly (tiny), and for sequence-sharded caches
(long_500k) relies on GSPMD turning the fp32 max/sum reductions over the
sharded T dim into the distributed two-pass flash-decode (pmax/psum)
schedule — see DESIGN.md §5.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, rmsnorm
from repro.models.param import Param, dense_init, ones_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attn_init(key, cfg) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d = cfg.d_model
    p = {
        "wq": dense_init(k1, (cfg.q_dim, d), ("q_heads", "embed")),
        "wk": dense_init(k2, (cfg.kv_dim, d), ("kv_heads", "embed")),
        "wv": dense_init(k3, (cfg.kv_dim, d), ("kv_heads", "embed")),
        "wo": dense_init(k4, (d, cfg.q_dim), ("embed", "q_heads")),
    }
    if cfg.qk_norm:
        p["q_norm"] = ones_init((cfg.head_dim,), (None,))
        p["k_norm"] = ones_init((cfg.head_dim,), (None,))
    return p


def qkv_project(p: dict, x: jax.Array, cfg, positions: jax.Array,
                theta, ov=None, vidx=None
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x (B,S,D) -> q (B,S,Hq,hd), k/v (B,S,Hkv,hd), RoPE'd (if theta).

    Sharding strategy (picked by divisibility against the live mesh):
    * head-TP when num_heads divides the model axis — constrain the FLAT
      projection outputs (fused heads×head_dim over `model`); constraining
      reshaped 4D per-head tensors makes GSPMD emit involuntary
      full-rematerialisation copies when counts don't divide.
    * sequence-TP (context parallelism) otherwise (whisper hq=8,
      starcoder2 hq=24 vs a 16-way axis): shard the q sequence over
      `model`; KV is gathered chunk-wise by the flash scan.  Head-dim
      sharded contraction is never allowed — it psums full logits.
    """
    from repro.distributed.sharding import ctx_axis_size, ctx_forward_only
    from repro.distributed.sharding import logical_constraint as _lc
    from repro.models.layers import _oget, linear, psel
    b, s, _ = x.shape
    ms = ctx_axis_size("model") or 1
    q = linear(x, p["wq"], _oget(ov, "wq"), vidx, waxes=("q_heads", "embed"))
    k = linear(x, p["wk"], _oget(ov, "wk"), vidx, waxes=("kv_heads", "embed"))
    v = linear(x, p["wv"], _oget(ov, "wv"), vidx, waxes=("kv_heads", "embed"))
    if cfg.num_heads % ms == 0 and cfg.num_kv_heads % ms == 0:
        # full head-TP
        q = _lc(q, "act_batch", "act_seq", "act_heads")
        k = _lc(k, "act_batch", "act_seq", "act_heads")
        v = _lc(v, "act_batch", "act_seq", "act_heads")
    elif cfg.num_heads % ms == 0 and s > 1:
        # GQA with kv ∤ TP (qwen3 kv=8, gemma3 kv=8, internvl kv=8):
        # shard q heads, replicate K/V — ONE (B,S,kv_dim) gather per layer.
        # Sequence-TP here makes GSPMD re-gather K/V per flash chunk
        # (measured 2.7 TB/step on qwen3 train)
        q = _lc(q, "act_batch", "act_seq", "act_heads")
        k = _lc(k, "act_batch", "act_seq", None)
        v = _lc(v, "act_batch", "act_seq", None)
    elif (s % ms == 0 and s > 1
          and (ctx_forward_only() or cfg.q_dim % ms != 0)):
        # head count indivisible: sequence-TP — but ONLY for forward-only
        # workloads (prefill) or when flat-q can't shard either; under
        # autodiff GSPMD re-gathers K/V per flash chunk in the backward
        # (measured 8× on starcoder2 train)
        q = _lc(q, "act_batch", "act_seq_tp", None)
        k = _lc(k, "act_batch", "act_seq_tp", None)
        v = _lc(v, "act_batch", "act_seq_tp", None)
    elif cfg.q_dim % ms == 0 and s > 1:
        # training fallback: shard the FLAT q_dim (starcoder2 24×128=3072);
        # GSPMD multi-dim-tiles (heads, head_dim) after the reshape.
        # K/V replicated.
        q = _lc(q, "act_batch", "act_seq", "act_heads")
        k = _lc(k, "act_batch", "act_seq", None)
        v = _lc(v, "act_batch", "act_seq", None)
    # else (decode s=1 / odd lengths): unconstrained — forcing replication
    # makes GSPMD all-gather the TP-sharded weights per layer
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(q, psel(p["q_norm"], _oget(ov, "q_norm"), vidx, lead=2),
                    cfg.norm_eps)
        k = rmsnorm(k, psel(p["k_norm"], _oget(ov, "k_norm"), vidx, lead=2),
                    cfg.norm_eps)
    if theta is not None:
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


# ---------------------------------------------------------------------------
# flash attention (train / prefill)
# ---------------------------------------------------------------------------

def _pick_chunk(t: int, chunk: int) -> int:
    chunk = min(chunk, t)
    while t % chunk:
        chunk //= 2
    return chunk


def _mask_for(s, chunk, idx, q_pos, kv_offset, causal, window):
    k_pos = kv_offset + idx * chunk + jnp.arange(chunk)
    mask = jnp.ones((s, chunk), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    mask &= (q_pos[:, None] - k_pos[None, :] < window) | (window <= 0)
    return mask


def _kv_chunk(arr, idx, chunk):
    """(b, t, hkv, hd) -> (b, chunk, hkv, hd) at chunk index idx (traced)."""
    b, t, hkv, hd = arr.shape
    return jax.lax.dynamic_slice(arr, (0, idx * chunk, 0, 0),
                                 (b, chunk, hkv, hd))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _flash(causal: bool, chunk: int, kv_offset: int,
           q, k, v, window, q_pos):
    out, _ = _flash_fwd(causal, chunk, kv_offset, q, k, v, window, q_pos)
    return out


def _flash_fwd(causal, chunk, kv_offset, q, k, v, window, q_pos):
    """KV chunks are dynamic-sliced from the natural (b, t, hkv, hd)
    layout — no physical chunk-major transpose (those showed up as
    hundreds of GB of copy/transpose traffic in the HLO byte audit)."""
    b, s, hq, hd = q.shape
    _, t, hkv, _ = k.shape
    g = hq // hkv
    n_chunks = t // chunk
    # operands stay in storage dtype (bf16): dots accumulate fp32 via
    # preferred_element_type — fp32 pre-casts double attention HBM reads
    qf = ((q.astype(jnp.float32) * hd ** -0.5).astype(k.dtype)
          .reshape(b, s, hkv, g, hd))

    def step(carry, idx):
        m, l, o = carry
        k_blk = _kv_chunk(k, idx, chunk).reshape(b, chunk, hkv, hd)
        v_blk = _kv_chunk(v, idx, chunk).reshape(b, chunk, hkv, hd)
        logits = jnp.einsum("bskgh,bckh->bskgc", qf, k_blk,
                            preferred_element_type=jnp.float32)
        mask = _mask_for(s, chunk, idx, q_pos, kv_offset, causal, window)
        logits = jnp.where(mask[None, :, None, None, :], logits, NEG_INF)
        blk_max = jnp.max(logits, axis=-1)
        new_m = jnp.maximum(m, blk_max)
        alpha = jnp.exp(m - new_m)
        p_exp = jnp.exp(logits - new_m[..., None])
        new_l = l * alpha + jnp.sum(p_exp, axis=-1)
        upd = jnp.einsum("bskgc,bckh->bskgh", p_exp.astype(v.dtype), v_blk,
                         preferred_element_type=jnp.float32)
        new_o = o * alpha[..., None] + upd
        return (new_m, new_l, new_o), None

    m0 = jnp.full((b, s, hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, s, hkv, g), jnp.float32)
    o0 = jnp.zeros((b, s, hkv, g, hd), jnp.float32)
    (m, l, o), _ = jax.lax.scan(step, (m0, l0, o0), jnp.arange(n_chunks))
    out = (o / jnp.maximum(l, 1e-30)[..., None])
    res = (q, k, v, window, q_pos, out, m, l)
    return out.reshape(b, s, hq, hd).astype(q.dtype), res


def _flash_bwd(causal, chunk, kv_offset, res, dout):
    """FlashAttention-2 style backward: recompute p per KV chunk from the
    saved (m, l); O(S·chunk) live memory, no stored logits."""
    q, k, v, window, q_pos, out, m, l = res
    b, s, hq, hd = q.shape
    _, t, hkv, _ = k.shape
    g = hq // hkv
    n_chunks = t // chunk
    scale = hd ** -0.5
    qf = ((q.astype(jnp.float32) * scale).astype(k.dtype)
          .reshape(b, s, hkv, g, hd))
    do = dout.astype(jnp.float32).reshape(b, s, hkv, g, hd)
    do_lp = do.astype(k.dtype)
    l_safe = jnp.maximum(l, 1e-30)
    # delta_i = rowsum(dO ⊙ O)
    delta = jnp.sum(do * out, axis=-1)                      # (b,s,hkv,g)

    def step(dq_acc, idx):
        k_blk = _kv_chunk(k, idx, chunk).reshape(b, chunk, hkv, hd)
        v_blk = _kv_chunk(v, idx, chunk).reshape(b, chunk, hkv, hd)
        logits = jnp.einsum("bskgh,bckh->bskgc", qf, k_blk,
                            preferred_element_type=jnp.float32)
        mask = _mask_for(s, chunk, idx, q_pos, kv_offset, causal, window)
        logits = jnp.where(mask[None, :, None, None, :], logits, NEG_INF)
        p = jnp.exp(logits - m[..., None]) / l_safe[..., None]
        p_lp = p.astype(k.dtype)
        dv_blk = jnp.einsum("bskgc,bskgh->bckh", p_lp, do_lp,
                            preferred_element_type=jnp.float32)
        dp = jnp.einsum("bskgh,bckh->bskgc", do_lp, v_blk,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None])                    # (b,s,hkv,g,c)
        ds_lp = ds.astype(k.dtype)
        dq_acc = dq_acc + jnp.einsum("bskgc,bckh->bskgh", ds_lp, k_blk,
                                     preferred_element_type=jnp.float32)
        dk_blk = jnp.einsum("bskgc,bskgh->bckh", ds_lp, qf,
                            preferred_element_type=jnp.float32)
        # dk/dv leave as scan outputs (stacked chunk-major) — accumulating
        # via dynamic-update-slice into a sequence-sharded buffer makes
        # GSPMD all-gather the accumulator every iteration
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, s, hkv, g, hd), jnp.float32)
    dq, (dk_c, dv_c) = jax.lax.scan(step, dq0, jnp.arange(n_chunks))
    dq = (dq * scale).reshape(b, s, hq, hd).astype(q.dtype)
    dk = dk_c.swapaxes(0, 1).reshape(b, t, hkv, hd).astype(k.dtype)
    dv = dv_c.swapaxes(0, 1).reshape(b, t, hkv, hd).astype(v.dtype)
    return (dq, dk, dv,
            jnp.zeros_like(res[3]), jnp.zeros_like(res[4]))


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window=0,
                    q_offset=0, kv_offset=0,
                    chunk: int = 512) -> jax.Array:
    """Online-softmax attention, scanning over KV chunks.

    q: (B, S, Hq, hd); k, v: (B, T, Hkv, hd); GQA via head grouping.
    window > 0 limits attention to the last ``window`` keys (inclusive of
    self); it may be a *traced* scalar (gemma3 scans a per-layer window
    array) — window <= 0 disables it dynamically.  Offsets give absolute
    positions of q[0] / k[0].

    Custom VJP: the backward recomputes attention probabilities per KV
    chunk from the saved (m, l) running-softmax stats, so neither pass ever
    materialises (S × T) logits — O(S·chunk) live memory both ways.
    Returns (B, S, Hq, hd) in q.dtype; softmax in fp32.  Its ops carry the
    name scope ``attention`` (op metadata only), which the benchmark's
    trace reduction reads.
    """
    t = k.shape[1]
    chunk = _pick_chunk(t, chunk)
    with jax.named_scope("attention"):
        window = jnp.asarray(window, jnp.int32)
        q_pos = q_offset + jnp.arange(q.shape[1])
        return _flash(causal, chunk, int(kv_offset), q, k, v, window, q_pos)


def attention_ref(q, k, v, *, causal=True, window=0, q_offset=0, kv_offset=0):
    """Dense reference attention (tests only)."""
    b, s, hq, hd = q.shape
    _, t, hkv, _ = k.shape
    g = hq // hkv
    qf = (q.astype(jnp.float32) * hd ** -0.5).reshape(b, s, hkv, g, hd)
    logits = jnp.einsum("bskgh,btkh->bskgt", qf, k.astype(jnp.float32))
    q_pos = q_offset + jnp.arange(s)
    k_pos = kv_offset + jnp.arange(t)
    window = jnp.asarray(window, jnp.int32)
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    mask &= (q_pos[:, None] - k_pos[None, :] < window) | (window <= 0)
    logits = jnp.where(mask[None, :, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bskgt,btkh->bskgh", w, v.astype(jnp.float32))
    return out.reshape(b, s, hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# decode attention (single new token against a cache)
# ---------------------------------------------------------------------------

def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     slot_pos: jax.Array, pos: jax.Array,
                     window: int = 0) -> jax.Array:
    """q: (B, 1, Hq, hd); caches (B, T, Hkv, hd); slot_pos — absolute
    position stored in each cache slot (−1 = empty), shape (T,) shared or
    (B, T) per batch row; pos — current absolute position, scalar shared or
    (B,) per row (continuous batching admits slots at different times, so
    each batch lane carries its own position — DESIGN.md §9).

    Cache operands stay in their storage dtype (bf16) — the dots accumulate
    fp32 via preferred_element_type; pre-casting the cache to fp32 doubles
    the dominant HBM read of the whole decode step.  Softmax stats fp32.
    When T is sequence-sharded, GSPMD lowers the reductions to the
    distributed flash-decode (pmax + psum) schedule.  Its ops carry the
    name scope ``attention``, as ``flash_attention``'s do.
    """
    with jax.named_scope("attention"):
        b, _, hq, hd = q.shape
        _, t, hkv, _ = k_cache.shape
        g = hq // hkv
        qf = ((q.astype(jnp.float32) * hd ** -0.5)
              .astype(k_cache.dtype).reshape(b, hkv, g, hd))
        logits = jnp.einsum("bkgh,btkh->bkgt", qf, k_cache,
                            preferred_element_type=jnp.float32)
        sp = jnp.broadcast_to(jnp.asarray(slot_pos, jnp.int32), (b, t))
        pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))[:, None]
        valid = (sp >= 0) & (sp <= pos_b)
        if window > 0:
            valid &= sp > pos_b - window
        logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
        m = jnp.max(logits, axis=-1, keepdims=True)
        p = jnp.exp(logits - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        p_norm = (p / jnp.maximum(l, 1e-30)).astype(v_cache.dtype)
        out = jnp.einsum("bkgt,btkh->bkgh", p_norm, v_cache,
                         preferred_element_type=jnp.float32)
        return out.reshape(b, 1, hq, hd).astype(q.dtype)


def verify_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     slot_pos: jax.Array, pos: jax.Array,
                     window: int = 0) -> jax.Array:
    """T-query generalisation of ``decode_attention`` for speculative
    verify (DESIGN.md §15): q (B, S, Hq, hd) holds S teacher-forced
    queries per row, where query t sits at absolute position pos[b] + t.
    Caches/slot_pos/pos as in ``decode_attention``.

    Each query slice must reproduce ``decode_attention`` BIT-EXACTLY —
    the engine's draft/verify parity contract (accepted tokens equal the
    non-speculative greedy chain) rides on it — so the arithmetic is the
    same: fp32-accumulated dots over storage-dtype operands, fp32 softmax
    stats, exact-zero masking via NEG_INF (exp underflows to 0.0 for
    masked slots, so stale post-rewind entries contribute nothing).
    """
    b, s, hq, hd = q.shape
    _, t, hkv, _ = k_cache.shape
    g = hq // hkv
    qf = ((q.astype(jnp.float32) * hd ** -0.5)
          .astype(k_cache.dtype).reshape(b, s, hkv, g, hd))
    logits = jnp.einsum("bskgh,btkh->bskgt", qf, k_cache,
                        preferred_element_type=jnp.float32)
    sp = jnp.broadcast_to(jnp.asarray(slot_pos, jnp.int32), (b, t))
    qpos = (jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))[:, None]
            + jnp.arange(s, dtype=jnp.int32)[None, :])       # (B, S)
    valid = (sp[:, None, :] >= 0) & (sp[:, None, :] <= qpos[:, :, None])
    if window > 0:
        valid &= sp[:, None, :] > qpos[:, :, None] - window
    logits = jnp.where(valid[:, :, None, None, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p_norm = (p / jnp.maximum(l, 1e-30)).astype(v_cache.dtype)
    out = jnp.einsum("bskgt,btkh->bskgh", p_norm, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def make_kv_cache(batch: int, max_len: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16) -> dict:
    """One layer's cache.  ``slot_pos`` records the absolute position held
    in each slot (supports ring buffers for sliding-window layers), PER
    BATCH ROW — continuous batching (DESIGN.md §9) admits/retires rows
    independently, so lanes disagree about which positions are valid."""
    return {
        "k": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "slot_pos": jnp.full((batch, max_len), -1, jnp.int32),
    }


def _row_pos(pos, b: int) -> jax.Array:
    """Normalise a scalar-or-(B,) position to (B,) int32."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))


def cache_insert(cache: dict, k_new: jax.Array, v_new: jax.Array,
                 pos: jax.Array, ring: bool = False) -> dict:
    """Insert (B, n, Hkv, hd) at absolute position(s) starting at ``pos``.

    ring=True wraps writes modulo the cache length (sliding-window layers).
    ``pos`` may be scalar (all rows aligned — prefill) or (B,) per row
    (continuous decode, lanes at different depths).
    """
    b, t = cache["k"].shape[:2]
    n = k_new.shape[1]
    dtype = cache["k"].dtype
    if not ring and n > 1:
        # prefill path: contiguous write at static offset 0 expected
        k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(dtype), (0, pos, 0, 0))
        v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(dtype), (0, pos, 0, 0))
        sp_rows = jnp.broadcast_to(pos + jnp.arange(n, dtype=jnp.int32),
                                   (b, n))
        sp = jax.lax.dynamic_update_slice(cache["slot_pos"], sp_rows, (0, pos))
        return {"k": k, "v": v, "slot_pos": sp}
    # single-token (or ring) writes; per-row positions scatter per lane
    pos_b = _row_pos(pos, b)
    idx = (pos_b % t) if ring else jnp.clip(pos_b, 0, t - 1)
    rows = jnp.arange(b)
    k = cache["k"].at[rows, idx].set(k_new[:, 0].astype(dtype))
    v = cache["v"].at[rows, idx].set(v_new[:, 0].astype(dtype))
    sp = cache["slot_pos"].at[rows, idx].set(pos_b)
    return {"k": k, "v": v, "slot_pos": sp}


def cache_insert_multi(cache: dict, k_new: jax.Array, v_new: jax.Array,
                       pos) -> dict:
    """Teacher-forced multi-token insert: (B, n, Hkv, hd) lands at PER-ROW
    absolute positions pos[b]..pos[b]+n-1 (speculative verify — lanes sit
    at different depths, so the prefill path's scalar-offset
    dynamic_update_slice cannot serve).  Non-ring caches only: slot index
    == absolute position, which is what makes rewind a pure ``pos``
    retreat (stale slots mask out via slot_pos <= pos and are overwritten
    before they could become readable again)."""
    b, t = cache["k"].shape[:2]
    n = k_new.shape[1]
    dtype = cache["k"].dtype
    pos_b = _row_pos(pos, b)
    posn = pos_b[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    idx = jnp.clip(posn, 0, t - 1)
    rows = jnp.arange(b)[:, None]
    k = cache["k"].at[rows, idx].set(k_new.astype(dtype))
    v = cache["v"].at[rows, idx].set(v_new.astype(dtype))
    sp = cache["slot_pos"].at[rows, idx].set(posn)
    return {"k": k, "v": v, "slot_pos": sp}


def cache_insert_stacked(caches: dict, layer_idx, k_new: jax.Array,
                         v_new: jax.Array, pos, ring: bool = False) -> dict:
    """In-place-style single-token insert into a STACKED (L, B, T, H, hd)
    cache at (layer_idx, b, pos_b).  Used by the decode scan, which carries
    the whole stacked cache: the scatter update is one token per lane (KB),
    so XLA aliases the carry buffer instead of copying the cache every
    layer (scan-ys stacking rewrites the full cache per step — measured as
    the dominant decode byte term before this change)."""
    b, t = caches["k"].shape[1:3]
    pos_b = _row_pos(pos, b)
    idx = (pos_b % t) if ring else jnp.clip(pos_b, 0, t - 1)
    dtype = caches["k"].dtype
    rows = jnp.arange(b)
    k = caches["k"].at[layer_idx, rows, idx].set(k_new[:, 0].astype(dtype))
    v = caches["v"].at[layer_idx, rows, idx].set(v_new[:, 0].astype(dtype))
    sp = caches["slot_pos"].at[layer_idx, rows, idx].set(pos_b)
    return {"k": k, "v": v, "slot_pos": sp}


def cache_insert_stacked_multi(caches: dict, layer_idx, k_new: jax.Array,
                               v_new: jax.Array, pos) -> dict:
    """``cache_insert_multi`` against a STACKED (L, B, T, H, hd) cache at
    (layer_idx, b, pos[b]..pos[b]+n-1) — the speculative verify analogue
    of ``cache_insert_stacked`` (the write is n tokens per lane, still KB
    against the full cache, so XLA aliases the scan carry)."""
    b, t = caches["k"].shape[1:3]
    n = k_new.shape[1]
    dtype = caches["k"].dtype
    pos_b = _row_pos(pos, b)
    posn = pos_b[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    idx = jnp.clip(posn, 0, t - 1)
    rows = jnp.arange(b)[:, None]
    k = caches["k"].at[layer_idx, rows, idx].set(k_new.astype(dtype))
    v = caches["v"].at[layer_idx, rows, idx].set(v_new.astype(dtype))
    sp = caches["slot_pos"].at[layer_idx, rows, idx].set(posn)
    return {"k": k, "v": v, "slot_pos": sp}


def cache_layer_view(caches: dict, layer_idx) -> dict:
    """Read one layer's (B, T, H, hd) slice from a stacked cache."""
    lk = caches["k"].shape
    k = jax.lax.dynamic_slice(
        caches["k"], (layer_idx, 0, 0, 0, 0), (1,) + lk[1:])[0]
    v = jax.lax.dynamic_slice(
        caches["v"], (layer_idx, 0, 0, 0, 0), (1,) + lk[1:])[0]
    sp = jax.lax.dynamic_slice(
        caches["slot_pos"], (layer_idx, 0, 0), (1, lk[1], lk[2]))[0]
    return {"k": k, "v": v, "slot_pos": sp}


def prefill_ring(cache: dict, k_all: jax.Array, v_all: jax.Array,
                 window: int) -> dict:
    """Fill a ring cache of size ``window`` with the last ``window`` of a
    full prefill (S >= window assumed handled by caller slicing)."""
    s = k_all.shape[1]
    w = cache["k"].shape[1]
    start = max(0, s - w)
    k_tail = k_all[:, start:start + w]
    v_tail = v_all[:, start:start + w]
    n = k_tail.shape[1]
    positions = jnp.arange(start, start + n, dtype=jnp.int32)
    slots = positions % w
    k = cache["k"].at[:, slots].set(k_tail.astype(cache["k"].dtype))
    v = cache["v"].at[:, slots].set(v_tail.astype(cache["v"].dtype))
    sp = cache["slot_pos"].at[:, slots].set(positions)
    return {"k": k, "v": v, "slot_pos": sp}
