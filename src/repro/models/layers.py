"""Common building blocks: norms, RoPE, embeddings, gated MLP."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.param import Param, dense_init, ones_init


def cast_to(x: jax.Array, dtype_name: str) -> jax.Array:
    return x.astype(jnp.dtype(dtype_name))


# ---------------------------------------------------------------------------
# shared linear: every family's projection matmul routes through here so a
# delta overlay entry (models/delta_overlay.py) can swap the dense GEMM for
# the fused on-the-fly delta GEMM without touching call sites
# ---------------------------------------------------------------------------

def linear(x: jax.Array, w: jax.Array, ov=None, vidx=None,
           waxes=None) -> jax.Array:
    """y = x @ Ŵᵀ where Ŵ = w without an overlay entry, else the variant
    weight v ⊙ unpack(B) + w applied on the fly (never densified).

    With ``vidx`` (per-batch-row int32 variant indices, 0 = base) the
    overlay entry is BANKED — leaves carry a leading bank axis and every
    row fuses its own variant's delta in one mixed-variant GEMM
    (DESIGN.md §9).

    ``waxes`` — the weight's logical axes as declared at init (e.g.
    ``("ffn", "embed")``) — is the mesh/axes context the model families
    thread down: inside an active mesh the fused delta GEMM then lowers
    as a shard_map'd per-shard Pallas kernel on the weight's own tiling
    (kernels/dispatch.py, DESIGN.md §12) instead of leaning on GSPMD to
    partition the opaque kernel call.

    ``w`` may be a ``core/quantize.QuantWeight`` (int8 base + fp16
    per-output-channel scale).  The no-overlay path factors EXACTLY —
    x @ Ŵᵀ = (x @ qᵀ) ⊙ scale, per-channel scales commute out of the
    contraction — so the dense fp base is never materialised; overlay
    paths hand the QuantWeight to the kernels, which dequantize per
    tile (DESIGN.md §16)."""
    from repro.kernels import ops as K
    if ov is None:
        return K.base_matmul(x, w)
    if vidx is None:
        return K.bitlinear_axes(x, ov.packed, ov.v_row, ov.v_col, w,
                                waxes=waxes)
    return K.bitlinear_axes_banked(x, vidx, ov.packed, ov.v_row, ov.v_col,
                                   w, waxes=waxes)


def psel(w: jax.Array, bank, vidx, *, lead: int = 1) -> jax.Array:
    """Per-row parameter select for BANKED extras (norm scales, biases,
    convs — fine-tuned leaves that are not delta targets).

    ``bank`` is (V, *w.shape) with slot 0 holding the base value; returns
    ``w`` untouched when unbanked, else ``bank[vidx]`` with ``lead``
    singleton axes inserted after the batch dim so the result broadcasts
    against (B, S, ...) activations."""
    if bank is None or vidx is None:
        return w
    sel = jnp.take(bank, vidx, axis=0)
    return sel.reshape(sel.shape[0], *([1] * lead), *sel.shape[1:])


def _oget(ov, key):
    from repro.models.delta_overlay import oget
    return oget(ov, key)


# ---------------------------------------------------------------------------
# RMSNorm (fp32 internally)
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int) -> Param:
    return ones_init((d,), (None,))


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with fp32 statistics and an x.dtype data path — both ways.

    Autodiff through an fp32 variance branch creates an fp32 (B,S,D)
    cotangent that promotes the whole residual-stream gradient (and every
    TP backward all-reduce riding on it) to fp32 — measured as 2× the
    collective wire bytes on TP cells.  The hand-written VJP keeps all
    (B,S,D) tensors in x.dtype; only rowwise statistics are fp32.
    """
    return _rms_fwd(x, scale, eps)[0]


def _rms_stats(x, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return jax.lax.rsqrt(var + eps)              # fp32 (..., 1)


def _rms_fwd(x, scale, eps):
    inv = _rms_stats(x, eps)
    y = x * inv.astype(x.dtype) * scale.astype(x.dtype)
    return y, (x, scale, inv)


def _rms_bwd(eps, res, dy):
    x, scale, inv = res
    d = x.shape[-1]
    sc = scale.astype(x.dtype)
    # t = Σ_D dy·scale·x   (fp32 rowwise scalar)
    t = jnp.sum((dy * sc).astype(jnp.float32) * x.astype(jnp.float32),
                axis=-1, keepdims=True)
    coef = (inv ** 3 * (t / d)).astype(x.dtype)  # (..., 1)
    dx = dy * sc * inv.astype(x.dtype) - x * coef
    # scale broadcasts as a suffix of x.shape (may be multi-dim, e.g.
    # per-head (H, hd) norms): reduce the leading broadcast dims
    lead = tuple(range(x.ndim - scale.ndim))
    dscale = jnp.sum((dy * x).astype(jnp.float32) * inv,
                     axis=lead).astype(scale.dtype)
    return dx.astype(x.dtype), dscale


rmsnorm.defvjp(_rms_fwd, _rms_bwd)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x: jax.Array, positions: jax.Array, theta) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S).

    theta may be a python float or a traced scalar (gemma3 per-layer base).
    """
    hd = x.shape[-1]
    theta = jnp.asarray(theta, jnp.float32)
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len: int, d: int) -> jax.Array:
    """Whisper-style absolute sinusoidal embeddings (S, d)."""
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    div = jnp.exp(-jnp.log(10000.0) * jnp.arange(0, d, 2, jnp.float32) / d)
    ang = pos * div
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def embed_init(key, vocab: int, d: int) -> Param:
    return dense_init(key, (vocab, d), ("vocab", "embed"), scale=1.0)


def embed_lookup(table: jax.Array, tokens: jax.Array, dtype: str,
                 bank=None, vidx=None) -> jax.Array:
    """Token embedding; with a banked extras table (V, vocab, d) and per-row
    variant indices, each batch row looks up its own variant's table."""
    if bank is None or vidx is None:
        return cast_to(jnp.take(table, tokens, axis=0), dtype)
    idx = vidx.reshape(vidx.shape[0], *([1] * (tokens.ndim - 1)))
    return cast_to(bank[idx, tokens], dtype)


def unembed_logits(x: jax.Array, table: jax.Array, bank=None,
                   vidx=None) -> jax.Array:
    """logits = x @ tableᵀ; with a banked table each row contracts against
    its own variant's (fine-tuned, fp16-rounded) unembedding.

    Banked path is a masked select over the V bank slots (same pattern as
    the banked MoE router): the table is read at most V times per step —
    never gathered per ROW, which would cost B copies of (vocab, d) and
    break the traffic-independent-of-batch-mix invariant (DESIGN.md §9) —
    and each row's logits come from the identical matmul the per-variant
    path runs, so greedy tokens match it exactly."""
    if bank is None or vidx is None:
        return x @ table.T.astype(x.dtype)
    logits = x @ bank[0].T.astype(x.dtype)               # slot 0 = base
    for v in range(1, bank.shape[0]):
        lv = x @ bank[v].T.astype(x.dtype)
        logits = jnp.where((vidx == v)[:, None, None], lv, logits)
    return logits


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU)
# ---------------------------------------------------------------------------

def mlp_init(key, d: int, d_ff: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_ff, d), ("ffn", "embed")),
        "w_up": dense_init(k2, (d_ff, d), ("ffn", "embed")),
        "w_down": dense_init(k3, (d, d_ff), ("embed", "ffn")),
    }


def mlp_apply(p: dict, x: jax.Array, ov=None, vidx=None,
              ffn_ax: str = "ffn") -> jax.Array:
    """``ffn_ax`` names the hidden dim's logical axis — "ffn" for the
    standard gated MLP, "ffn_small" for replicated shared experts — so the
    per-shard kernel dispatch sees the same axes the weights were
    initialised (and placed) with."""
    h = (jax.nn.silu(linear(x, p["w_gate"], _oget(ov, "w_gate"), vidx,
                            waxes=(ffn_ax, "embed")))
         * linear(x, p["w_up"], _oget(ov, "w_up"), vidx,
                  waxes=(ffn_ax, "embed")))
    return linear(h, p["w_down"], _oget(ov, "w_down"), vidx,
                  waxes=("embed", ffn_ax))


# ---------------------------------------------------------------------------
# Non-gated MLP (whisper)
# ---------------------------------------------------------------------------

def mlp2_init(key, d: int, d_ff: int) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "w_in": dense_init(k1, (d_ff, d), ("ffn", "embed")),
        "w_out": dense_init(k2, (d, d_ff), ("embed", "ffn")),
    }


def mlp2_apply(p: dict, x: jax.Array, ov=None, vidx=None) -> jax.Array:
    return linear(jax.nn.gelu(linear(x, p["w_in"], _oget(ov, "w_in"), vidx,
                                     waxes=("ffn", "embed"))),
                  p["w_out"], _oget(ov, "w_out"), vidx,
                  waxes=("embed", "ffn"))
