"""Jit'd public wrappers for the Pallas kernels.

Handles: per-axis-mode v reshaping, block-size selection (``_pick_block``
is the one place that knows the TPU tiling rules and the VMEM budget),
interpret mode on CPU hosts (Pallas' interpreter; any other non-TPU
backend is an error), and output dtype casting.

Every delta GEMM here is the factored form of ``kernels/bitlinear.py``:
``base_matmul(x, W_b)`` — the plain XLA matmul the no-overlay path runs,
fp or int8 base — plus the sign-plane delta from the one Pallas sign-GEMM
kernel.  Plain (mode), fused (one variant) and banked (per-row slot) calls
differ only in the vectors and slot indices they hand that kernel.

Partitioned execution (DESIGN.md §12): inside an active mesh context
(``distributed.sharding.shard_ctx`` — the sharded engine traces there)
the sign delta routes through ``kernels/dispatch.py``, which lowers it as
a shard_map'd per-shard kernel with blocks picked from SHARD-LOCAL dims;
the caller passes the shadowed weight's logical axes via ``waxes``.  The
base matmul stays a plain XLA op that GSPMD partitions.  Without a mesh
the global jit path runs; a mesh matmul the dispatcher declines is counted
(``dispatch.memo_stats["declined"]``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import bitlinear as _bl
from repro.kernels import dispatch as _dp
from repro.kernels import unpack_apply as _ua

PACK = 8

# TPU tiling: a block's last dim is a multiple of 128 lanes and its
# second-to-last a multiple of 8 sublanes, or the block spans the full dim.
LANES = 128
SUBLANES = 8

# block targets (sign GEMM: rows, out features, packed bytes of K)
_TILE_M = 256
_TILE_N = 512
_TILE_KP = 512
# estimated VMEM a block may take, well under bitlinear.VMEM_LIMIT
_VMEM_BUDGET = 24 * 1024 * 1024


def _interpret() -> bool:
    """Pallas interpret mode on a CPU host; compiled Mosaic on a TPU.  Any
    other backend has no Pallas TPU lowering and must not silently run the
    interpreter in place of the device."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels need a TPU (or the CPU interpreter); the "
        f"default backend is {backend!r}")


def _unwrap_quant(w):
    """Split a base-weight operand into (payload, scale-or-None).

    An int8-quantized base arrives as a ``core/quantize.QuantWeight``
    pytree (duck-typed on the ``__quant_leaf__`` marker — works on
    tracers); a full-precision base passes through with no scale."""
    if getattr(w, "__quant_leaf__", False):
        return w.q, w.scale
    return w, None


def _pick_block(dim: int, target: int, multiple: int = 1,
                align: int = 1) -> int:
    """Block size along one dim: the largest divisor of ``dim`` that is
    <= ``target`` and a multiple of both ``multiple`` and ``align``.

    ``multiple`` is a hard requirement (the packing width along K): a dim
    that is not a multiple of it has no valid block, so refuse loudly —
    the dispatch planner keeps such shard-local splits on the global path.
    ``align`` is the TPU tiling rule (8 sublanes / 128 lanes): when no
    aligned divisor exists the block spans the full dim, which Mosaic
    always accepts (e.g. K=11008 packs to 1376 bytes — not a multiple of
    128 — so its packed block is the whole row; ``_gemm_blocks`` then
    shrinks the other dims to keep VMEM in budget).  Without alignment
    and with ``multiple > target`` the smallest valid block is returned
    instead of an oversized one."""
    if dim % multiple:
        raise ValueError(
            f"no valid block for dim={dim}: not a multiple of {multiple} "
            "(shard-local kernel dims must stay aligned to the packing "
            "width; kernels/dispatch.py falls back to the global path "
            "for such splits)")
    step = math.lcm(multiple, align)
    for cand in range(min(dim, target) // step * step, 0, -step):
        if dim % cand == 0:
            return cand
    if align > 1 or dim <= target:
        return dim
    return multiple


def _gemm_blocks(m: int, n: int, k: int, sublanes: int = SUBLANES) -> tuple:
    """(bm, bn, bk) for the sign GEMM, tiling-legal and sized so one
    grid step's VMEM (double-buffered operands + unpack temporaries) fits
    ``_VMEM_BUDGET``.  ``sublanes`` is the row tile of the activation
    dtype (16 for bf16); ``m`` is already padded by the caller when it has
    no aligned divisor."""
    kp = k // PACK
    bkp = _pick_block(kp, _TILE_KP, align=LANES)
    tm, tn = _TILE_M, _TILE_N
    while True:
        bm = _pick_block(m, tm, align=sublanes)
        bn = _pick_block(n, tn, align=LANES)
        need = (2 * (PACK * bm * bkp * 4 + bn * bkp + PACK * bkp * 4
                     + bn * 4 + bm * LANES * 4)
                + 3 * bm * bn * 4 + 3 * bn * bkp * 4 + bm * bkp * 4)
        if need <= _VMEM_BUDGET:
            return bm, bn, bkp * PACK
        if tm > sublanes:
            tm //= 2
        elif tn > LANES:
            tn //= 2
        else:
            return bm, bn, bkp * PACK


def _unpack_blocks(n: int, k: int, out_itemsize: int,
                   w_itemsize: int) -> tuple:
    """(bm, bn) for unpack_apply: bn a multiple of the 1024-lane unpack
    chunk (so its packed block is 128-lane aligned) or the full row."""
    bn = _pick_block(k, 4 * _ua.CHUNK, multiple=PACK, align=_ua.CHUNK)
    tm = _TILE_M
    while True:
        bm = _pick_block(n, tm, align=32)   # int8 sublane tile is 32 rows
        need = (2 * bm * bn * (out_itemsize + w_itemsize + 1.0 / PACK)
                + 4 * bm * _ua.CHUNK * 4)
        if need <= _VMEM_BUDGET or tm <= 32:
            return bm, bn
        tm //= 2


def flatten_vidx(variant_idx: jax.Array, lead: tuple) -> jax.Array:
    """Per-row variant indices -> flattened batch rows (m,) int32.

    ``variant_idx`` has shape ``lead`` (one slot per row) or ``(lead[0],)``
    (broadcast over the remaining lead dims).  The ONE definition of the
    banked vidx convention — both the global jit path and the shard_map
    dispatch (kernels/dispatch.py) flatten through here, so the two
    lowerings can never drift apart."""
    m = math.prod(lead)
    if variant_idx.shape == tuple(lead):
        return variant_idx.astype(jnp.int32).reshape(m)
    return jnp.broadcast_to(
        variant_idx.reshape(variant_idx.shape[0], *([1] * (len(lead) - 1))),
        tuple(lead)).astype(jnp.int32).reshape(m)


def _v2d(v: jax.Array, mode: str, d_out: int, d_in: int) -> jax.Array:
    if mode == "row":
        assert v.shape == (d_out,), (v.shape, d_out)
        return v.reshape(d_out, 1)
    if mode == "col":
        assert v.shape == (d_in,), (v.shape, d_in)
        return v.reshape(1, d_in)
    if mode == "scalar":
        return v.reshape(1, 1)
    raise ValueError(mode)


# ---------------------------------------------------------------------------
# dense reconstruction
# ---------------------------------------------------------------------------

def unpack_apply_local(packed: jax.Array, v2d: jax.Array, wq: jax.Array,
                       ws, out_dtype) -> jax.Array:
    """The unpack_apply kernel on whole local operands (global jit path
    and each shard_map shard alike); ``ws`` is the int8 base's
    per-output-channel scale or None.  v5e's vector unit has no fp16, so
    an fp16 Ŵ is written as fp32 and narrowed by XLA."""
    d_out, d_in = wq.shape
    kdt = (jnp.float32 if jnp.dtype(out_dtype) == jnp.float16
           else out_dtype)
    bm, bn = _unpack_blocks(d_out, d_in, jnp.dtype(kdt).itemsize,
                            jnp.dtype(wq.dtype).itemsize)
    y = _ua.unpack_apply_p(
        packed, v2d, wq, block_m=bm, block_n=bn, out_dtype=kdt,
        interpret=_interpret(),
        w_scale=None if ws is None else ws.reshape(d_out, 1))
    return y.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("mode", "out_dtype"))
def _unpack_apply_global(packed: jax.Array, v: jax.Array, w_base,
                         mode: str, out_dtype) -> jax.Array:
    wq, ws = _unwrap_quant(w_base)
    d_out, d_in = wq.shape
    return unpack_apply_local(packed, _v2d(v, mode, d_out, d_in), wq, ws,
                              out_dtype)


def unpack_apply(packed: jax.Array, v: jax.Array, w_base,
                 mode: str = "row", out_dtype=None,
                 waxes=None) -> jax.Array:
    """Production Ŵ = v ⊙ unpack(B) + W_b (loader hot path).

    ``w_base`` may be a QuantWeight (int8 base): the kernel then
    dequantizes per tile and the default out dtype follows the scale.
    ``waxes`` (the weight's logical axes) + an active mesh context lower
    this as a shard_map'd per-tile reconstruction — each device rebuilds
    only its own Ŵ shard; otherwise the global jit path runs."""
    _, ws = _unwrap_quant(w_base)
    out_dtype = out_dtype or (ws.dtype if ws is not None else w_base.dtype)
    st = _dp.state()
    if st is not None:
        y = (None if waxes is None else
             _dp.unpack_apply(st, packed, v, w_base, mode, out_dtype, waxes))
        if y is not None:
            return y
        _dp.note_decline()
    return _unpack_apply_global(packed, v, w_base, mode=mode,
                                out_dtype=out_dtype)


@functools.partial(jax.jit, static_argnames=("causal", "q_offset",
                                              "kv_offset"))
def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True, q_offset: int = 0,
                        kv_offset: int = 0) -> jax.Array:
    """Pallas forward flash attention (serving/prefill hot path).

    q (B, S, Hq, hd); k/v (B, T, Hkv, hd) — GQA via head index mapping.
    Logits never leave VMEM (see kernels/flash_attn.py for the roofline
    argument).  Forward-only: training uses models/attention.py.
    """
    from repro.kernels.flash_attn import flash_attention_fwd_p
    b, s, hq, hd = q.shape
    _, t, hkv, _ = k.shape
    group = hq // hkv
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, s, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, t, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, t, hd)
    o = flash_attention_fwd_p(qf, kf, vf, group=group, causal=causal,
                              q_offset=q_offset, kv_offset=kv_offset,
                              interpret=_interpret())
    return o.reshape(b, hq, s, hd).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# delta GEMMs: base matmul + sign delta
# ---------------------------------------------------------------------------

def base_matmul(x: jax.Array, w) -> jax.Array:
    """y = x @ W_bᵀ in x.dtype — THE base GEMM of every linear, with or
    without an overlay.  An int8 base factors exactly: per-output-channel
    scales commute out of the contraction, (x @ qᵀ) ⊙ scale.

    The optimization barrier pins the rounded result: without it XLA may
    fuse the matmul into different consumers in the plain and the banked
    step programs and keep excess precision in one of them, and a base
    lane of a mixed batch would then drift from plain serving.

    Its ops carry the name scope ``base_gemm`` (op metadata only: the
    program is the same), which the benchmark's trace reduction reads."""
    with jax.named_scope("base_gemm"):
        if getattr(w, "__quant_leaf__", False):
            y = (x @ w.q.T.astype(x.dtype)) * w.scale.astype(x.dtype)
        else:
            y = x @ w.T.astype(x.dtype)
        return jax.lax.optimization_barrier(y)


def sign_delta_local(x2: jax.Array, vidx: jax.Array, packed: jax.Array,
                     v_row: jax.Array, v_col: jax.Array) -> jax.Array:
    """The sign-GEMM kernel on whole local operands: x2 (M, K) · vidx (M,)
    · packed (V, N, K/8) · v_row (V, N) · v_col (V, K) -> (M, N) fp32.
    Rows are zero-padded to a sublane multiple when M is over the row
    target and not aligned (a smaller M is one full-height block)."""
    m, k = x2.shape
    n = packed.shape[1]
    sub = 2 * SUBLANES if x2.dtype == jnp.bfloat16 else SUBLANES
    mp = m
    if m > _TILE_M and m % sub:
        mp = -(-m // sub) * sub
        x2 = jnp.pad(x2, ((0, mp - m), (0, 0)))
        vidx = jnp.pad(vidx, (0, mp - m))
    bm, bn, bk = _gemm_blocks(mp, n, k, sublanes=sub)
    y = _bl.sign_gemm_p(x2, vidx, packed, v_row, v_col, block_m=bm,
                        block_n=bn, block_k=bk, interpret=_interpret())
    return y[:m]


_sign_delta_global = jax.jit(sign_delta_local)


def _sign_delta(x: jax.Array, variant_idx, packed: jax.Array,
                v_row: jax.Array, v_col: jax.Array, waxes) -> jax.Array:
    """fp32 delta (..., N) of x (..., K) against a bank (leading slot
    axis on packed/v_row/v_col); ``variant_idx`` None means slot 0."""
    *lead, k = x.shape
    n = packed.shape[1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    vidx = (jnp.zeros((m,), jnp.int32) if variant_idx is None
            else flatten_vidx(variant_idx, tuple(lead)))
    y = None
    st = _dp.state()
    if st is not None:
        if waxes is not None:
            y = _dp.sign_delta(st, x2, vidx, packed, v_row, v_col, waxes)
        if y is None:
            _dp.note_decline()
    if y is None:
        y = _sign_delta_global(x2, vidx, packed, v_row, v_col)
    return y.reshape(*lead, n)


def bitlinear_axes_banked(x: jax.Array, variant_idx: jax.Array,
                          packed: jax.Array, v_row: jax.Array,
                          v_col: jax.Array, w_base,
                          waxes=None) -> jax.Array:
    """Mixed-variant y: row m of x computes against bank slot
    ``variant_idx[m]`` of a stacked overlay (slot 0 = base, zero delta).

    packed (V, N, K/8) · v_row (V, N) · v_col (V, K) stack the per-variant
    overlay leaves along a leading bank axis; ``variant_idx`` is int32 with
    shape x.shape[:-1] or (x.shape[0],) (broadcast over the remaining lead
    dims — one variant per batch row).  ``w_base`` may be a QuantWeight.

    ``waxes`` + an active mesh context lower the delta per-shard (each
    device reads its own weight tile's bank — kernels/dispatch.py)."""
    base = base_matmul(x, w_base)
    d = _sign_delta(x, variant_idx, packed, v_row, v_col, waxes)
    return base + d.astype(base.dtype)


def bitlinear_axes(x: jax.Array, packed: jax.Array, v_row: jax.Array,
                   v_col: jax.Array, w_base, waxes=None) -> jax.Array:
    """Fused y = x @ ((v_row ⊕ v_col) ⊙ unpack(B) + W_b)ᵀ.

    Effective scale v[n,k] = v_row[n] + v_col[k]; the on-the-fly serving
    overlay zeroes the unselected axis vector per matrix, so this one
    entry point covers row-, col- and scalar-scaled deltas with no static
    mode argument.  The one-slot case of :func:`bitlinear_axes_banked`.
    x may carry leading batch dims; the result has x.dtype."""
    return bitlinear_axes_banked(x, None, packed[None], v_row[None],
                                 v_col[None], w_base, waxes=waxes)


@functools.partial(jax.jit, static_argnames=("mode",))
def bitlinear(x: jax.Array, packed: jax.Array, v: jax.Array,
              w_base, mode: str = "row") -> jax.Array:
    """Fused y = x @ (v ⊙ unpack(B) + W_b)ᵀ for a single-mode scale
    (row / col / scalar).  ``w_base`` may be a QuantWeight."""
    n, k = packed.shape[0], packed.shape[1] * PACK
    zr, zc = jnp.zeros((n,), jnp.float32), jnp.zeros((k,), jnp.float32)
    v = v.astype(jnp.float32)
    if mode == "row":
        v_row, v_col = v, zc
    elif mode == "col":
        v_row, v_col = zr, v
    elif mode == "scalar":
        v_row, v_col = jnp.broadcast_to(v, (n,)), zc
    else:
        raise ValueError(mode)
    return bitlinear_axes(x, packed, v_row, v_col, w_base)
