"""Pallas TPU kernel: fused dense reconstruction Ŵ = v ⊙ unpack(B) + W_b.

This is the loader hot path (paper §3.2 "Storage and load-time"): after the
packed mask + scale vector arrive in HBM (one transfer per module), this
kernel streams W_b once and the packed mask at 1/16 the bytes of a bf16
weight, unpacks to ±1 *inside VMEM* and applies the per-axis FMA on the
VPU.  HBM traffic ≈ (1 + 1/16)·|W| reads + |W| writes — the unpack never
round-trips a dense ±1 matrix through HBM.

Layout contract (matches repro.core.delta):
  packed : (d_out, d_in // 8) uint8, little-endian bit j ↔ column i*8+j
  w_base : (d_out, d_in)
  v2d    : row  (d_out, 1) · col (1, d_in) · scalar (1, 1)  — pre-reshaped
           by ops.py so the kernel is mode-agnostic (pure broadcast FMA).

Unpacking in natural column order needs each packed byte repeated across
the 8 lanes it covers, a lane interleave Mosaic does not lower.  The kernel
gets it from the MXU instead: bytes (0..255, exact in bf16) times the 0/1
matrix ``E[i, c] = (c // 8 == i)`` put byte c//8 in lane c, and a per-lane
shift by c % 8 picks the bit.  The work runs in chunks of 1024 output lanes
(128 packed bytes), so E is one (128, 1024) block for any width.

Blocking: grid (d_out/bm, d_in/bn); bn is a multiple of 1024 or the full
d_in (ops._unpack_blocks).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitlinear import VMEM_LIMIT

PACK = 8
CHUNK = 1024                   # output lanes per in-kernel unpack step


def expand_matrix() -> jax.Array:
    """(CHUNK/8, CHUNK) bf16 with E[i, c] = 1 where c // 8 == i."""
    rows = jnp.arange(CHUNK // PACK)[:, None]
    cols = jnp.arange(CHUNK)[None, :]
    return (cols // PACK == rows).astype(jnp.bfloat16)


def _unpack_chunk(packed: jax.Array, expand: jax.Array) -> jax.Array:
    """(bm, w/8) uint8 -> (bm, w) ±1 fp32, little-endian within bytes."""
    w = packed.shape[1] * PACK
    byte = packed.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)
    rep = jax.lax.dot_general(byte, expand[:packed.shape[1], :w],
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    shift = jax.lax.broadcasted_iota(jnp.int32, rep.shape, 1) & (PACK - 1)
    bits = (rep.astype(jnp.int32) >> shift) & 1
    return (bits * 2 - 1).astype(jnp.float32)


def _kernel(e_ref, packed_ref, v_ref, wb_ref, *rest):
    *ws_ref, out_ref = rest
    expand = e_ref[...]
    v = v_ref[...]                              # (bm,1) | (1,bn) | (1,1)
    scale = ws_ref[0][...] if ws_ref else None  # int8 base: (bm, 1)
    bn = out_ref.shape[1]
    for c0 in range(0, bn, CHUNK):
        w = min(CHUNK, bn - c0)
        signs = _unpack_chunk(packed_ref[:, c0 // PACK:(c0 + w) // PACK],
                              expand)
        vc = v[:, c0:c0 + w] if v.shape[1] > 1 else v
        wb = wb_ref[:, c0:c0 + w].astype(jnp.float32)
        if scale is not None:
            wb = wb * scale
        out_ref[:, c0:c0 + w] = (vc * signs + wb).astype(out_ref.dtype)


def unpack_apply_p(packed: jax.Array, v2d: jax.Array, w_base: jax.Array,
                   *, block_m: int, block_n: int, out_dtype,
                   interpret: bool, w_scale: jax.Array = None) -> jax.Array:
    """``w_scale`` (d_out, 1) fp16 selects the int8-base path: w_base is
    then the int8 payload and the tile loop dequantizes in VMEM."""
    d_out, d_in = w_base.shape
    assert d_in % PACK == 0 and block_n % PACK == 0
    assert d_out % block_m == 0 and d_in % block_n == 0
    grid = (d_out // block_m, d_in // block_n)

    vm, vn = v2d.shape  # (d_out,1) | (1,d_in) | (1,1)
    v_block = (block_m if vm > 1 else 1, block_n if vn > 1 else 1)

    def v_index(i, j):
        return (i if vm > 1 else 0, j if vn > 1 else 0)

    in_specs = [
        pl.BlockSpec((CHUNK // PACK, CHUNK), lambda i, j: (0, 0)),
        pl.BlockSpec((block_m, block_n // PACK), lambda i, j: (i, j)),
        pl.BlockSpec(v_block, v_index),
        pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
    ]
    operands = [expand_matrix(), packed, v2d.astype(jnp.float32), w_base]
    if w_scale is not None:
        assert w_scale.shape == (d_out, 1)
        in_specs.append(pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)))
        operands.append(w_scale.astype(jnp.float32))

    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d_out, d_in), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*operands)
