"""Pallas TPU kernel: the sign GEMM of a (banked) per-axis delta.

Every delta GEMM in this repo factors the variant weight
Ŵ = (v_row ⊕ v_col) ⊙ S + W_b as

    x · Ŵᵀ = x · W_bᵀ  +  v_row ⊙ (x · Sᵀ)  +  (x ⊙ v_col) · Sᵀ

The base term is an ordinary XLA matmul (``ops.base_matmul``, shared with
the no-overlay path, fp or int8 base alike).  This kernel computes the two
sign terms straight from the packed 1-bit planes, for a BANK of variants:
row m of x contracts against bank slot ``vidx[m]`` (slot-0 vectors are
zero, so base rows get an exactly-zero delta).  A single fused variant is
the one-slot bank.

Layout (bit-compatible with ``core/delta.pack_signs``): byte i of a packed
row holds columns 8i..8i+7, bit j ↔ column 8i+j.  Bit-plane j of a
(bn, bk/8) packed block is therefore a lane-dense ±1 block over columns
j, j+8, j+16, ...; the wrapper permutes the small activation x (and v_col)
to match — ``xp[j, m, i] = x[m, 8i+j]`` — so each plane is one MXU dot and
no lane interleave ever happens in VMEM.

Grid (M/bm, N/bn, K/bk, V), slot axis innermost; the output block
accumulates in fp32 over K blocks and slots.  Slots absent from an M block
are skipped through a scalar-prefetched occupancy table, so a decode step
unpacks only the variants its rows use.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PACK = 8

# scoped-VMEM ceiling handed to Mosaic; ops._gemm_blocks sizes blocks to
# stay well under it (v5e has 128 MiB of VMEM per core)
VMEM_LIMIT = 64 * 1024 * 1024


def _dot_nt(a, b):
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _kernel(used_ref, vidx_ref, xp_ref, packed_ref, vr_ref, vc_ref, out_ref):
    i, kk, s = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nslots = pl.num_programs(3)

    @pl.when((kk == 0) & (s == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(used_ref[i * nslots + s] > 0)
    def _accumulate():
        p = packed_ref[0].astype(jnp.int32)                 # (bn, bk/8)
        cdt = xp_ref.dtype

        def plane_step(j, acc):
            xs, xc = acc                                    # x·Sᵀ, (x⊙v_col)·Sᵀ
            plane = (((p >> j) & 1) * 2 - 1).astype(cdt)
            xj = xp_ref[j]                                  # (bm, bk/8)
            xcj = (xj.astype(jnp.float32) * vc_ref[0, j]).astype(cdt)
            return xs + _dot_nt(xj, plane), xc + _dot_nt(xcj, plane)

        zero = jnp.zeros(out_ref.shape, jnp.float32)
        xs, xc = jax.lax.fori_loop(0, PACK, plane_step, (zero, zero))
        contrib = vr_ref[0] * xs + xc                       # vr (1, bn)
        out_ref[...] += jnp.where(vidx_ref[...] == s, contrib, 0.0)


def sign_gemm_p(x: jax.Array, vidx: jax.Array, packed: jax.Array,
                v_row: jax.Array, v_col: jax.Array, *, block_m: int,
                block_n: int, block_k: int, interpret: bool) -> jax.Array:
    """x (M, K) · vidx (M,) int32 · packed (V, N, K/8) uint8 · v_row (V, N)
    · v_col (V, K) -> Σ-delta (M, N) fp32.  ``block_k`` counts unpacked
    columns (its packed block is block_k/8 bytes wide)."""
    m, k = x.shape
    nslots, n, kp = packed.shape
    assert k == kp * PACK and block_k % PACK == 0
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    assert vidx.shape == (m,)
    assert v_row.shape == (nslots, n) and v_col.shape == (nslots, k)
    bkp = block_k // PACK
    nm = m // block_m
    # bf16 activations feed the MXU as bf16 (±1 is exact there, products
    # accumulate in fp32); anything wider runs fp32
    cdt = jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32
    xp = x.astype(cdt).reshape(m, kp, PACK).transpose(2, 0, 1)
    vcp = (v_col.astype(jnp.float32).reshape(nslots, kp, PACK)
           .transpose(0, 2, 1)[:, :, None])
    vidx = vidx.astype(jnp.int32)
    used = (vidx.reshape(nm, block_m, 1)
            == jnp.arange(nslots, dtype=jnp.int32)).any(axis=1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nm, n // block_n, kp // bkp, nslots),
        in_specs=[
            pl.BlockSpec((block_m, 1), lambda i, j, kk, s, u: (i, 0)),
            pl.BlockSpec((PACK, block_m, bkp),
                         lambda i, j, kk, s, u: (0, i, kk)),
            pl.BlockSpec((1, block_n, bkp),
                         lambda i, j, kk, s, u: (s, j, kk)),
            pl.BlockSpec((1, 1, block_n), lambda i, j, kk, s, u: (s, 0, j)),
            pl.BlockSpec((1, PACK, 1, bkp),
                         lambda i, j, kk, s, u: (s, 0, 0, kk)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, kk, s, u: (i, j)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(used.reshape(-1).astype(jnp.int32), vidx.reshape(m, 1), xp, packed,
      v_row.astype(jnp.float32).reshape(nslots, 1, n), vcp)
