"""Pure-jnp oracles for the Pallas kernels.

Every kernel in this package must match its oracle here to within dtype
tolerance (tests/test_kernels.py sweeps shapes/dtypes/modes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import delta as D


def _deq(w_base: jax.Array, w_scale) -> jax.Array:
    """Dense-dequant the int8 base when a per-output-channel scale rides
    along (``w_scale`` (d_out,), broadcast over the contracted dim) —
    the oracle twin of the kernels' in-tile dequant."""
    wb = w_base.astype(jnp.float32)
    if w_scale is not None:
        wb = wb * w_scale.astype(jnp.float32)[..., None]
    return wb


def unpack_apply_ref(packed: jax.Array, v: jax.Array, w_base: jax.Array,
                     mode: str, dtype=jnp.float32,
                     w_scale: jax.Array = None) -> jax.Array:
    """Ŵ = v ⊙ unpack(B) + W_b  — dense reconstruction oracle."""
    return D.reconstruct(packed, v, _deq(w_base, w_scale), mode, dtype=dtype)


def bitlinear_ref(x: jax.Array, packed: jax.Array, v: jax.Array,
                  w_base: jax.Array, mode: str,
                  w_scale: jax.Array = None) -> jax.Array:
    """y = x @ (v ⊙ unpack(B) + W_b)ᵀ — fused delta-GEMM oracle.

    Computed the *dense* way (reconstruct then matmul) in fp32 so the oracle
    is unambiguous; the kernel accumulates in fp32 too.
    """
    w_hat = D.reconstruct(packed, v, _deq(w_base, w_scale), mode,
                          dtype=jnp.float32)
    return (x.astype(jnp.float32) @ w_hat.T).astype(x.dtype)


def bitlinear_axes_ref(x: jax.Array, packed: jax.Array, v_row: jax.Array,
                       v_col: jax.Array, w_base: jax.Array,
                       w_scale: jax.Array = None) -> jax.Array:
    """Dual-axis oracle: v[n,k] = v_row[n] + v_col[k] (overlay convention:
    the unselected vector is zero, so the sum IS the selected scale)."""
    d_out, d_in = w_base.shape
    signs = D.unpack_signs(packed, d_in, jnp.float32)
    v = (v_row.astype(jnp.float32)[:, None]
         + v_col.astype(jnp.float32)[None, :])
    w_hat = v * signs + _deq(w_base, w_scale)
    return (x.astype(jnp.float32) @ w_hat.T).astype(x.dtype)


def bitlinear_axes_banked_ref(x: jax.Array, variant_idx: jax.Array,
                              packed: jax.Array, v_row: jax.Array,
                              v_col: jax.Array, w_base: jax.Array,
                              w_scale: jax.Array = None) -> jax.Array:
    """Banked oracle: overlay operands carry a leading bank axis V; each row
    of x computes against the bank slot named by variant_idx (slot 0 = base:
    its vectors are zero, so Ŵ[0] = W_b exactly).

    x (M, K) · variant_idx (M,) int32 · packed (V, N, K/8) · v_row (V, N) ·
    v_col (V, K) · w_base (N, K) -> (M, N).
    """
    d_out, d_in = w_base.shape
    signs = D.unpack_signs(packed, d_in, jnp.float32)        # (V, N, K)
    v = (v_row.astype(jnp.float32)[:, :, None]
         + v_col.astype(jnp.float32)[:, None, :])
    w_hat = v * signs + _deq(w_base, w_scale)[None]          # (V, N, K)
    # every row against every slot, then each row keeps its own slot's
    # result: a (V, M, N) product instead of an (M, N, K) per-row gather
    y_all = jnp.einsum("vnk,mk->vmn", w_hat, x.astype(jnp.float32))
    y = jnp.take_along_axis(y_all, variant_idx[None, :, None], axis=0)[0]
    return y.astype(x.dtype)
