"""Kernel accounting: unpack_apply + bitlinear vs dense baselines.

Interpret-mode wall time on CPU is not a device time, so the *derived*
column carries only what shapes determine: HBM bytes per op for the
memory-bound regimes the kernels target (decode GEMV, loader
reconstruction).  Correctness vs ref.py is asserted inline.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, timeit
from repro.core import delta as D
from repro.kernels import ops as K
from repro.kernels import ref as R


def _case(d_out, d_in, mode="row"):
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    wb = (0.1 * jax.random.normal(k1, (d_out, d_in))).astype(jnp.bfloat16)
    dw = 0.01 * jax.random.normal(k2, (d_out, d_in))
    packed = D.pack_signs(D.sign_mask(dw))
    v = D.init_scale(dw, mode).astype(jnp.float32)
    return packed, v, wb


def run() -> list:
    out = []
    d_out, d_in = 1024, 1024
    packed, v, wb = _case(d_out, d_in)

    got = K.unpack_apply(packed, v, wb, mode="row", out_dtype=jnp.float32)
    want = R.unpack_apply_ref(packed, v, wb, "row", dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    t = timeit(lambda: jax.block_until_ready(
        K.unpack_apply(packed, v, wb, mode="row")), n=3)
    # loader path: reads Wb (2B/elt) + mask (1/8 B/elt), writes 2B/elt
    bytes_moved = d_out * d_in * (2 + 2 + 1 / 8)
    out.append(row("kernel/unpack_apply_1024sq", t * 1e6,
                   f"hbm_bytes={int(bytes_moved)};"
                   f"vs_dense_copy={(d_out*d_in*4)/bytes_moved:.2f}x"))

    m = 8  # decode GEMV regime
    x = (0.1 * jax.random.normal(jax.random.PRNGKey(3), (m, d_in))
         ).astype(jnp.bfloat16)
    got = K.bitlinear(x, packed, v, wb, mode="row")
    want = R.bitlinear_ref(x, packed, v, wb, "row")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)
    t = timeit(lambda: jax.block_until_ready(
        K.bitlinear(x, packed, v, wb, mode="row")), n=3)
    # decode is weight-traffic bound: fused = Wb + mask; two-pass dense
    # (reconstruct variant then matmul) = 2 reads + 1 write of W
    fused_bytes = d_out * d_in * (2 + 1 / 8)
    swap_bytes = d_out * d_in * (2 + 2 + 1 / 8) + d_out * d_in * 2
    out.append(row("kernel/bitlinear_decode8", t * 1e6,
                   f"fused_hbm={int(fused_bytes)};"
                   f"vs_dense_reswap={swap_bytes/fused_bytes:.2f}x;"
                   f"delta_overhead_vs_base_only={(fused_bytes)/(d_out*d_in*2):.3f}x"))
    return out


if __name__ == "__main__":
    print("\n".join(run()))
