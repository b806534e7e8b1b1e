"""1-bit per-axis gradient compression with error feedback (beyond-paper).

The paper's representation — sign mask + per-axis scale — applied to
*gradients* for cross-pod data parallelism: within a pod, gradients reduce
in full precision over fast ICI; across pods (slow DCN), each pod
exchanges sign(g)+per-row scale: 16× less DCN traffic per step.  Error
feedback (residual carried to the next step) keeps SGD convergence —
standard 1-bit Adam / EF-signSGD theory.

Two entry points:
* ``make_ef_transform`` — a ``grad_transform`` hook for train.step that
  quantises+dequantises gradients with persistent error feedback
  (simulates the cross-pod wire format end-to-end; used by tests to show
  convergence is preserved).
* ``compressed_psum`` — the actual wire exchange as a shard_map collective
  over a mesh axis: pack → all_gather(packed + scales) → decompress →
  mean.  Wire bytes ≈ bits/16 of the fp32 exchange.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import delta as D


def _compressible(g: jax.Array) -> bool:
    return g.ndim >= 2 and g.shape[-1] % 8 == 0


def quantize(g: jax.Array) -> tuple[jax.Array, jax.Array]:
    """g -> (packed sign bits, per-row fp16 scale).  Per-axis scale over
    the last dim (row mode on (..., rows, cols))."""
    gf = g.astype(jnp.float32)
    packed = D.pack_signs(D.sign_mask(gf))
    scale = jnp.mean(jnp.abs(gf), axis=-1).astype(jnp.float16)
    return packed, scale


def dequantize(packed: jax.Array, scale: jax.Array, d_last: int
               ) -> jax.Array:
    signs = D.unpack_signs(packed, d_last, jnp.float32)
    return scale.astype(jnp.float32)[..., None] * signs


def wire_bytes(g: jax.Array) -> tuple[int, int]:
    """(compressed, fp32) bytes for one tensor's cross-pod exchange."""
    if not _compressible(g):
        return 4 * g.size, 4 * g.size
    comp = g.size // 8 + 2 * int(g.size // g.shape[-1])
    return comp, 4 * g.size


def make_ef_transform():
    """Returns (transform(grads, ef_state) -> (grads, ef_state), init_fn).

    transform quantises each compressible leaf of (g + e), dequantises,
    and carries the residual e' = (g + e) − deq — exactly what each pod
    would send/receive across DCN.
    """
    def init(grads_template):
        return jax.tree.map(
            lambda g: jnp.zeros_like(g, dtype=jnp.float32)
            if _compressible(g) else None, grads_template)

    def transform(grads, ef):
        def one(g, e):
            if not _compressible(g):
                return g, None
            tot = g.astype(jnp.float32) + (e if e is not None else 0.0)
            packed, scale = quantize(tot)
            deq = dequantize(packed, scale, g.shape[-1])
            return deq.astype(g.dtype), tot - deq
        flat_g, treedef = jax.tree.flatten(grads)
        flat_e = treedef.flatten_up_to(ef)
        out = [one(g, e) for g, e in zip(flat_g, flat_e)]
        new_g = treedef.unflatten([o[0] for o in out])
        new_e = treedef.unflatten([o[1] for o in out])
        return new_g, new_e

    return transform, init


def compressed_psum(g: jax.Array, axis_name: str) -> jax.Array:
    """Mean of ``g`` across ``axis_name`` exchanging only (packed signs,
    fp16 scales).  Call inside shard_map; g is this shard's local value.
    """
    if not _compressible(g):
        return jax.lax.pmean(g, axis_name)
    packed, scale = quantize(g)
    all_packed = jax.lax.all_gather(packed, axis_name)    # (P, ..., cols/8)
    all_scale = jax.lax.all_gather(scale, axis_name)
    deq = dequantize(all_packed, all_scale, g.shape[-1])  # (P, ..., cols)
    return jnp.mean(deq, axis=0).astype(g.dtype)


def cross_pod_grad_mean(grads, mesh, axis_name: str = "pod"):
    """Apply compressed_psum leaf-wise over the pod axis (grads replicated
    within pod, differing across pods)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    def fn(*leaves):
        return tuple(compressed_psum(l, axis_name) for l in leaves)

    flat, treedef = jax.tree.flatten(grads)
    specs = tuple(P() for _ in flat)  # replicated per pod-shard
    out = shard_map(fn, mesh=mesh, in_specs=specs, out_specs=specs,
                    check_vma=False)(*flat)
    return jax.tree.unflatten(treedef, list(out))
