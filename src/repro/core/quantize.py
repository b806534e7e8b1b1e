"""Symmetric per-channel int8 quantization of shadowed base weights.

The second base-weight dtype (DESIGN.md §16): every target matrix the
1-bit delta machinery shadows can be held resident as int8 + one fp16
scale per output channel instead of full precision, halving (or better)
resident base HBM per device.  The fused Pallas kernels dequantize the
int8 base tile and apply the unpacked ±1 sign plane × v_row⊕v_col delta
in the SAME tile pass — the dense fp Ŵ (and the dense fp base) is never
written to HBM.

Scale layout: symmetric per-OUTPUT-channel.  For a weight stack
``W[..., d_out, d_in]``::

    scale[..., n] = max_k |W[..., n, k]| / 127          (fp16)
    q[..., n, k]  = clip(round(W[..., n, k] / scale), -127, 127)  (int8)

Per-output-channel (not per-input-channel) so that the no-overlay plain
path factors EXACTLY without materialising a dense dequant::

    x @ W.T  ==  (x @ q.T) * scale

and so the kernel's in-tile dequant broadcast is a cheap (bn, 1) column
read per (bn, bk) weight tile.

``QuantWeight`` is a registered pytree that duck-types ``.shape`` /
``.ndim`` / ``.dtype`` after its int8 payload, so shape-level consumers
(``calibration.is_target``, overlay struct builders) treat it like the
array it replaces.  Tree flattening treats it as a LEAF via the
``__quant_leaf__`` marker (``calibration.flatten_params`` checks the
attribute, not the class — no import cycle).

The same threading (one extra per-channel operand through kernels,
dispatch, loader, registry) is what unlocks an fp8 base later: only
``quantize_weight`` and the in-tile ``astype`` change.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

# floor keeps all-zero channels from dividing by zero; any q on such a
# channel is 0 anyway so the floor value never reaches the output
_SCALE_FLOOR = 1e-8


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantWeight:
    """One quantized base weight (stack): int8 payload + fp16 per-output-
    channel scales.  A pytree of two leaves; flattened as ONE leaf by the
    params flatteners (``__quant_leaf__``)."""
    q: jax.Array                 # (..., d_out, d_in) int8
    scale: jax.Array             # (..., d_out) fp16

    __quant_leaf__ = True

    # duck-type the array the QuantWeight replaces: shape-level consumers
    # (is_target, overlay_struct, entry ndim checks) read these three
    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def dtype(self):
        return self.q.dtype

    def nbytes(self) -> int:
        return (self.q.size * self.q.dtype.itemsize
                + self.scale.size * self.scale.dtype.itemsize)


def is_quant(x) -> bool:
    """True for QuantWeight instances (marker-based, matches the duck
    check used by ``calibration.flatten_params``)."""
    return isinstance(x, QuantWeight)


def quantize_weight(w: jax.Array) -> QuantWeight:
    """Symmetric per-output-channel int8 quantization of one weight
    (stack).  Scales calibrate from the weight itself (abs-max)."""
    w32 = jnp.asarray(w).astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w32), axis=-1) / 127.0, _SCALE_FLOOR)
    q = jnp.clip(jnp.round(w32 / s[..., None]), -127, 127).astype(jnp.int8)
    return QuantWeight(q=q, scale=s.astype(jnp.float16))


def dequantize(qw: QuantWeight, dtype=jnp.float32) -> jax.Array:
    """Dense dequant — OFF the serving hot path (used by the dense
    residency mode, ref oracles and round-trip tests only)."""
    return (qw.q.astype(jnp.float32)
            * qw.scale.astype(jnp.float32)[..., None]).astype(dtype)


def quant_sharding(weight_sharding, w_ndim: int):
    """QuantWeight-of-NamedSharding for one quantized leaf by spec
    surgery on the fp weight's resolved sharding: the int8 payload keeps
    the weight's placement verbatim, the scale vector keeps the spec
    entries of the dims it copies ((lead..., d_out)) — the same surgery
    ``delta_overlay.entry_shardings_from_weight`` applies to v_row.
    Returns the input unchanged when it carries no inspectable spec
    (single-device placements)."""
    try:
        from jax.sharding import NamedSharding, PartitionSpec
        spec = list(weight_sharding.spec) + [None] * w_ndim
        spec = spec[:w_ndim]
        return QuantWeight(
            q=weight_sharding,
            scale=NamedSharding(weight_sharding.mesh,
                                PartitionSpec(*spec[:-1])))
    except Exception:
        return weight_sharding


def quantize_base(params, param_shardings=None):
    """Quantize every shadowed target weight of a base params tree.

    Returns ``(qparams, qshardings, stats)``: the params tree with
    target leaves replaced by :class:`QuantWeight` (non-targets
    untouched — embeddings, norms, convs stay full precision), the
    matching shardings tree with target leaves upgraded via
    :func:`quant_sharding` (None in, None out), and a byte accounting
    dict (``fp_bytes`` / ``int8_bytes`` / ``ratio`` over targets).

    Targets that arrive already quantized (a base built int8 from the
    start, ``launch/build.init_base``) pass through with their shardings;
    their ``fp_bytes`` count the bf16 weight they stand for."""
    from repro.core.calibration import (flatten_params, is_target,
                                        unflatten_like)
    flat = flatten_params(params)
    targets = {p for p, l in flat.items() if is_target(p, l)}
    fp_bytes = q_bytes = 0
    out = {}
    for path, leaf in flat.items():
        if path in targets:
            if is_quant(leaf):
                qw = leaf
                fp_bytes += leaf.q.size * 2
            else:
                qw = quantize_weight(leaf)
                fp_bytes += leaf.size * leaf.dtype.itemsize
            q_bytes += qw.nbytes()
            out[path] = qw
        else:
            out[path] = leaf
    qparams = unflatten_like(params, out)
    qsh = None
    if param_shardings is not None:
        sflat = flatten_params(param_shardings)
        for path in targets:
            if not is_quant(sflat[path]):
                sflat[path] = quant_sharding(sflat[path], flat[path].ndim)
        qsh = unflatten_like(param_shardings, sflat)
    stats = {"targets": len(targets), "fp_bytes": int(fp_bytes),
             "int8_bytes": int(q_bytes),
             "ratio": q_bytes / max(fp_bytes, 1)}
    return qparams, qsh, stats


def quantize_struct(flat_shapes: dict, paths) -> dict:
    """Abstract twin of :func:`quantize_base` over a flat {path ->
    array | ShapeDtypeStruct} view: target leaves become QuantWeight-of-
    ShapeDtypeStruct (dry-run serving cells, AOT in_shardings)."""
    out = dict(flat_shapes)
    for p in paths:
        w = flat_shapes[p]
        out[p] = QuantWeight(
            q=jax.ShapeDtypeStruct(tuple(w.shape), jnp.int8),
            scale=jax.ShapeDtypeStruct(tuple(w.shape[:-1]), jnp.float16))
    return out
