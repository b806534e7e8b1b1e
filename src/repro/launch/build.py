"""Build a deployment at published width from a seed: base and variants.

What a serving node holds is one base model and many packed delta
variants.  Building both the way training would — an fp32 base, a dense
fine-tuned copy per variant, then compression — needs several whole
models of memory at once, which no accelerator holds at published widths
(deepseek-7b: 27.6 GB of fp32 base on a 16 GB chip).  This module builds
them directly in their serving form, one leaf at a time:

* :func:`init_base` — the base in its resident dtype.  Non-target leaves
  (embeddings, norms, routers) come from the model's own init cast to
  bf16; every delta target is drawn layer by layer inside a ``lax.map``
  and stored bf16 or int8 (``core/quantize.py``) straight away, so no
  fp32 copy of a stack ever exists;
* :func:`random_variant` — a variant artifact drawn on the host: random
  packed sign planes plus small fp16 ``v_row``/``v_col`` per target, the
  exact layout ``core/delta.pack_signs`` and the store use;
* :func:`memory_plan` — the bytes a deployment will hold on the device,
  reckoned from shapes before anything is allocated.

``launch/serve.py`` and ``chip_smoke.py`` share these.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantize as Q
from repro.core.calibration import (DeltaEntry, DeltaModel, flatten_params,
                                    is_target, unflatten_like)
from repro.models.param import split

PACK = 8
DTYPE = jnp.bfloat16            # every floating base leaf that is not int8
VEC_SCALE = 0.05                # variant vectors, as a share of init std
FLIP_FRAC = 0.01                # sign bytes an update redraws


def base_struct(model, seed: int = 0) -> tuple:
    """(flat {path -> ShapeDtypeStruct}, abstract params tree, axes tree)
    of the model's base as its init declares it — no allocation."""
    abstract, axes = split(jax.eval_shape(model.init,
                                          jax.random.PRNGKey(seed)))
    return flatten_params(abstract), abstract, axes


def target_paths(flat: dict) -> list:
    """Delta-target paths of a flat params view, in a fixed order."""
    return sorted(p for p, leaf in flat.items() if is_target(p, leaf))


def _target_init(shape: tuple, quantize: bool):
    """key -> one target leaf in its resident form.  Stacked leaves are
    drawn one leading slice (layer) at a time under ``lax.map``: the fp32
    draw of a slice is the only full-precision copy that ever exists."""
    std = shape[-1] ** -0.5          # dense_init's fan-in scaling

    def draw(key, sub):
        w = std * jax.random.normal(key, sub, jnp.float32)
        return Q.quantize_weight(w) if quantize else w.astype(DTYPE)

    if len(shape) == 2:
        return lambda key: draw(key, shape)

    def init(key):
        keys = jax.random.split(key, shape[0])
        return jax.lax.map(lambda k: draw(k, shape[1:]), keys)
    return init


def init_base(model, seed: int = 0, *, base_dtype: str = "int8",
              mesh=None) -> tuple:
    """Random base params of ``model`` in serving form, from ``seed``.

    Returns ``(params, param_axes, param_shardings)``.  ``base_dtype``
    "int8" stores every delta target as a QuantWeight (symmetric
    per-output-channel int8 + fp16 scales), "fp" as bf16; every other
    floating leaf is bf16.  With ``mesh`` each leaf is created
    on its serving placement (``rules_for("decode")``) and
    ``param_shardings`` mirrors ``params`` (QuantWeight-of-shardings on
    quantized targets); without it, shardings are None."""
    if base_dtype not in ("fp", "int8"):
        raise ValueError(f"unknown base dtype {base_dtype!r}")
    quantize = base_dtype == "int8"
    flat, abstract, axes = base_struct(model, seed)
    targets = target_paths(flat)
    shard = None
    if mesh is not None:
        from repro.distributed.sharding import rules_for, tree_shardings
        shard = flatten_params(tree_shardings(abstract, axes,
                                              rules_for("decode"), mesh))
        if quantize:
            for p in targets:
                shard[p] = Q.quant_sharding(shard[p], flat[p].ndim)

    rest = [p for p in flat if p not in targets]

    def init_rest(key):
        # the model's own init; XLA drops the target draws nobody reads
        full = flatten_params(split(model.init(key))[0])
        return {p: (full[p].astype(DTYPE)
                    if jnp.issubdtype(full[p].dtype, jnp.floating)
                    else full[p]) for p in rest}

    key = jax.random.PRNGKey(seed)
    out = jax.jit(init_rest, out_shardings=(
        None if shard is None else {p: shard[p] for p in rest}))(key)
    tkey = jax.random.fold_in(key, 1)
    for i, p in enumerate(targets):
        fn = _target_init(tuple(flat[p].shape), quantize)
        out[p] = jax.jit(fn, out_shardings=(
            None if shard is None else shard[p]))(jax.random.fold_in(tkey, i))
    params = unflatten_like(abstract, out)
    shardings = None if shard is None else unflatten_like(abstract, shard)
    return params, axes, shardings


def random_variant(flat_shapes: dict, seed: int) -> DeltaModel:
    """A variant artifact drawn on the host from ``seed``: per target, a
    random packed sign plane (byte i of a row holds columns 8i..8i+7) and
    fp16 ``v_row``/``v_col`` with a random per-matrix axis selection.
    Vector magnitudes are ``VEC_SCALE`` of the target's init std, the
    order of a fine-tune's per-axis mean |ΔW|.  No extras: norms and
    embeddings stay the base's, so a bank slot holds only planes and
    vectors (an embed + unembed pair of extras would add
    2·vocab·d_model·2 bytes per slot)."""
    rng = np.random.default_rng(seed)
    deltas = {}
    for p in target_paths(flat_shapes):
        shape = tuple(flat_shapes[p].shape)
        *lead, n, k = shape
        lead = tuple(lead)
        nbytes = math.prod(lead) * n * (k // PACK)
        packed = np.frombuffer(rng.bytes(nbytes), np.uint8).reshape(
            lead + (n, k // PACK))
        std = VEC_SCALE * k ** -0.5
        v_row = (std * np.abs(rng.standard_normal(lead + (n,),
                                                  np.float32))
                 ).astype(np.float16)
        v_col = (std * np.abs(rng.standard_normal(lead + (k,),
                                                  np.float32))
                 ).astype(np.float16)
        use_row = rng.random(lead) < 0.5
        deltas[p] = DeltaEntry(packed=packed, v_row=v_row, v_col=v_col,
                               use_row=np.asarray(use_row))
    return DeltaModel(deltas=deltas, extras={})


def perturb_variant(dm: DeltaModel, seed: int) -> DeltaModel:
    """The next version of a variant: a ``FLIP_FRAC`` share of its packed
    sign bytes redrawn and its vectors rescaled by up to ±5% — a small
    residual, as an incremental update ships."""
    rng = np.random.default_rng(seed)
    deltas = {}
    for p, e in dm.deltas.items():
        packed = np.array(e.packed)
        flat = packed.reshape(-1)
        idx = rng.integers(0, flat.size,
                           size=max(1, int(FLIP_FRAC * flat.size)))
        flat[idx] = rng.integers(0, 256, size=idx.size, dtype=np.uint8)
        scale = 1 + 0.05 * rng.uniform(-1, 1)
        deltas[p] = DeltaEntry(
            packed=packed,
            v_row=(np.asarray(e.v_row, np.float32) * scale
                   ).astype(np.float16),
            v_col=(np.asarray(e.v_col, np.float32) * scale
                   ).astype(np.float16),
            use_row=e.use_row)
    return DeltaModel(deltas=deltas, extras=dict(dm.extras))


def memory_plan(model, *, base_dtype: str, bank_size: int,
                kv_tokens: int) -> dict:
    """Device bytes a deployment of ``model`` holds, from shapes alone:
    ``base`` (targets int8 + fp16 scales, or bf16; other leaves bf16),
    ``slot`` (one bank slot: packed planes + fp16 vectors),
    ``kv_per_token`` (bf16 K and V of every layer plus the slot-position
    entry) and ``total`` for ``bank_size`` slots and ``kv_tokens``
    cached tokens.  Activations and compiler temporaries are not in it."""
    flat, _, _ = base_struct(model)
    targets = set(target_paths(flat))
    item = jnp.dtype(DTYPE).itemsize
    base = slot = 0
    for p, leaf in flat.items():
        size = math.prod(leaf.shape)
        if p in targets:
            *lead, n, k = leaf.shape
            base += (size + 2 * math.prod(lead) * n if base_dtype == "int8"
                     else size * item)
            slot += size // PACK + 2 * math.prod(lead) * (n + k)
        else:
            base += size * (item if jnp.issubdtype(leaf.dtype, jnp.floating)
                            else jnp.dtype(leaf.dtype).itemsize)
    cache = jax.eval_shape(lambda: model.init_cache(1, 1))
    kv_per_token = sum(math.prod(leaf.shape) * leaf.dtype.itemsize
                       for leaf in jax.tree.leaves(cache)
                       if leaf.ndim > 1)
    return {"base": base, "slot": slot, "kv_per_token": kv_per_token,
            "total": base + bank_size * slot + kv_tokens * kv_per_token}
