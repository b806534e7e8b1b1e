"""Weights and variants made from ``--seed``, in the form they are served.

The benchmark makes every number the model uses; the program is handed
them and the reference reads the same arrays, so the reference takes
nothing the program made.  Leaves are named by their dot-path in the
served parameter tree (``layers.attn.wq``, ``pre_layers.mlp.w_gate``,
``layers.moe.w_up``, ...):

* delta targets (the attention and MLP projections, experts included) are
  int8 with one fp16 scale per output channel: ``q`` uniform over
  [-127, 127] and ``scale`` = fan_in^-1/2 / std(q), jittered by
  exp(0.2·N(0,1)) per channel;
* norm scales are 1 + 0.1·N(0,1), the embedding N(0,1), the unembedding
  and the router N(0, 1/d), all bf16;
* a variant is, per target, a random packed sign plane (bit j of byte i of
  a row is column 8i+j, 1 meaning +1), fp16 ``v_row`` and ``v_col`` of
  magnitude 0.05·fan_in^-1/2·|N(0,1)|, and a per-matrix choice of axis.

The base is made on the device in one jitted call; each variant in one
jitted call and then copied to the host, where the program's publish path
takes it from.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TARGET_NAMES = frozenset({"wq", "wk", "wv", "wo", "w_gate", "w_up",
                          "w_down"})
NORM_NAMES = frozenset({"ln1", "ln2", "final_norm", "q_norm", "k_norm"})
PACK = 8
Q_STD = float(np.sqrt((255 ** 2 - 1) / 12))   # std of uniform [-127, 127]
VEC_SCALE = 0.05
SLICE_BYTES = 256 << 20         # leaves above this are drawn per slice


def is_target(path: str, shape: tuple) -> bool:
    return (path.split(".")[-1] in TARGET_NAMES and len(shape) >= 2
            and shape[-1] % PACK == 0)


def seed_key(seed: int, salt: int):
    """A PRNG key from a seed of any size (more than 32 bits hold)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 62) & 0x7FFFFFFF)
    return jax.random.fold_in(key, salt)


def _sliced(draw, key, shape: tuple, itemsize: int):
    """``draw(key, shape)``, one leading slice at a time for big leaves,
    so that no temporary of a whole stack exists."""
    size = int(np.prod(shape)) * itemsize
    if len(shape) < 3 or size <= SLICE_BYTES:
        return draw(key, shape)
    keys = jax.random.split(key, shape[0])
    inner = shape[1:]
    return jax.lax.map(lambda k: _sliced(draw, k, inner, itemsize), keys)


def _int8(key, shape):
    q = jax.lax.bitcast_convert_type(
        jax.random.bits(key, shape, jnp.uint8), jnp.int8)
    return jnp.maximum(q, jnp.int8(-127))


def _leaf(path: str, shape: tuple, key):
    name = path.split(".")[-1]
    d = shape[-1]
    if is_target(path, shape):
        kq, ks = jax.random.split(key)
        q = _sliced(_int8, kq, shape, 1)
        jitter = jnp.exp(0.2 * jax.random.normal(ks, shape[:-1]))
        scale = (d ** -0.5 / Q_STD * jitter).astype(jnp.float16)
        return {"q": q, "scale": scale}
    if name in NORM_NAMES:
        w = 1.0 + 0.1 * jax.random.normal(key, shape)
    elif name == "embed":
        w = jax.random.normal(key, shape)
    elif name in ("unembed", "router"):
        w = d ** -0.5 * jax.random.normal(key, shape)
    else:
        raise ValueError(f"no rule to make leaf {path!r} {shape}")
    return w.astype(jnp.bfloat16)


def make_base(shapes: dict, seed: int) -> dict:
    """{path -> array | {"q", "scale"}} on the device, one jitted call.
    ``shapes`` maps each served leaf's path to its shape."""
    paths = sorted(shapes)

    def gen(key):
        return {p: _leaf(p, tuple(shapes[p]), jax.random.fold_in(key, i))
                for i, p in enumerate(paths)}

    return jax.jit(gen)(seed_key(seed, 0))


def variant_paths(shapes: dict) -> list:
    return sorted(p for p, s in shapes.items() if is_target(p, tuple(s)))


def _variant_leaf(shape: tuple, key):
    *lead, n, k = shape
    lead = tuple(lead)
    kp, kr, kc, ku = jax.random.split(key, 4)
    packed = _sliced(lambda kk, s: jax.random.bits(kk, s, jnp.uint8), kp,
                     lead + (n, k // PACK), 1)
    std = VEC_SCALE * k ** -0.5
    v_row = (std * jnp.abs(jax.random.normal(kr, lead + (n,)))
             ).astype(jnp.float16)
    v_col = (std * jnp.abs(jax.random.normal(kc, lead + (k,)))
             ).astype(jnp.float16)
    use_row = jax.random.bernoulli(ku, 0.5, lead)
    return {"packed": packed, "v_row": v_row, "v_col": v_col,
            "use_row": use_row}


@functools.lru_cache(maxsize=8)
def _variant_gen(items: tuple):
    shapes = dict(items)
    paths = variant_paths(shapes)

    def gen(key):
        return {p: _variant_leaf(tuple(shapes[p]), jax.random.fold_in(key, i))
                for i, p in enumerate(paths)}
    return jax.jit(gen)


def make_variant(shapes: dict, seed: int, index: int) -> dict:
    """{path -> {"packed", "v_row", "v_col", "use_row"}} as host numpy
    arrays: variant ``index`` of this seed, drawn on the device in one
    jitted call and copied to the host."""
    gen = _variant_gen(tuple(sorted((p, tuple(s)) for p, s in
                                    shapes.items())))
    dev = gen(seed_key(seed, 1 + index))
    host = jax.device_get(dev)
    del dev
    return {p: {k: np.asarray(v) for k, v in e.items()}
            for p, e in host.items()}
