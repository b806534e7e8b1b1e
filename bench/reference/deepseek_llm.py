"""Plain float32 reference of a DeepSeek-LLM (LLaMA-style) decoder.

Follows the published description (arXiv:2401.02954; the model's
``config.json``): token embedding; per layer a pre-norm multi-head
attention block with rotary embeddings on the two halves of each head and
a causal softmax, then a pre-norm SwiGLU MLP, each added to the residual
stream; a final RMSNorm and an untied unembedding.  Departures: none.

A served variant's weight is Ŵ = W + v ⊙ S, with W the int8 base times its
per-output-channel scale, S the ±1 sign plane and v the selected per-axis
vector (``v_row[n]`` on every column of row n, or ``v_col[k]`` on every row
of column k).  Ŵ is rebuilt densely here, one layer at a time.

Imports nothing of the program.  Weights come as the benchmark made them
(``bench/weights.py``): a flat {dot-path -> array} dict, int8 targets as
{"q", "scale"}, variant targets as {"packed", "v_row", "v_col",
"use_row"} host arrays.  ``precision="int4"`` is the control: the same
computation with every int8 base matrix requantized to int4 per output
channel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dequant(w: dict, precision: str) -> jax.Array:
    q = w["q"].astype(F32)
    s = w["scale"].astype(F32)
    if precision == "int4":
        m = jnp.maximum(jnp.max(jnp.abs(q), axis=-1, keepdims=True), 1.0)
        q = jnp.round(q * 7.0 / m)
        s = s * m[..., 0] / 7.0
    return q * s[..., None]


def unpack(packed: jax.Array) -> jax.Array:
    """(..., N, K/8) uint8 -> (..., N, K) ±1: bit j of byte i is column
    8i + j, a set bit meaning +1."""
    bits = (packed[..., None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
    return bits.reshape(*packed.shape[:-1], packed.shape[-1] * 8
                        ).astype(F32) * 2 - 1


def delta(v: dict) -> jax.Array:
    s = unpack(v["packed"])
    scale = jnp.where(v["use_row"][..., None, None],
                      v["v_row"].astype(F32)[..., :, None],
                      v["v_col"].astype(F32)[..., None, :])
    return scale * s


def weight(lw: dict, lv, name: str, precision: str) -> jax.Array:
    w = dequant(lw[name], precision)
    if lv is not None:
        w = w + delta(lv[name])
    return w


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def rope(x, theta):
    """x (T, H, hd): rotate the halves of each head by position."""
    t, _, hd = x.shape
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(t, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, lw, lv, conf, precision):
    t = x.shape[0]
    h = conf["num_attention_heads"]
    kvh = conf.get("num_key_value_heads", h)
    hd = conf["hidden_size"] // h
    a = lambda n: weight(lw, lv, f"attn.{n}", precision)
    q = rope((x @ a("wq").T).reshape(t, h, hd), conf["rope_theta"])
    k = rope((x @ a("wk").T).reshape(t, kvh, hd), conf["rope_theta"])
    v = (x @ a("wv").T).reshape(t, kvh, hd)
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(t, h * hd)
    return o @ a("wo").T


def mlp(x, lw, lv, prefix, precision):
    w = lambda n: weight(lw, lv, f"{prefix}.{n}", precision)
    return (jax.nn.silu(x @ w("w_gate").T) * (x @ w("w_up").T)) @ w("w_down").T


def dense_layer(x, lw, lv, conf, precision):
    eps = conf["rms_norm_eps"]
    x = x + attention(rmsnorm(x, lw["ln1"], eps), lw, lv, conf, precision)
    return x + mlp(rmsnorm(x, lw["ln2"], eps), lw, lv, "mlp", precision)


def _layer_slice(flat: dict, group: str, i: int) -> dict:
    """The weights of layer ``i`` of a stacked group, keyed below it."""
    out = {}
    for path, w in flat.items():
        head, _, rest = path.partition(".")
        if head == group:
            out[rest] = jax.tree.map(lambda a: a[i], w)
    return out


def _device(tree):
    return None if tree is None else jax.tree.map(jnp.asarray, tree)


def run_layers(x, weights, variant, conf, precision, group, layer_fn):
    """Apply every layer of ``group`` in turn, one jitted call per layer."""
    n = next(np.shape(w["q"] if isinstance(w, dict) else w)[0]
             for p, w in weights.items() if p.startswith(group + "."))
    for i in range(n):
        lw = _layer_slice(weights, group, i)
        lv = None if variant is None else _device(
            _layer_slice(variant, group, i))
        x = layer_fn(x, lw, lv, precision)
    return x


@functools.partial(jax.jit, static_argnums=(3,))
def head_logits(x, final_norm, unembed, eps, positions):
    with jax.default_matmul_precision("highest"):
        h = rmsnorm(x[positions], final_norm, eps)
        return h @ unembed.astype(F32).T


def _jit(fn):
    def traced(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return jax.jit(traced, static_argnums=(3,))


@functools.lru_cache(maxsize=None)
def _dense_layer_jit(conf_items: tuple):
    conf = dict(conf_items)
    return _jit(lambda x, lw, lv, precision:
                dense_layer(x, lw, lv, conf, precision))


def padded(conf: dict, tokens: np.ndarray) -> jax.Array:
    """``tokens`` right-padded to the deployment's ``max_len``, so that
    every request runs the same compiled layers; under the causal mask
    the padding changes no earlier position."""
    n = int(conf["deployment"]["max_len"])
    if len(tokens) > n:
        raise ValueError(f"{len(tokens)} tokens exceed max_len {n}")
    return jnp.asarray(np.pad(np.asarray(tokens), (0, n - len(tokens))))


def conf_key(conf: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in conf.items()
                        if isinstance(v, (int, float, str, bool))))


def logits(conf: dict, weights: dict, variant, tokens: np.ndarray,
           positions: np.ndarray, precision: str = "f32") -> np.ndarray:
    """Float32 logits at ``positions`` of the causal forward over
    ``tokens`` (T,), under ``variant`` (None: the base)."""
    x = weights["embed"][padded(conf, tokens)].astype(F32)
    layer = _dense_layer_jit(conf_key(conf))
    x = run_layers(x, weights, variant, conf, precision, "layers", layer)
    return np.asarray(head_logits(x, weights["final_norm"],
                                  weights["unembed"], conf["rms_norm_eps"],
                                  jnp.asarray(positions)))
