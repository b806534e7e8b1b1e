"""Pallas TPU kernel: forward flash attention (serving path).

The §Roofline tables show prefill cells are memory-bound, dominated by
attention logit traffic: the jnp-level flash scan materialises one
(S × chunk) fp32 logit block per step through HBM (dot output + softmax
reduce reads + second dot input ≈ 4 passes).  This kernel keeps the logit
block, the online-softmax statistics and the output accumulator resident
in VMEM — HBM traffic drops to reading Q/K/V once and writing O once, the
flash-attention ideal.  Napkin (qwen3 prefill_32k, per device): logits
traffic ≈ 2.4 s of the 3.6 s memory term → kernel-resident logits bring
the memory term toward ≈1.2 s (weights+activations), ≈3× on that term.

Forward-only by design: training keeps the custom-VJP jnp path
(models/attention.py); serving (prefill) has no backward.

Layout: q (BH, S, hd) · k/v (BH_kv, T, hd) — heads flattened into the
leading dim by ops.py; GQA handled by index-mapping each q head to its
kv head (bh // group).  Grid (BH, S/bq, T/bk), causal masking by absolute
positions, fp32 accumulation in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, bq: int, bk: int, n_k: int,
            q_offset: int, kv_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale              # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                      # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    q_pos = q_offset + qi * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    k_pos = kv_offset + ki * bk + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 1)
    if causal:
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    m_scr[...] = m_new
    v = v_ref[0].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0, ...] = (acc_scr[...]
                         / jnp.maximum(l_scr[...], 1e-30)[:, None]
                         ).astype(o_ref.dtype)


def flash_attention_fwd_p(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          group: int, causal: bool = True,
                          q_offset: int = 0, kv_offset: int = 0,
                          block_q: int = 128, block_k: int = 128,
                          interpret: bool) -> jax.Array:
    """q (BH, S, hd); k/v (BH//group, T, hd) -> o (BH, S, hd)."""
    bh, s, hd = q.shape
    _, t, _ = k.shape
    bq = min(block_q, s)
    while s % bq:
        bq //= 2
    bk = min(block_k, t)
    while t % bk:
        bk //= 2
    n_k = t // bk
    grid = (bh, s // bq, n_k)

    from jax.experimental.pallas import tpu as pltpu
    scratch = [pltpu.VMEM((bq,), jnp.float32),
               pltpu.VMEM((bq,), jnp.float32),
               pltpu.VMEM((bq, hd), jnp.float32)]

    kernel = functools.partial(
        _kernel, scale=hd ** -0.5, causal=causal, bq=bq, bk=bk, n_k=n_k,
        q_offset=q_offset, kv_offset=kv_offset)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)
