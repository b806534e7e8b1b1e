"""Seed-built deployments (launch/build.py) and the sliced base fingerprint.

``launch/build.py`` makes the base in its serving form leaf by leaf and
draws variants as packed artifacts, so a published-width model never needs
an fp32 copy or a dense fine-tune.  These tests pin its contract at reduced
size: the shapes and dtypes the model declares, non-target leaves that are
the model's own init, a memory plan that matches the bytes built, and a
Deployment that serves a base which arrives already quantized.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import delta as D
from repro.core import store as S
from repro.core.calibration import flatten_params
from repro.core.quantize import QuantWeight
from repro.launch import build as B
from repro.models import build_model
from repro.models.param import split
from repro.serving import Deployment


@pytest.fixture(scope="module")
def model():
    return build_model(get_config("deepseek-7b").reduced())


@pytest.mark.parametrize("base_dtype", ["fp", "int8"])
def test_init_base_shapes_and_dtypes(model, base_dtype):
    params, axes, shardings = B.init_base(model, 3, base_dtype=base_dtype)
    assert shardings is None
    flat = flatten_params(params)
    want, _, want_axes = B.base_struct(model, 3)
    assert set(flat) == set(want)
    assert flatten_params(axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == flatten_params(want_axes, is_leaf=lambda x: isinstance(x, tuple))
    targets = set(B.target_paths(want))
    assert targets
    for p, leaf in flat.items():
        assert tuple(leaf.shape) == tuple(want[p].shape), p
        if p in targets and base_dtype == "int8":
            assert isinstance(leaf, QuantWeight)
            assert leaf.q.dtype == jnp.int8
            assert leaf.scale.shape == want[p].shape[:-1]
        else:
            assert leaf.dtype == jnp.bfloat16, p
    # targets carry the init's fan-in scale, per layer
    w = flat["layers.mlp.w_down"]
    w = (w.q.astype(jnp.float32) * w.scale[..., None].astype(jnp.float32)
         if base_dtype == "int8" else w.astype(jnp.float32))
    std = float(jnp.std(w))
    assert 0.8 < std * math.sqrt(w.shape[-1]) < 1.2


def test_init_base_non_targets_are_the_model_init(model):
    params, _, _ = B.init_base(model, 5, base_dtype="int8")
    ref = flatten_params(split(model.init(jax.random.PRNGKey(5)))[0])
    flat = flatten_params(params)
    for p in set(ref) - set(B.target_paths(ref)):
        np.testing.assert_array_equal(
            np.asarray(flat[p], np.float32),
            np.asarray(ref[p].astype(jnp.bfloat16), np.float32), err_msg=p)


@pytest.mark.parametrize("base_dtype", ["fp", "int8"])
def test_memory_plan_matches_built_bytes(model, base_dtype):
    params, _, _ = B.init_base(model, 0, base_dtype=base_dtype)
    built = sum(int(x.size) * x.dtype.itemsize
                for x in jax.tree.leaves(params))
    plan = B.memory_plan(model, base_dtype=base_dtype, bank_size=3,
                         kv_tokens=10)
    assert plan["base"] == built
    flat, _, _ = B.base_struct(model)
    dm = B.random_variant(flat, 1)
    slot = sum(e.packed.nbytes + e.v_row.nbytes + e.v_col.nbytes
               for e in dm.deltas.values())
    assert plan["slot"] == slot
    cache = model.init_cache(1, 10)
    kv = sum(int(x.size) * x.dtype.itemsize for x in jax.tree.leaves(cache)
             if x.ndim > 1)
    assert plan["kv_per_token"] * 10 == kv
    assert plan["total"] == plan["base"] + 3 * plan["slot"] + 10 * \
        plan["kv_per_token"]


def test_random_variant_layout(model):
    flat, _, _ = B.base_struct(model)
    a, b = B.random_variant(flat, 7), B.random_variant(flat, 7)
    assert set(a.deltas) == set(B.target_paths(flat)) and not a.extras
    for p, e in a.deltas.items():
        *lead, n, k = flat[p].shape
        assert e.packed.shape == (*lead, n, k // 8)
        assert e.packed.dtype == np.uint8
        assert e.v_row.shape == (*lead, n) and e.v_row.dtype == np.float16
        assert e.v_col.shape == (*lead, k) and e.v_col.dtype == np.float16
        assert e.use_row.shape == tuple(lead)
        np.testing.assert_array_equal(e.packed, b.deltas[p].packed)
        # the packed bytes round-trip through the store's sign layout
        signs = D.unpack_signs(jnp.asarray(e.packed), k)
        np.testing.assert_array_equal(
            np.asarray(D.pack_signs(signs > 0)), e.packed)
    nxt = B.perturb_variant(a, 1)
    e0, e1 = a.deltas["layers.mlp.w_up"], nxt.deltas["layers.mlp.w_up"]
    changed = np.mean(e0.packed != e1.packed)
    assert 0 < changed < 0.02


def test_deployment_serves_prebuilt_int8_base(model):
    params, _, _ = B.init_base(model, 0, base_dtype="int8")
    dep = Deployment(model, params, batch_size=2, prompt_len=8, max_len=16,
                     bank_size=3, base_dtype="int8")
    assert dep.registry.quant_stats["targets"] == len(
        B.target_paths(B.base_struct(model)[0]))
    flat, _, _ = B.base_struct(model)
    dep.publish("v", B.random_variant(flat, 1))
    rids = [dep.submit(np.arange(1, 9), variant=v, max_new_tokens=3)
            for v in ("v", "__base__")]
    dep.drain()
    assert dep.metrics["failed"] == 0
    assert all(len(dep.result(r).out_tokens) == 3 for r in rids)
    dep.close()


@pytest.mark.parametrize("shape", [(), (1,), (70,), (5, 7), (2, 300),
                                   (30, 2, 16), (3000, 2, 8), (4, 4, 4, 4),
                                   (0, 5), (400_000,), (600, 1000),
                                   (40, 100, 100), (20000, 2, 8)])
def test_fingerprint_head_is_the_flat_prefix(shape):
    """The fingerprint reads a leaf's first 64 row-major values through a
    slice taken where the leaf lives — the same bytes as flattening the
    whole leaf on the host."""
    x = np.random.default_rng(0).standard_normal(shape).astype(np.float32)
    want = x.ravel()[:64]
    np.testing.assert_array_equal(S._head(jnp.asarray(x)), want)
    np.testing.assert_array_equal(S._head(x), want)
