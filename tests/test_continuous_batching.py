"""Mixed-variant continuous batching: banked kernel, overlay bank,
slot scheduler (DESIGN.md §9).

Parity contract: a heterogeneous decode batch (base + fused variants, one
``variant_idx`` per row) must match per-variant fused serving row for row —
the banked kernel computes each row's Ŵ from the same packed mask + axis
vectors, and banked extras store the same fp16-rounded values the
per-variant params view carries.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import calibration as C
from repro.core import delta as D
from repro.core import loader as L
from repro.kernels import ops as K
from repro.kernels import ref as R
from repro.models import build_model
from repro.models.param import split
from repro.serving import ServingEngine, VariantRegistry
from repro.serving.variants import OverlayBank


# ---------------------------------------------------------------------------
# banked kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,k,v", [(4, 16, 32, 2), (8, 32, 64, 5),
                                     (6, 24, 40, 3)])
def test_banked_kernel_matches_ref(m, n, k, v):
    rng = np.random.default_rng(m + n + k)
    packed = jnp.asarray(rng.integers(0, 256, (v, n, k // 8)), jnp.uint8)
    v_row = jnp.asarray(rng.normal(size=(v, n)), jnp.float16).at[0].set(0)
    v_col = jnp.asarray(rng.normal(size=(v, k)), jnp.float16).at[0].set(0)
    wb = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    vidx = jnp.asarray(rng.integers(0, v, (m,)), jnp.int32)
    got = K.bitlinear_axes_banked(x, vidx, packed, v_row, v_col, wb)
    want = R.bitlinear_axes_banked_ref(x, vidx, packed, v_row, v_col, wb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_banked_kernel_rows_match_single_variant_kernel():
    """Each row of a mixed batch equals the per-variant fused kernel run on
    the same rows; slot-0 rows equal the plain base GEMM."""
    rng = np.random.default_rng(0)
    v, n, k, m = 4, 32, 64, 8
    packed = jnp.asarray(rng.integers(0, 256, (v, n, k // 8)), jnp.uint8)
    v_row = jnp.asarray(rng.normal(size=(v, n)), jnp.float16).at[0].set(0)
    v_col = jnp.asarray(rng.normal(size=(v, k)), jnp.float16).at[0].set(0)
    wb = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    vidx = jnp.asarray([0, 1, 2, 3, 0, 1, 2, 3], jnp.int32)
    y = K.bitlinear_axes_banked(x, vidx, packed, v_row, v_col, wb)
    base = x @ wb.T
    np.testing.assert_allclose(np.asarray(y[vidx == 0]),
                               np.asarray(base[vidx == 0]),
                               rtol=1e-5, atol=1e-5)
    for vi in range(1, v):
        ys = K.bitlinear_axes(x, packed[vi], v_row[vi], v_col[vi], wb)
        rows = np.asarray(vidx == vi)
        np.testing.assert_allclose(np.asarray(y)[rows],
                                   np.asarray(ys)[rows],
                                   rtol=1e-5, atol=1e-5)


def test_banked_kernel_leading_dims_broadcast():
    """(B, S, K) input with (B,) variant_idx: every row of a sequence uses
    its batch lane's variant."""
    rng = np.random.default_rng(1)
    v, n, k = 3, 16, 32
    packed = jnp.asarray(rng.integers(0, 256, (v, n, k // 8)), jnp.uint8)
    v_row = jnp.asarray(rng.normal(size=(v, n)), jnp.float16).at[0].set(0)
    v_col = jnp.asarray(rng.normal(size=(v, k)), jnp.float16).at[0].set(0)
    wb = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 4, k)), jnp.float32)
    vidx = jnp.asarray([1, 2], jnp.int32)
    got = K.bitlinear_axes_banked(x, vidx, packed, v_row, v_col, wb)
    flat = K.bitlinear_axes_banked(
        x.reshape(8, k), jnp.repeat(vidx, 4), packed, v_row, v_col, wb)
    np.testing.assert_allclose(np.asarray(got).reshape(8, n),
                               np.asarray(flat), rtol=1e-6, atol=1e-6)


def _banked_operands(rng, v, n, k):
    packed = jnp.asarray(rng.integers(0, 256, (v, n, k // 8)), jnp.uint8)
    v_row = jnp.asarray(rng.normal(size=(v, n)), jnp.float16).at[0].set(0)
    v_col = jnp.asarray(rng.normal(size=(v, k)), jnp.float16).at[0].set(0)
    wb = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    return packed, v_row, v_col, wb


@pytest.mark.parametrize("t", [2, 4])
@pytest.mark.parametrize("dispatch", ["shard_map", "gspmd"])
def test_banked_kernel_multi_token_decode_shapes(t, dispatch):
    """(B, T, K) banked decode — the speculative verify_step shape
    (DESIGN.md §15): every row must be BIT-IDENTICAL to the T = 1
    per-token call the continuous scheduler makes (anything looser breaks
    the speculative scheduler's exactness guarantee), and allclose vs the
    dense oracle.  Both kernel lowerings: the shard_map per-shard path
    (1x1 mesh) and the global/GSPMD path."""
    from jax.sharding import Mesh
    from repro.distributed import sharding as S
    from repro.kernels import dispatch as KD
    from repro.kernels import ref as R

    rng = np.random.default_rng(10 + t)
    v, n, k, b = 3, 32, 64, 4
    packed, v_row, v_col, wb = _banked_operands(rng, v, n, k)
    x = jnp.asarray(rng.normal(size=(b, t, k)), jnp.float32)
    vidx = jnp.asarray(rng.integers(0, v, (b,)), jnp.int32)

    if dispatch == "shard_map":
        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model"))
        ctx = S.shard_ctx(mesh, S.rules_for("decode"))
    else:
        ctx = KD.no_dispatch()
    with ctx:
        got = K.bitlinear_axes_banked(x, vidx, packed, v_row, v_col, wb)
        per_tok = jnp.stack(
            [K.bitlinear_axes_banked(x[:, j], vidx, packed, v_row, v_col,
                                     wb) for j in range(t)], axis=1)
    assert got.shape == (b, t, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(per_tok))
    want = R.bitlinear_axes_banked_ref(
        x.reshape(b * t, k), jnp.repeat(vidx, t), packed, v_row, v_col, wb)
    np.testing.assert_allclose(np.asarray(got).reshape(b * t, n),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# model-level mixed-variant parity
# ---------------------------------------------------------------------------

def _pair3(arch: str, layers: int = 2):
    """Base + two perturbation fine-tunes (fp32 compute for tight parity)."""
    cfg = get_config(arch).reduced()
    if layers:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    cfg = dataclasses.replace(cfg, compute_dtype="float32", remat=False)
    model = build_model(cfg)
    base, _ = split(model.init(jax.random.PRNGKey(0)))
    pert, _ = split(model.init(jax.random.PRNGKey(1)))
    ft1 = jax.tree.map(lambda b, f: b + 0.05 * f, base, pert)
    ft2 = jax.tree.map(lambda b, f: b - 0.05 * f, base, pert)
    return model, base, C.compress(base, ft1), C.compress(base, ft2)


def _batch(model, bs=3, s=8, seed=7):
    cfg = model.cfg
    batch = {"tokens": jnp.asarray(np.random.default_rng(seed).integers(
        1, cfg.vocab_size, size=(bs, s)), jnp.int32)}
    if cfg.family == "audio":
        batch["frames"] = jnp.zeros((bs, cfg.encoder_frames, cfg.d_model),
                                    jnp.float32)
    if cfg.family == "vlm":
        batch["image_embeds"] = jnp.zeros(
            (bs, cfg.num_image_tokens, cfg.d_model), jnp.float32)
    return batch


def _per_variant_rows(model, base, dms, batch, max_len=32):
    """Reference: serve each row's variant separately on the PR-1 fused
    path; returns (prefill logits, one-step decode logits) stacked."""
    pre_rows, dec_rows = [], []
    for row, dm in enumerate(dms):
        if dm is None:
            params, ov = base, None
            pl, cc = jax.jit(lambda p, b: model.prefill(p, b, max_len)
                             )(params, batch)
            tok = jnp.argmax(pl, -1).astype(jnp.int32)
            dl, _ = jax.jit(model.decode_step)(params, tok, cc)
        else:
            params, ov, _ = L.device_put_overlay(base, dm)
            pl, cc = jax.jit(lambda p, o, b: model.prefill(
                p, b, max_len, overlay=o))(params, ov, batch)
            tok = jnp.argmax(pl, -1).astype(jnp.int32)
            dl, _ = jax.jit(lambda p, o, t, c: model.decode_step(
                p, t, c, overlay=o))(params, ov, tok, cc)
        pre_rows.append(pl[row])
        dec_rows.append(dl[row])
    return jnp.stack(pre_rows), jnp.stack(dec_rows)


@pytest.mark.parametrize("arch,layers", [("qwen3-8b", 2),
                                         ("deepseek-7b", 2)])
def test_mixed_decode_batch_parity_vs_per_variant(arch, layers):
    """Heterogeneous (base + 2 fused variants) prefill + decode batch vs
    per-variant fused serving: logits agree per row to fp32 rounding and
    greedy tokens agree exactly."""
    model, base, dm1, dm2 = _pair3(arch, layers)
    bank = OverlayBank(base, 4)
    s1, _ = bank.admit("v1", dm1)
    s2, _ = bank.admit("v2", dm2)
    batch = _batch(model)
    vidx = jnp.asarray([0, s1, s2], jnp.int32)

    lg, cache = jax.jit(lambda p, bk, vi, b: model.prefill(
        p, b, 32, overlay=bk, variant_idx=vi))(base, bank.tree, vidx, batch)
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    dl, _ = jax.jit(lambda p, bk, vi, t, c: model.decode_step(
        p, t, c, overlay=bk, variant_idx=vi))(base, bank.tree, vidx, tok,
                                              cache)

    want_pre, want_dec = _per_variant_rows(model, base, [None, dm1, dm2],
                                           batch)
    scale = float(jnp.max(jnp.abs(want_pre)))
    tol = 1e-4 * max(scale, 1.0)
    assert float(jnp.max(jnp.abs(lg - want_pre))) < tol
    assert float(jnp.max(jnp.abs(dl - want_dec))) < tol
    # greedy tokens: exact
    np.testing.assert_array_equal(np.asarray(jnp.argmax(lg, -1)),
                                  np.asarray(jnp.argmax(want_pre, -1)))
    np.testing.assert_array_equal(np.asarray(jnp.argmax(dl, -1)),
                                  np.asarray(jnp.argmax(want_dec, -1)))


@pytest.mark.parametrize("arch", ["whisper-base", "xlstm-350m", "zamba2-7b"])
def test_mixed_forward_parity_families(arch):
    """The other families serve heterogeneous rows through the same banked
    overlay (incl. banked extras: convs, recurrent weights, SSD params)."""
    model, base, dm1, dm2 = _pair3(arch, layers=0)
    bank = OverlayBank(base, 4)
    s1, _ = bank.admit("v1", dm1)
    s2, _ = bank.admit("v2", dm2)
    batch = _batch(model)
    vidx = jnp.asarray([0, s1, s2], jnp.int32)
    lg = jax.jit(lambda p, bk, vi, b: model.forward(
        p, b, overlay=bk, variant_idx=vi)[0])(base, bank.tree, vidx, batch)
    for row, dm in enumerate([None, dm1, dm2]):
        if dm is None:
            want = jax.jit(lambda p, b: model.forward(p, b)[0])(base, batch)
        else:
            params, ov, _ = L.device_put_overlay(base, dm)
            want = jax.jit(lambda p, o, b: model.forward(
                p, b, overlay=o)[0])(params, ov, batch)
        scale = float(jnp.max(jnp.abs(want)))
        tol = 1e-4 * max(scale, 1.0)
        assert float(jnp.max(jnp.abs(lg[row] - want[row]))) < tol, (arch,
                                                                    row)


def test_moe_mixed_batch_jittable_and_uniform_rows_match():
    """MoE falls back to masked per-variant expert application: a mixed
    batch stays jittable; a uniform batch (all rows one variant) matches
    the single-variant fused path exactly (same capacity competition)."""
    model, base, dm1, dm2 = _pair3("deepseek-moe-16b", 2)
    bank = OverlayBank(base, 4)
    s1, _ = bank.admit("v1", dm1)
    s2, _ = bank.admit("v2", dm2)
    batch = _batch(model)
    fwd = jax.jit(lambda p, bk, vi, b: model.forward(
        p, b, overlay=bk, variant_idx=vi)[0])
    # uniform rows -> identical routing/capacity as per-variant serving
    lg_uni = fwd(base, bank.tree, jnp.full((3,), s1, jnp.int32), batch)
    params, ov, _ = L.device_put_overlay(base, dm1)
    want = jax.jit(lambda p, o, b: model.forward(p, b, overlay=o)[0])(
        params, ov, batch)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(lg_uni - want))) < 1e-4 * max(scale, 1.0)
    # mixed rows: jittable, finite
    lg_mix = fwd(base, bank.tree, jnp.asarray([0, s1, s2], jnp.int32), batch)
    assert bool(jnp.isfinite(lg_mix).all())


# ---------------------------------------------------------------------------
# overlay bank lifecycle
# ---------------------------------------------------------------------------

def test_bank_admit_pin_evict_slot_reuse():
    model, base, dm1, dm2 = _pair3("deepseek-7b")
    bank = OverlayBank(base, 3)          # base + 2 variant slots
    s1, payload = bank.admit("a", dm1)
    assert s1 == 1 and payload > 0
    s2, _ = bank.admit("b", dm2)
    assert s2 == 2
    assert bank.nbytes() > 0
    # re-admit is a hit (no payload)
    assert bank.admit("a", dm1) == (1, 0)
    # full + everything pinned -> admission refuses
    bank.pin("a"); bank.pin("b")
    with pytest.raises(RuntimeError):
        bank.admit("c", dm1)
    # pinned eviction refuses; unpinned LRU slot is reused
    with pytest.raises(RuntimeError):
        bank.evict("b")
    bank.unpin("b")
    s3, _ = bank.admit("c", dm1)         # evicts "b" (LRU among unpinned)
    assert s3 == 2 and bank.resident() == ["a", "c"]
    assert bank.stats["evictions"] == 1


def test_registry_evict_banked_variant_mid_flight():
    """A banked variant referenced by an in-flight request is pinned:
    registry.evict raises until the request retires."""
    model, base, dm1, dm2 = _pair3("deepseek-7b")
    reg = VariantRegistry(base, mode="fused", bank_size=4)
    reg.register("v1", dm1)
    reg.register("v2", dm2)
    eng = ServingEngine(model, reg, batch_size=2, prompt_len=8, max_len=32,
                        scheduler="continuous")
    rid = eng.submit(np.arange(1, 7), variant="v1", max_new_tokens=4)
    # stage a mid-flight state: admit + prefill without draining
    eng._prefill_admitted(eng._admit_free_slots())
    assert eng.status(rid) == "running"
    with pytest.raises(RuntimeError):
        reg.evict("v1")
    eng.run_until_drained()                      # retires -> unpinned
    assert eng.result(rid).status == "done"
    reg.evict("v1")                              # now fine
    assert "v1" not in reg.bank.resident()


# ---------------------------------------------------------------------------
# slot scheduler
# ---------------------------------------------------------------------------

def test_scheduler_admit_retire_slot_reuse_and_budgets():
    """More requests than lanes, heterogeneous budgets: slots retire the
    moment their budget is exhausted and free lanes admit from the queue;
    every request gets exactly its budget of tokens."""
    model, base, dm1, dm2 = _pair3("deepseek-7b")
    reg = VariantRegistry(base, mode="fused", bank_size=4)
    reg.register("v1", dm1)
    reg.register("v2", dm2)
    eng = ServingEngine(model, reg, batch_size=2, prompt_len=8, max_len=32,
                        scheduler="continuous")
    budgets = [2, 5, 3, 2]
    variants = ["v1", "__base__", "v2", "v1"]
    rids = [eng.submit(np.arange(1, 7), variant=v, max_new_tokens=m)
            for v, m in zip(variants, budgets)]
    eng.run_until_drained()
    for rid, m in zip(rids, budgets):
        r = eng.result(rid)
        assert r.status == "done"
        assert len(r.out_tokens) == m
    assert eng.metrics["admitted"] == 4
    assert eng.metrics["retired"] == 4
    assert eng.metrics["prefills"] >= 2          # slot reuse => extra waves
    assert eng.pending() == 0 and eng.active() == 0


def test_scheduler_slot_reuse_preserves_isolation():
    """A request admitted into a REUSED lane must decode exactly what it
    would decode in a fresh engine (cache-row merge isolates lanes)."""
    model, base, dm1, dm2 = _pair3("deepseek-7b")

    def make_engine():
        reg = VariantRegistry(base, mode="fused", bank_size=4)
        reg.register("v1", dm1)
        reg.register("v2", dm2)
        return ServingEngine(model, reg, batch_size=2, prompt_len=8,
                             max_len=32, scheduler="continuous")

    eng = make_engine()
    eng.submit(np.arange(1, 7), variant="v1", max_new_tokens=2)
    eng.submit(np.arange(2, 8), variant="__base__", max_new_tokens=6)
    late = eng.submit(np.arange(3, 9), variant="v2", max_new_tokens=3)
    eng.run_until_drained()

    solo = make_engine()
    ref = solo.submit(np.arange(3, 9), variant="v2", max_new_tokens=3)
    solo.run_until_drained()
    assert eng.result(late).out_tokens == solo.result(ref).out_tokens


@pytest.fixture(scope="module")
def four_lanes():
    """Two 4-lane continuous engines over one base and two variants: one
    to serve mixed waves, one to serve each request alone."""
    model, base, dm1, dm2 = _pair3("deepseek-7b")

    def make_engine():
        reg = VariantRegistry(base, mode="fused", bank_size=4)
        reg.register("v1", dm1)
        reg.register("v2", dm2)
        return ServingEngine(model, reg, batch_size=4, prompt_len=8,
                             max_len=32, scheduler="continuous")
    return make_engine(), make_engine()


@pytest.mark.parametrize("rows,bucket", [(1, 1), (2, 2), (3, 4)])
def test_a_wave_computes_the_smallest_row_bucket_that_holds_it(
        four_lanes, rows, bucket):
    """Idle lanes are not prefilled: a wave admitting R rows computes the
    smallest of the buckets 1, 2, 4 that holds R."""
    eng, _ = four_lanes
    before = dict(eng.metrics)
    for i in range(rows):
        eng.submit(np.arange(1 + i, 7 + i), variant=("v1", "__base__",
                                                     "v2")[i],
                   max_new_tokens=2)
    eng.run_until_drained()
    delta = {k: eng.metrics[k] - before[k] for k in
             ("prefills", "prefill_rows", "prefill_rows_computed")}
    assert delta == {"prefills": 1, "prefill_rows": rows,
                     "prefill_rows_computed": bucket}


@pytest.mark.parametrize("budgets,lanes", [
    # lanes 1 and 3 admitted while lanes 0 and 2 decode: bucket 2
    ((9, 2, 9, 2, 3, 4), [1, 3]),
    # lanes 0, 1 and 3 admitted while lane 2 decodes: bucket 4, one pad row
    ((2, 2, 9, 2, 3, 4, 5), [0, 1, 3]),
])
def test_a_wave_into_scattered_lanes_leaves_live_lanes_alone(
        four_lanes, budgets, lanes):
    """Rows admitted into non-contiguous lanes, beside lanes that are
    decoding, and the pad row of a bucket: every request decodes exactly
    the tokens it decodes alone."""
    eng, solo = four_lanes
    variants = ["v1", "__base__", "v2"]
    prompts = [np.arange(2 + i, 8 + i) for i in range(len(budgets))]
    waves = []
    prefill = eng._prefill_admitted

    def recording(newly):
        waves.append(list(newly))
        prefill(newly)
    eng._prefill_admitted = recording
    try:
        rids = [eng.submit(p, variant=variants[i % 3], max_new_tokens=m)
                for i, (p, m) in enumerate(zip(prompts, budgets))]
        eng.run_until_drained()
    finally:
        del eng._prefill_admitted
    assert waves == [[0, 1, 2, 3], lanes]
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        ref = solo.submit(p, variant=variants[i % 3], max_new_tokens=m)
        solo.run_until_drained()
        assert eng.result(rids[i]).out_tokens == \
            solo.result(ref).out_tokens, i


def test_scheduler_matches_grouped_serving_tokens():
    """End to end: mixed continuous batches generate exactly the tokens
    the grouped-by-variant engine generates per request."""
    model, base, dm1, dm2 = _pair3("deepseek-7b")

    def run(scheduler):
        reg = VariantRegistry(base, mode="fused", max_resident=4,
                              bank_size=4)
        reg.register("v1", dm1)
        reg.register("v2", dm2)
        eng = ServingEngine(model, reg, batch_size=2, prompt_len=8,
                            max_len=32, scheduler=scheduler)
        rids = [eng.submit(np.arange(1, 7), variant=v, max_new_tokens=3)
                for v in ["v1", "__base__", "v2", "v1", "v2"]]
        eng.run_until_drained()
        return [eng.result(r).out_tokens for r in rids]

    assert run("continuous") == run("group")


def test_engine_status_accessor_never_raises():
    model, base, dm1, _ = _pair3("deepseek-7b")
    reg = VariantRegistry(base, mode="fused", bank_size=4)
    reg.register("v1", dm1)
    eng = ServingEngine(model, reg, batch_size=2, prompt_len=8, max_len=32,
                        scheduler="continuous")
    rid = eng.submit(np.arange(1, 7), variant="v1", max_new_tokens=2)
    assert eng.status(rid) == "queued"
    assert eng.status(10_000) == "unknown"       # no KeyError
    eng.run_until_drained()
    assert eng.status(rid) == "done"
    # group-mode engines expose the same accessor
    eng2 = ServingEngine(model, reg, batch_size=2, prompt_len=8, max_len=32)
    assert eng2.status(123) == "unknown"
