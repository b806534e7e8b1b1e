"""Per-shard Pallas delta kernels under shard_map (DESIGN.md §12).

Three layers of coverage:

* pure planning (fake mesh, no devices; runs in tier-1): spec derivation,
  psum decision, packing-width fallbacks, and the `_pick_block` refusal
  for misaligned shard-local dims;
* 1-device no-mesh fallback (tier-1): outside a mesh context the ops
  wrappers must take the global jit path byte-for-byte — dispatch is
  invisible single-device;
* 4-device execution (sharded-smoke CI job, skip otherwise): kernel- and
  model-level logits parity sweeps (fused + banked, all four families)
  between the shard_map'd per-shard path, the PR-4 GSPMD-partitioned
  path (``no_dispatch`` / engine ``kernel_dispatch="gspmd"``) and the
  unsharded single-device path, plus the acceptance bar — bit-identical
  greedy tokens from the continuous-batching engine under both mesh
  lowerings.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core import calibration as C
from repro.core import loader as L
from repro.distributed import sharding as S
from repro.kernels import dispatch as D
from repro.kernels import ops as K
from repro.models import build_model
from repro.models import delta_overlay as DO
from repro.models.param import split
from repro.serving import Deployment
from repro.serving.variants import OverlayBank

RULES = S.rules_for("decode")


def _mesh22() -> Mesh:
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (sharded-smoke CI job)")
    return Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))


def _fake_mesh(shape, names):
    class M:
        axis_names = names
        devices = np.empty(shape, object)
    return M()


def _rand_entry(rng, n, k, nb=None):
    shp = (n, k // 8) if nb is None else (nb, n, k // 8)
    packed = jnp.asarray(rng.integers(0, 256, size=shp, dtype=np.uint8))
    vr = jnp.asarray(rng.normal(size=(n,) if nb is None
                                else (nb, n)).astype(np.float16))
    vc = jnp.zeros((k,) if nb is None else (nb, k), jnp.float16)
    wb = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    return packed, vr, vc, wb


# ---------------------------------------------------------------------------
# planning (tier-1: no devices needed)
# ---------------------------------------------------------------------------

def test_plan_row_sharded():
    mesh = _fake_mesh((2, 2), ("data", "model"))
    plan = D.plan_matmul(mesh, RULES, ("ffn", "embed"), m=8, n=32, k=24)
    assert plan.o_part == "model" and plan.i_part is None
    assert plan.m_part == "data"
    assert plan.psum_axes == ()


def test_plan_col_sharded_psums():
    mesh = _fake_mesh((2, 2), ("data", "model"))
    plan = D.plan_matmul(mesh, RULES, ("embed", "ffn"), m=8, n=24, k=32)
    assert plan.o_part is None and plan.i_part == "model"
    assert plan.psum_axes == ("model",)


def test_plan_refuses_misaligned_local_k():
    """K sharded 2-way would leave an 8-element local tile -> 4 bytes of
    packed plane per shard: not a packing-width multiple, so the plan must
    decline (global path) instead of letting _pick_block mis-size."""
    mesh = _fake_mesh((2, 2), ("data", "model"))
    assert D.plan_matmul(mesh, RULES, ("embed", "ffn"), m=4, n=16, k=8) \
        is None


def test_plan_none_without_axes():
    mesh = _fake_mesh((2, 2), ("data", "model"))
    assert D.plan_matmul(mesh, RULES, None, m=8, n=32, k=24) is None


def test_plan_multi_pod_batch_axes():
    mesh = _fake_mesh((2, 2, 2), ("pod", "data", "model"))
    plan = D.plan_matmul(mesh, RULES, ("ffn", "embed"), m=8, n=32, k=24)
    assert plan.m_part == ("pod", "data")


def test_pick_block_refuses_misaligned():
    with pytest.raises(ValueError, match="not a multiple"):
        K._pick_block(12, 512, multiple=8)
    with pytest.raises(ValueError, match="not a multiple"):
        K._pick_block(4, 512, multiple=8)   # dim smaller than the width
    assert K._pick_block(24, 512, multiple=8) == 24
    assert K._pick_block(40, 16, multiple=8) == 8
    # multiple > target: smallest VALID block, not an oversized dim block
    assert K._pick_block(64, 4, multiple=8) == 8


def test_shared_spec_surgery_matches_logical():
    """The ONE spec-surgery helper (delta_overlay.entry_shardings_from_
    weight) agrees with the logical derivation entry_axes resolves to —
    same equivalence the PR-4 loader regression asserts, now at the
    helper level both loader paths share."""
    mesh = _mesh22()
    w_sh = NamedSharding(mesh, P("model", None))
    ent = DO.entry_shardings_from_weight(w_sh, 2)
    ax = DO.entry_axes(("ffn", "embed"))
    assert ent.packed.spec == S.resolve_spec((32, 4), ax.packed, RULES, mesh)
    assert ent.v_row.spec == S.resolve_spec((32,), ax.v_row, RULES, mesh)
    assert ent.v_col.spec == S.resolve_spec((32,), ax.v_col, RULES, mesh)
    assert DO.entry_shardings_from_weight(object(), 2) is None


# ---------------------------------------------------------------------------
# 1-device no-mesh fallback (tier-1)
# ---------------------------------------------------------------------------

def test_no_mesh_state_inactive():
    assert D.state() is None
    with D.no_dispatch():
        assert D.state() is None


def test_no_mesh_waxes_is_global_path():
    """Outside a mesh context, passing waxes must be a no-op: identical
    results to the waxes-free call and to the jnp oracle."""
    from repro.kernels import ref
    rng = np.random.default_rng(3)
    packed, vr, vc, wb = _rand_entry(rng, 32, 24)
    x = jnp.asarray(rng.normal(size=(4, 24)).astype(np.float32))
    base = K.bitlinear_axes(x, packed, vr, vc, wb)
    with_axes = K.bitlinear_axes(x, packed, vr, vc, wb,
                                 waxes=("ffn", "embed"))
    np.testing.assert_array_equal(np.asarray(base), np.asarray(with_axes))
    want = ref.bitlinear_axes_ref(x, packed, vr, vc, wb)
    np.testing.assert_allclose(np.asarray(with_axes), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # banked + unpack_apply take the same fallback
    packed_b, vrb, vcb, wbb = _rand_entry(rng, 32, 24, nb=3)
    vidx = jnp.asarray(rng.integers(0, 3, size=(4,)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(K.bitlinear_axes_banked(x, vidx, packed_b, vrb, vcb, wbb,
                                           waxes=("ffn", "embed"))),
        np.asarray(K.bitlinear_axes_banked(x, vidx, packed_b, vrb, vcb,
                                           wbb)))
    v = jnp.asarray(rng.normal(size=(32,)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(K.unpack_apply(packed, v, wb, mode="row",
                                  waxes=("ffn", "embed"))),
        np.asarray(K.unpack_apply(packed, v, wb, mode="row")))


# ---------------------------------------------------------------------------
# 4-device kernel-level parity
# ---------------------------------------------------------------------------

def test_kernel_parity_row_col_banked_unpack():
    mesh = _mesh22()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 24)).astype(np.float32))

    packed, vr, vc, wb = _rand_entry(rng, 32, 24)
    want = K.bitlinear_axes(x, packed, vr, vc, wb)
    with S.shard_ctx(mesh, RULES):
        got = K.bitlinear_axes(x, packed, vr, vc, wb, waxes=("ffn", "embed"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    # col-sharded contraction: partial sums psum over `model`
    x2 = jnp.asarray(rng.normal(size=(4, 2, 32)).astype(np.float32))
    packed2, vr2, vc2, wb2 = _rand_entry(rng, 24, 32)
    want2 = K.bitlinear_axes(x2, packed2, vr2, vc2, wb2)
    with S.shard_ctx(mesh, RULES):
        got2 = K.bitlinear_axes(x2, packed2, vr2, vc2, wb2,
                                waxes=("embed", "ffn"))
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2),
                               rtol=2e-5, atol=2e-5)

    packed_b, vrb, vcb, wbb = _rand_entry(rng, 32, 24, nb=3)
    vidx = jnp.asarray(rng.integers(0, 3, size=(8,)), jnp.int32)
    wantb = K.bitlinear_axes_banked(x, vidx, packed_b, vrb, vcb, wbb)
    with S.shard_ctx(mesh, RULES):
        gotb = K.bitlinear_axes_banked(x, vidx, packed_b, vrb, vcb, wbb,
                                       waxes=("ffn", "embed"))
    np.testing.assert_allclose(np.asarray(gotb), np.asarray(wantb),
                               rtol=2e-5, atol=2e-5)

    v = jnp.asarray(rng.normal(size=(32,)).astype(np.float32))
    wantu = K.unpack_apply(packed, v, wb, mode="row")
    with S.shard_ctx(mesh, RULES):
        gotu = K.unpack_apply(packed, v, wb, mode="row",
                              waxes=("ffn", "embed"))
    np.testing.assert_array_equal(np.asarray(gotu), np.asarray(wantu))


# ---------------------------------------------------------------------------
# 4-device model-level sweeps (fused + banked, all four families)
# ---------------------------------------------------------------------------

def _family_pair(arch: str):
    """fp32-compute toy pair; layers=2 where the family allows an override
    (xlstm/zamba keep their reduced super-block counts)."""
    cfg = get_config(arch).reduced()
    if arch in ("deepseek-7b", "deepseek-moe-16b"):
        cfg = dataclasses.replace(cfg, num_layers=2)
    cfg = dataclasses.replace(cfg, compute_dtype="float32", remat=False)
    model = build_model(cfg)
    base, axes = split(model.init(jax.random.PRNGKey(0)))
    pert, _ = split(model.init(jax.random.PRNGKey(1)))
    ft1 = jax.tree.map(lambda b, f: b + 0.05 * f, base, pert)
    ft2 = jax.tree.map(lambda b, f: b - 0.05 * f, base, pert)
    return model, base, axes, C.compress(base, ft1), C.compress(base, ft2)


def _tokens_batch(model, bs=4, s=8):
    batch = {"tokens": jnp.asarray(np.random.default_rng(7).integers(
        1, model.cfg.vocab_size, size=(bs, s)), jnp.int32)}
    if model.cfg.family == "audio":
        batch["frames"] = jnp.zeros(
            (bs, model.cfg.encoder_frames, model.cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ["deepseek-7b", "deepseek-moe-16b",
                                  "xlstm-350m", "zamba2-7b",
                                  "whisper-base"])
def test_waxes_literals_match_param_declarations(arch, monkeypatch):
    """Drift guard for the hardcoded ``waxes=(...)`` call-site literals:
    every axes tuple the model families pass into the delta kernels must
    agree with the ``Param.axes`` declared at init for a weight of that
    shape (the single source of truth ``models/param.split`` recovers).
    A mismatched literal would silently make shard_map reshard the weight
    tile every step — parity stays green, the win evaporates — so this
    runs in tier-1, recording at trace time (no mesh needed).

    ``waxes=None`` records are the intentional GSPMD-fallback sites (the
    vmapped expert path); at least one dispatch-capable site must fire."""
    import repro.kernels.ops as OPS
    model, base, axes, dm1, dm2 = _family_pair(arch)
    flat_axes = DO.flatten_axes(axes)
    flat_base = C.flatten_params(base)
    declared: dict = {}
    for p in dm1.deltas:
        declared.setdefault(tuple(flat_base[p].shape[-2:]),
                            set()).add(tuple(flat_axes[p][-2:]))

    recorded = []
    orig, orig_b = OPS.bitlinear_axes, OPS.bitlinear_axes_banked

    def probe(x, packed, v_row, v_col, w_base, waxes=None):
        recorded.append((tuple(w_base.shape[-2:]), waxes))
        return orig(x, packed, v_row, v_col, w_base, waxes=waxes)

    def probe_b(x, vidx, packed, v_row, v_col, w_base, waxes=None):
        recorded.append((tuple(w_base.shape[-2:]), waxes))
        return orig_b(x, vidx, packed, v_row, v_col, w_base, waxes=waxes)

    monkeypatch.setattr(OPS, "bitlinear_axes", probe)
    monkeypatch.setattr(OPS, "bitlinear_axes_banked", probe_b)

    batch = _tokens_batch(model)
    # fused prefill + decode AND a banked step: every delta call site
    # (incl. the decode-only ones) traces through the probes
    pv, ov, _ = L.device_put_overlay(base, dm1)
    lg, cache = jax.jit(lambda p, o, b: model.prefill(
        p, b, 32, overlay=o))(pv, ov, batch)
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    jax.jit(lambda p, o, t, c: model.decode_step(
        p, t, c, overlay=o))(pv, ov, tok, cache)
    bank = OverlayBank(base, 3)
    s1, _ = bank.admit("v1", dm1)
    vidx = jnp.asarray([0, s1, s1, 0], jnp.int32)
    jax.jit(lambda p, bk, vi, b: model.prefill(
        p, b, 32, overlay=bk, variant_idx=vi))(base, bank.tree, vidx, batch)

    assert recorded
    assert any(w is not None for _, w in recorded), "no dispatch-capable site"
    for shape, waxes in recorded:
        if waxes is None:       # intentional GSPMD fallback (vmapped experts)
            continue
        assert shape in declared, (shape, waxes)
        assert tuple(waxes) in declared[shape], (shape, waxes,
                                                 declared[shape])


@pytest.mark.parametrize("mode", ["fused", "banked"])
@pytest.mark.parametrize("arch", ["deepseek-7b", "deepseek-moe-16b",
                                  "xlstm-350m", "zamba2-7b"])
def test_family_logits_parity_per_shard_vs_global(arch, mode):
    """Per-shard shard_map'd kernels vs the GSPMD-partitioned global
    kernels vs single-device: logits agree to fp32-reduction tolerance,
    greedy tokens exactly — for single-variant fused overlays and for
    banked mixed-variant batches, across all four families.  The KV cache
    is fp32 here: a bf16 cache turns a 1e-6 reassociation difference into
    a one-ulp (4e-3) flip of a cached key, which the decode step then
    reads back as a logit difference far above fp32 tolerance."""
    mesh = _mesh22()
    model, base, axes, dm1, dm2 = _family_pair(arch)
    batch = _tokens_batch(model)

    def run(use_mesh, gspmd=False):
        import contextlib
        stack = contextlib.ExitStack()
        if use_mesh:
            param_sh = S.tree_shardings(base, axes, RULES, mesh)
            params = jax.device_put(base, param_sh)
            stack.enter_context(mesh)
            stack.enter_context(S.shard_ctx(mesh, RULES))
            if gspmd:
                stack.enter_context(D.no_dispatch())
        else:
            params, param_sh = base, None
        with stack:
            if mode == "banked":
                bank = OverlayBank(params, 4,
                                   mesh=mesh if use_mesh else None,
                                   param_axes=axes if use_mesh else None)
                s1, _ = bank.admit("v1", dm1)
                s2, _ = bank.admit("v2", dm2)
                vidx = jnp.asarray([0, s1, s2, s1], jnp.int32)
                pf = jax.jit(lambda p, bk, vi, b: model.prefill(
                    p, b, 32, cache_dtype=jnp.float32, overlay=bk,
                    variant_idx=vi))
                dc = jax.jit(lambda p, bk, vi, t, c: model.decode_step(
                    p, t, c, overlay=bk, variant_idx=vi))
                lg, cache = pf(params, bank.tree, vidx, batch)
                tok = jnp.argmax(lg, -1).astype(jnp.int32)
                dl, _ = dc(params, bank.tree, vidx, tok, cache)
            else:
                pv, ov, _ = L.device_put_overlay(
                    params, dm1, param_shardings=param_sh)
                pf = jax.jit(lambda p, o, b: model.prefill(
                    p, b, 32, cache_dtype=jnp.float32, overlay=o))
                dc = jax.jit(lambda p, o, t, c: model.decode_step(
                    p, t, c, overlay=o))
                lg, cache = pf(pv, ov, batch)
                tok = jnp.argmax(lg, -1).astype(jnp.int32)
                dl, _ = dc(pv, ov, tok, cache)
        return np.asarray(lg), np.asarray(dl)

    want_pre, want_dec = run(False)
    got_pre, got_dec = run(True)
    ab_pre, ab_dec = run(True, gspmd=True)
    tol = 1e-4 * max(float(np.max(np.abs(want_pre))), 1.0)
    assert float(np.max(np.abs(got_pre - want_pre))) < tol
    assert float(np.max(np.abs(got_dec - want_dec))) < tol
    for got, want in [(got_pre, want_pre), (got_dec, want_dec),
                      (got_pre, ab_pre), (got_dec, ab_dec)]:
        np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))


# ---------------------------------------------------------------------------
# 4-device engine acceptance: shard_map vs gspmd, continuous + fused group
# ---------------------------------------------------------------------------

def test_engine_continuous_token_parity_shard_map_vs_gspmd():
    """ACCEPTANCE: the continuous-batching engine on the 4-device mesh
    emits bit-identical greedy tokens whether its fused/banked delta GEMMs
    lower per-shard (shard_map) or via the PR-4 GSPMD path — and both
    match the single-device engine."""
    mesh = _mesh22()
    model, base, axes, dm1, dm2 = _family_pair("deepseek-7b")

    def run(mesh_or_none, kernel_dispatch="shard_map"):
        dep = Deployment(model, base, batch_size=2, prompt_len=8,
                         max_len=32, bank_size=4, mesh=mesh_or_none,
                         param_axes=axes if mesh_or_none else None,
                         kernel_dispatch=kernel_dispatch)
        dep.publish("v1", dm1)
        dep.publish("v2", dm2)
        rids = [dep.submit(np.arange(1, 7), variant=v, max_new_tokens=m)
                for v, m in [("v1", 3), ("__base__", 5), ("v2", 2),
                             ("v1", 4), ("v2", 3)]]
        dep.drain()
        return [dep.result(r).out_tokens for r in rids]

    single = run(None)
    shard_map_toks = run(mesh, "shard_map")
    gspmd_toks = run(mesh, "gspmd")
    assert shard_map_toks == gspmd_toks == single


def test_engine_group_fused_token_parity_shard_map_vs_gspmd():
    """Same acceptance bar for the group scheduler's single-variant fused
    residency (per-variant overlays, non-banked kernels)."""
    mesh = _mesh22()
    model, base, axes, dm1, _ = _family_pair("deepseek-7b")
    from repro.serving import ServingEngine, VariantRegistry

    def run(mesh_or_none, kernel_dispatch="shard_map"):
        kw = {}
        params = base
        if mesh_or_none is not None:
            param_sh = S.tree_shardings(base, axes, RULES, mesh_or_none)
            params = jax.device_put(base, param_sh)
            kw = dict(param_shardings=param_sh, mesh=mesh_or_none,
                      param_axes=axes)
        reg = VariantRegistry(params, mode="fused", max_resident=4, **kw)
        reg.register("v1", dm1)
        eng = ServingEngine(model, reg, batch_size=2, prompt_len=8,
                            max_len=32, scheduler="group",
                            mesh=mesh_or_none,
                            kernel_dispatch=kernel_dispatch)
        rids = [eng.submit(np.arange(1, 7), variant=v, max_new_tokens=3)
                for v in ["v1", "__base__", "v1"]]
        eng.run_until_drained()
        return [eng.result(r).out_tokens for r in rids]

    assert run(mesh, "shard_map") == run(mesh, "gspmd") == run(None)


def test_engine_rejects_unknown_kernel_dispatch():
    model, base, axes, dm1, _ = _family_pair("deepseek-7b")
    from repro.serving import ServingEngine, VariantRegistry
    reg = VariantRegistry(base, mode="fused")
    with pytest.raises(ValueError, match="kernel_dispatch"):
        ServingEngine(model, reg, kernel_dispatch="magic")


def test_dense_reconstruction_per_shard():
    """apply_artifact(param_axes=) inside a mesh context reconstructs
    unstacked Ŵ per-shard (the production dense-residency path the
    registry threads) — bit-identical to the no-mesh reconstruction.
    zamba: its shared attention/MLP delta targets are 2-D (unstacked), so
    the per-shard unpack path genuinely engages (stacked entries stay on
    the vmapped global kernel)."""
    mesh = _mesh22()
    model, base, axes, dm1, _ = _family_pair("zamba2-7b")
    want, _ = L.apply_artifact(base, dm1)
    param_sh = S.tree_shardings(base, axes, RULES, mesh)
    sharded = jax.device_put(base, param_sh)
    with S.shard_ctx(mesh, RULES):
        got, _ = L.apply_artifact(sharded, dm1, param_shardings=param_sh,
                                  param_axes=axes)
    for path, w in C.flatten_params(want).items():
        np.testing.assert_array_equal(
            np.asarray(C.flatten_params(got)[path]), np.asarray(w), path)


# ---------------------------------------------------------------------------
# apply_update on derived shardings (shared spec-surgery helper)
# ---------------------------------------------------------------------------

def test_apply_update_lifts_to_derived_shardings():
    """With param_shardings, apply_update places every patched entry leaf
    on the placement the shared helper derives from the weight sharding —
    the same layout device_put_overlay transfers to — so a patched variant
    starts life sharded."""
    mesh = _mesh22()
    model, base, axes, dm1, _ = _family_pair("deepseek-7b")
    param_sh = S.tree_shardings(base, axes, RULES, mesh)
    flat_sh = C.flatten_params(param_sh)
    path = next(iter(dm1.deltas))
    e = dm1.deltas[path]
    patch = {path: {
        "packed": np.zeros(e.packed.size, np.uint8),
        "v_row": np.zeros(e.v_row.size, np.uint16),
        "v_col": np.zeros(e.v_col.size, np.uint16),
        "use_row": np.zeros(e.use_row.size, bool).reshape(e.use_row.shape),
    }}
    dm2 = L.apply_update(dm1, patch, {}, param_shardings=param_sh)
    want = DO.entry_shardings_from_weight(flat_sh[path], e.packed.ndim)
    got = dm2.deltas[path]
    # is_equivalent_to, not spec equality: jit outputs normalise trailing
    # Nones (P(None, None) -> P())
    assert got.packed.sharding.is_equivalent_to(want.packed,
                                                got.packed.ndim)
    assert got.v_row.sharding.is_equivalent_to(want.v_row, got.v_row.ndim)
    assert got.v_col.sharding.is_equivalent_to(want.v_col, got.v_col.ndim)
    np.testing.assert_array_equal(np.asarray(got.packed),
                                  np.asarray(e.packed))
