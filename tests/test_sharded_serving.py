"""Mesh-sharded multi-variant serving (DESIGN.md §11).

Pure-resolution tests use the fake-mesh idiom from test_sharding.py; the
execution tests need >= 4 host devices (run under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` — the CI
sharded-smoke job) and skip on the tier-1 single-device run.

Parity contract: sharding is a LAYOUT decision — banked mixed-variant
decode on a (data, model) mesh must produce the same greedy tokens as the
single-device path, with every overlay/bank leaf resident on its derived
placement and bank admission running as one jitted scatter on the sharded
leaves.
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
from repro.models import build_model
from repro.models import delta_overlay as DO
from repro.models.param import split
from repro.serving import Deployment, ServingEngine, VariantRegistry
from repro.serving.variants import OverlayBank


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


def _pair(arch: str = "deepseek-7b", layers: int = 2):
    """Base + two perturbation fine-tunes (fp32 compute for tight parity,
    same recipe as test_continuous_batching)."""
    cfg = dataclasses.replace(get_config(arch).reduced(), num_layers=layers,
                              compute_dtype="float32", remat=False)
    model = build_model(cfg)
    base, axes = split(model.init(jax.random.PRNGKey(0)))
    pert, _ = split(model.init(jax.random.PRNGKey(1)))
    ft1 = jax.tree.map(lambda b, f: b + 0.05 * f, base, pert)
    ft2 = jax.tree.map(lambda b, f: b - 0.05 * f, base, pert)
    return model, base, axes, C.compress(base, ft1), C.compress(base, ft2)


# ---------------------------------------------------------------------------
# pure pspec derivation (no devices)
# ---------------------------------------------------------------------------

def test_entry_axes_derivation():
    mesh = _fake_mesh((16, 16), ("data", "model"))
    rules = S.rules_for("decode")
    ax = DO.entry_axes(("ffn", "embed"))
    assert ax.packed == ("ffn", None)          # packed byte dim replicated
    assert ax.v_row == ("ffn",)
    assert ax.v_col == ("embed",)
    # resolved under serve rules: ffn -> model, embed replicated over data
    spec = S.resolve_spec((4096, 128), ax.packed, rules, mesh)
    assert spec == P("model", None)
    assert S.resolve_spec((4096,), ax.v_row, rules, mesh) == P("model")
    assert S.resolve_spec((1024,), ax.v_col, rules, mesh) == P(None)


def test_entry_axes_banked_stacked():
    """Leaves under a scan stack put the bank axis at position 1 (after
    the layer dim), and "bank" always resolves replicated."""
    ax = DO.entry_axes(("layers", "ffn", "embed"), path="layers.mlp.w_gate",
                       bank=True)
    assert ax.packed == ("layers", "bank", "ffn", None)
    assert ax.v_row == ("layers", "bank", "ffn")
    assert DO.extra_axes(("vocab", "embed"), path="embed", bank=True) == \
        ("bank", "vocab", "embed")
    mesh = _fake_mesh((16, 16), ("data", "model"))
    rules = S.rules_for("decode")
    spec = S.resolve_spec((4, 8, 4096, 128), ax.packed, rules, mesh)
    assert spec == P(None, None, "model", None)


def test_overlay_pspecs_tree_mirrors_overlay():
    model, base, axes, dm1, _ = _pair(layers=2)
    tree = DO.overlay_pspecs(axes, sorted(dm1.deltas), sorted(dm1.extras),
                             bank=True)
    # every delta path resolves to an OverlayEntry of axis tuples, every
    # extras path to a plain tuple with the bank axis inserted
    flat_axes = DO.flatten_axes(axes)
    for path in dm1.deltas:
        node = tree
        for part in path.split("."):
            node = node[part]
        assert isinstance(node, DO.OverlayEntry)
        assert "bank" in node.packed
    for path in dm1.extras:
        node = tree
        for part in path.split("."):
            node = node[part]
        assert isinstance(node, tuple)
        assert len(node) == len(flat_axes[path]) + 1


# ---------------------------------------------------------------------------
# loader placement (regression: v_row/v_col/extras must land sharded)
# ---------------------------------------------------------------------------

def test_device_put_overlay_places_every_leaf():
    """Regression: device_put_overlay used to place only the packed mask
    with param_shardings — v_row/v_col went to the default device.  Every
    overlay leaf and every extras leaf must land on a NamedSharding of the
    serving mesh, and the spec-surgery derivation in the loader must agree
    with the logical derivation in delta_overlay."""
    mesh = _mesh22()
    model, base, axes, dm1, _ = _pair(layers=2)
    rules = S.rules_for("decode")
    param_sh = S.tree_shardings(base, axes, rules, mesh)
    params_view, overlay, _ = L.device_put_overlay(
        base, dm1, param_shardings=param_sh)

    flat_want = DO.overlay_shardings(
        axes, C.flatten_params(base), sorted(dm1.deltas), (), rules, mesh)
    for path in dm1.deltas:
        node = overlay
        for part in path.split("."):
            node = node[part]
        want = flat_want[path]
        for leaf, want_sh in [(node.packed, want.packed),
                              (node.v_row, want.v_row),
                              (node.v_col, want.v_col)]:
            assert isinstance(leaf.sharding, NamedSharding), path
            assert leaf.sharding.mesh == mesh, path
            assert leaf.sharding.spec == want_sh.spec, (
                path, leaf.sharding.spec, want_sh.spec)
    # extras swap into the params view on the weight's own sharding
    flat_view = C.flatten_params(params_view)
    flat_sh = C.flatten_params(param_sh)
    for path in dm1.extras:
        assert flat_view[path].sharding == flat_sh[path], path


def test_apply_update_preserves_sharding():
    """A zero (identity) update patch applied to sharded parent leaves
    must leave the result on the SAME sharding (patches apply in place —
    no replicated round-trip)."""
    mesh = _mesh22()
    model, base, axes, dm1, _ = _pair(layers=2)
    rules = S.rules_for("decode")
    param_sh = S.tree_shardings(base, axes, rules, mesh)
    flat_sh = C.flatten_params(param_sh)
    path = next(iter(dm1.deltas))
    e = dm1.deltas[path]
    mask_sh = L._mask_sharding(flat_sh[path], e.packed.ndim)
    deltas = dict(dm1.deltas)
    deltas[path] = dataclasses.replace(
        e, packed=jax.device_put(e.packed, mask_sh))
    dm_sharded = C.DeltaModel(deltas=deltas, extras=dm1.extras)
    patch = {path: {
        "packed": np.zeros(e.packed.size, np.uint8),
        "v_row": np.zeros(e.v_row.size, np.uint16),
        "v_col": np.zeros(e.v_col.size, np.uint16),
        "use_row": np.zeros(e.use_row.size, bool).reshape(e.use_row.shape),
    }}
    dm2 = L.apply_update(dm_sharded, patch, {})
    got = dm2.deltas[path].packed
    assert got.sharding.spec == mask_sh.spec
    np.testing.assert_array_equal(np.asarray(got), np.asarray(e.packed))


# ---------------------------------------------------------------------------
# sharded overlay bank
# ---------------------------------------------------------------------------

def test_bank_admit_evict_readmit_sharded():
    """Bank lifecycle on a 2x2 mesh: leaves allocated on their derived
    shardings, admission = one jitted scatter on the sharded leaves, slot
    reuse after eviction, per-device byte accounting covers every shard."""
    mesh = _mesh22()
    model, base, axes, dm1, dm2 = _pair(layers=2)
    bank = OverlayBank(base, 3, mesh=mesh, param_axes=axes)
    s1, payload = bank.admit("a", dm1)
    assert s1 == 1 and payload > 0
    for path, want in bank.shardings.items():
        leaf = bank._flat[path]
        leaves = ([leaf] if not isinstance(leaf, DO.OverlayEntry)
                  else [leaf.packed, leaf.v_row, leaf.v_col])
        wants = ([want] if not isinstance(want, DO.OverlayEntry)
                 else [want.packed, want.v_row, want.v_col])
        for lf, w in zip(leaves, wants):
            assert isinstance(lf.sharding, NamedSharding), path
            assert lf.sharding.spec == w.spec, path
    s2, _ = bank.admit("b", dm2)
    assert s2 == 2
    # per-device accounting: every mesh device holds bank bytes, and the
    # total equals nbytes (replicated leaves counted once per device)
    per_dev = bank.per_device_nbytes()
    assert set(per_dev) == {str(d) for d in mesh.devices.flatten()}
    assert all(v > 0 for v in per_dev.values())
    bank.evict("a")
    s3, _ = bank.admit("c", dm1)
    assert s3 == 1                       # slot reuse
    assert bank.resident() == ["b", "c"]


def test_sharded_banked_decode_logits_parity():
    """Mixed-variant banked prefill + decode on the mesh vs single-device:
    logits agree to fp32-reduction tolerance, greedy tokens exactly."""
    mesh = _mesh22()
    model, base, axes, dm1, dm2 = _pair(layers=2)
    batch = {"tokens": jnp.asarray(np.random.default_rng(7).integers(
        1, model.cfg.vocab_size, size=(4, 8)), jnp.int32)}

    def run(mesh_or_none):
        if mesh_or_none is None:
            bank = OverlayBank(base, 4)
            params = base
        else:
            rules = S.rules_for("decode")
            param_sh = S.tree_shardings(base, axes, rules, mesh_or_none)
            params = jax.device_put(base, param_sh)
            bank = OverlayBank(params, 4, mesh=mesh_or_none,
                               param_axes=axes)
        s1, _ = bank.admit("v1", dm1)
        s2, _ = bank.admit("v2", dm2)
        vidx = jnp.asarray([0, s1, s2, s1], jnp.int32)
        pf = jax.jit(lambda p, bk, vi, b: model.prefill(
            p, b, 32, overlay=bk, variant_idx=vi))
        dc = jax.jit(lambda p, bk, vi, t, c: model.decode_step(
            p, t, c, overlay=bk, variant_idx=vi))
        lg, cache = pf(params, bank.tree, vidx, batch)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        dl, _ = dc(params, bank.tree, vidx, tok, cache)
        return np.asarray(lg), np.asarray(dl)

    want_pre, want_dec = run(None)
    got_pre, got_dec = run(mesh)
    scale = float(np.max(np.abs(want_pre)))
    tol = 1e-4 * max(scale, 1.0)
    assert float(np.max(np.abs(got_pre - want_pre))) < tol
    assert float(np.max(np.abs(got_dec - want_dec))) < tol
    np.testing.assert_array_equal(got_pre.argmax(-1), want_pre.argmax(-1))
    np.testing.assert_array_equal(got_dec.argmax(-1), want_dec.argmax(-1))


# ---------------------------------------------------------------------------
# engine / deployment end to end
# ---------------------------------------------------------------------------

def test_engine_sharded_greedy_token_parity():
    """Acceptance: the continuous-batching engine on a (2, 2) mesh emits
    bit-identical greedy tokens to the single-device engine for a mixed
    base + 2-variant workload (incl. slot reuse: more requests than
    lanes)."""
    mesh = _mesh22()
    model, base, axes, dm1, dm2 = _pair(layers=2)

    def run(mesh_or_none):
        dep = Deployment(model, base, batch_size=2, prompt_len=8,
                         max_len=32, bank_size=4, mesh=mesh_or_none,
                         param_axes=axes if mesh_or_none else None)
        dep.publish("v1", dm1)
        dep.publish("v2", dm2)
        rids = [dep.submit(np.arange(1, 7), variant=v, max_new_tokens=m)
                for v, m in [("v1", 3), ("__base__", 5), ("v2", 2),
                             ("v1", 4), ("v2", 3)]]
        dep.drain()
        assert dep.active() == 0 and dep.pending() == 0
        return [dep.result(r).out_tokens for r in rids]

    assert run(mesh) == run(None)


def test_engine_sharded_lanes_keep_full_width_waves():
    """Lanes sharded over the data axis keep the one full-width admission
    wave (row i on lane i's shard): a wave admitting one row still
    computes every lane, no smaller bucket is compiled, and tokens match
    the single-device engine.  Runs in a child with four host devices, so
    that the tier-1 single-device run covers it too."""
    import os
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys; sys.path[:0] = ["src", "tests"]
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.serving import Deployment
        from test_sharded_serving import _pair
        model, base, axes, dm1, dm2 = _pair(layers=2)
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("data", "model"))
        def run(mesh_or_none):
            dep = Deployment(model, base, batch_size=2, prompt_len=8,
                             max_len=32, bank_size=4, mesh=mesh_or_none,
                             param_axes=axes if mesh_or_none else None)
            dep.publish("v1", dm1)
            dep.publish("v2", dm2)
            rids = [dep.submit(np.arange(1, 7), variant=v, max_new_tokens=m)
                    for v, m in [("v1", 2), ("__base__", 5), ("v2", 3)]]
            dep.drain()
            eng = dep.engine
            out = ([dep.result(r).out_tokens for r in rids],
                   {k: eng.metrics[k] for k in ("prefills", "prefill_rows",
                                                "prefill_rows_computed")},
                   sorted(eng.step_hlo()))
            dep.close()
            return out
        got, counts, kinds = run(mesh)
        want, single, _ = run(None)
        assert got == want, (got, want)
        assert counts == {"prefills": 2, "prefill_rows": 3,
                          "prefill_rows_computed": 4}, counts
        assert kinds == ["decode_banked", "prefill_banked"], kinds
        assert single["prefill_rows_computed"] == 3, single
        print("OK")
    """)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=root, timeout=600)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-3000:]


def test_engine_sharded_group_mode_parity():
    """The group scheduler (dense + fused residency) also runs sharded:
    same tokens as single-device for both residency modes."""
    mesh = _mesh22()
    model, base, axes, dm1, _ = _pair(layers=2)

    def run(mode, mesh_or_none):
        kw = {}
        if mesh_or_none is not None:
            rules = S.rules_for("decode")
            param_sh = S.tree_shardings(base, axes, rules, mesh_or_none)
            kw = dict(param_shardings=param_sh, mesh=mesh_or_none,
                      param_axes=axes)
            params = jax.device_put(base, param_sh)
        else:
            params = base
        reg = VariantRegistry(params, mode=mode, max_resident=4, **kw)
        reg.register("v1", dm1)
        eng = ServingEngine(model, reg, batch_size=2, prompt_len=8,
                            max_len=32, scheduler="group",
                            mesh=mesh_or_none)
        rids = [eng.submit(np.arange(1, 7), variant=v, max_new_tokens=3)
                for v in ["v1", "__base__", "v1"]]
        eng.run_until_drained()
        return [eng.result(r).out_tokens for r in rids]

    for mode in ("fused", "dense"):
        assert run(mode, mesh) == run(mode, None), mode


def test_registry_bank_hotswap_sharded():
    """Versioned hot-swap over the sharded bank: update moves the pointer,
    rollback re-admits as a bank hit, tokens match the unsharded path."""
    mesh = _mesh22()
    model, base, axes, dm1, dm2 = _pair(layers=2)

    def run(mesh_or_none):
        dep = Deployment(model, base, batch_size=2, prompt_len=8,
                         max_len=32, bank_size=4, mesh=mesh_or_none,
                         param_axes=axes if mesh_or_none else None)
        dep.publish("v", dm1)
        out = []
        r1 = dep.submit(np.arange(1, 7), variant="v", max_new_tokens=3)
        dep.drain()
        out.append(dep.result(r1).out_tokens)
        dep.update("v", dm2)
        r2 = dep.submit(np.arange(1, 7), variant="v", max_new_tokens=3)
        dep.drain()
        out.append(dep.result(r2).out_tokens)
        dep.rollback("v")
        hits_before = dep.stats["hits"]
        r3 = dep.submit(np.arange(1, 7), variant="v", max_new_tokens=3)
        dep.drain()
        out.append(dep.result(r3).out_tokens)
        return out, dep.stats["hits"] - hits_before

    want, _ = run(None)
    got, hits = run(mesh)
    assert got == want
    assert got[0] == got[2]              # rollback serves v1 again (tokens
                                         # of v1/v2 may coincide on a toy
                                         # model — only v1==v1 is contract)
    assert hits >= 1                     # rollback re-admitted as bank hit
