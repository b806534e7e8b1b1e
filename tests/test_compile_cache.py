"""Compile-once serving (DESIGN.md §14): persistent compile cache safety,
AOT warmup, and the dispatch-memo LRU bound.

The cache's contract is asymmetric on purpose: a warm entry may only ever
be (a) the right executable or (b) a MISS.  Corruption, truncation,
environment drift and topology changes must all degrade to a clean
compile — never an exception on the serving path, never a wrong program.
Warm-path value is gated the same way the CI benchmark gates it: zero
compiles after warmup, bit-exact greedy tokens versus the cold path.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import calibration as C
from repro.core import compile_cache as CC
from repro.kernels import dispatch as _dp
from repro.models import build_model
from repro.models.param import split
from repro.serving import Deployment

PROMPT = np.arange(1, 7)


@pytest.fixture(autouse=True)
def _no_xla_cache_leak():
    """use_jax_cache points jax's own persistent cache at a directory;
    unhook the global config after each test."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


# ---------------------------------------------------------------------------
# JAX's persistent cache placement (entry points call use_jax_cache)
# ---------------------------------------------------------------------------

def test_jax_cache_follows_env_var(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, that directory is the cache and
    no other is configured."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert CC.use_jax_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_jax_cache_defaults_to_checkout(monkeypatch):
    """Without the variable, the cache is the fixed <checkout>/.jax_cache
    (never a temporary, per-process or timestamped path)."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    import pathlib
    want = pathlib.Path(CC.__file__).resolve().parents[3] / ".jax_cache"
    assert (want.parent / "pyproject.toml").exists() or \
        (want.parent / "ROADMAP.md").exists()
    assert CC.use_jax_cache() == str(want)
    assert jax.config.jax_compilation_cache_dir == str(want)
    assert CC.use_jax_cache() == str(want)      # stable across calls


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_config("deepseek-7b").reduced(),
                              num_layers=1, compute_dtype="float32",
                              remat=False)
    model = build_model(cfg)
    base, _ = split(model.init(jax.random.PRNGKey(0)))
    pert, _ = split(model.init(jax.random.PRNGKey(1)))
    ft = jax.tree.map(lambda b, p: b + 0.05 * p, base, pert)
    return model, base, C.compress(base, ft)


# ---------------------------------------------------------------------------
# CompileCache: round-trip + every stale/corrupt shape reads as a miss
# ---------------------------------------------------------------------------

def _compiled_double():
    return jax.jit(lambda x: x * 2).lower(jnp.ones((4,), jnp.float32)) \
        .compile()


def test_roundtrip_and_counters(tmp_path):
    cc = CC.CompileCache(tmp_path)
    parts = ("unit", "double", CC.aval_fp(jnp.ones((4,), jnp.float32)))
    assert cc.get(parts) is None
    assert cc.stats["misses"] == 1
    assert cc.put(parts, _compiled_double())
    assert cc.stats["puts"] == 1
    exe = cc.get(parts)
    assert exe is not None and cc.stats["hits"] == 1
    np.testing.assert_array_equal(
        np.asarray(exe(jnp.ones((4,), jnp.float32))), np.full((4,), 2.0))


def test_corrupt_and_truncated_entries_miss(tmp_path):
    cc = CC.CompileCache(tmp_path)
    parts = ("unit", "corrupt")
    cc.put(parts, _compiled_double())
    entry = cc._entry(cc.key(*parts))

    entry.write_bytes(b"not a pickle at all")
    assert cc.get(parts) is None
    assert cc.stats["corrupt"] == 1

    cc.put(parts, _compiled_double())
    entry.write_bytes(entry.read_bytes()[: entry.stat().st_size // 2])
    assert cc.get(parts) is None          # truncated mid-payload
    assert cc.stats["corrupt"] == 2

    entry.write_bytes(b"")                # zero-length file
    assert cc.get(parts) is None
    assert cc.stats["corrupt"] == 3


def test_env_fingerprint_mismatch_misses(tmp_path):
    import pickle
    cc = CC.CompileCache(tmp_path)
    parts = ("unit", "envdrift")
    cc.put(parts, _compiled_double())
    entry = cc._entry(cc.key(*parts))
    # simulate a cache dir hand-copied from another env: same key file,
    # different recorded environment
    e = pickle.loads(entry.read_bytes())
    e["env"] = ("jax-999", "jaxlib-999", "tpu", "TPU v9", 8192, "deadbeef")
    entry.write_bytes(pickle.dumps(e))
    assert cc.get(parts) is None
    assert cc.stats["env_mismatch"] == 1


def test_mesh_and_code_fingerprints_separate_keys(tmp_path):
    cc = CC.CompileCache(tmp_path)
    dev = np.array(jax.devices()[:1])
    mesh_a = jax.sharding.Mesh(dev.reshape(1), ("data",))
    mesh_b = jax.sharding.Mesh(dev.reshape(1, 1), ("data", "model"))
    assert CC.mesh_fp(mesh_a) != CC.mesh_fp(mesh_b) != CC.mesh_fp(None)
    base = ("engine-step", "decode")
    keys = {cc.key(*base, CC.mesh_fp(m)) for m in (mesh_a, mesh_b, None)}
    assert len(keys) == 3
    # an entry stored under one topology can never be read under another
    cc.put(base + (CC.mesh_fp(mesh_a),), _compiled_double())
    assert cc.get(base + (CC.mesh_fp(mesh_b),)) is None


def test_cached_callable_static_kwargs_and_persistence(tmp_path):
    cc = CC.CompileCache(tmp_path)
    fn = jax.jit(lambda x, n: x * n, static_argnames=("n",))
    x = jnp.ones((3,), jnp.float32)

    a = CC.CachedCallable(fn, ("unit", "mul"), cache=cc)
    np.testing.assert_array_equal(np.asarray(a(x, n=3)), np.full((3,), 3.0))
    assert cc.stats["compiles"] == 1
    np.testing.assert_array_equal(np.asarray(a(x, n=3)), np.full((3,), 3.0))
    assert cc.stats["compiles"] == 1      # in-process executable reuse

    # a fresh instance (fresh process stand-in) deserializes, not compiles
    b = CC.CachedCallable(fn, ("unit", "mul"), cache=cc)
    np.testing.assert_array_equal(np.asarray(b(x, n=3)), np.full((3,), 3.0))
    assert cc.stats["compiles"] == 1 and cc.stats["hits"] >= 1
    # different static value -> different key -> fresh compile
    np.testing.assert_array_equal(np.asarray(b(x, n=4)), np.full((3,), 4.0))
    assert cc.stats["compiles"] == 2


# ---------------------------------------------------------------------------
# dispatch memo: bounded LRU
# ---------------------------------------------------------------------------

def test_dispatch_memo_lru_cap():
    saved_cap = _dp.memo_info()["cap"]
    saved = dict(_dp._compiled)
    _dp._compiled.clear()
    for k in ("hits", "misses", "evictions"):
        _dp.memo_stats[k] = 0
    try:
        _dp.set_memo_cap(2)
        f1 = _dp._cached_jit(("t", 1), lambda: (lambda x: x + 1))
        _dp._cached_jit(("t", 2), lambda: (lambda x: x + 2))
        assert _dp._cached_jit(("t", 1), None) is f1   # hit, no rebuild
        _dp._cached_jit(("t", 3), lambda: (lambda x: x + 3))
        info = _dp.memo_info()
        assert info["entries"] == 2 and info["evictions"] == 1
        # ("t", 2) was LRU and evicted; ("t", 1) survived the cap
        assert ("t", 2) not in _dp._compiled
        assert ("t", 1) in _dp._compiled
        assert info["hits"] == 1 and info["misses"] == 3
        # re-requesting the evicted key is a clean rebuild, not an error
        f2b = _dp._cached_jit(("t", 2), lambda: (lambda x: x + 2))
        assert np.asarray(f2b(jnp.zeros(()))) == 2
        with pytest.raises(ValueError):
            _dp.set_memo_cap(0)
    finally:
        _dp.set_memo_cap(saved_cap)
        _dp._compiled.clear()
        _dp._compiled.update(saved)


# ---------------------------------------------------------------------------
# engine integration: warm restart = zero compiles + bit-exact tokens
# ---------------------------------------------------------------------------

def _dep(model, base, cache_dir, **kw):
    kw.setdefault("batch_size", 2)
    kw.setdefault("prompt_len", 8)
    kw.setdefault("max_len", 32)
    kw.setdefault("bank_size", 4)
    return Deployment(model, base, compile_cache_dir=cache_dir, **kw)


def _serve(dep, variant, n=6):
    rid = dep.submit(PROMPT, variant=variant, max_new_tokens=n)
    dep.drain()
    assert dep.result(rid).status == "done"
    return dep.result(rid).out_tokens


def test_warm_restart_zero_compiles_bit_exact(setup, tmp_path):
    model, base, dm = setup

    cold = _dep(model, base, tmp_path)
    cold.publish("ft", dm)
    toks_cold = _serve(cold, "ft")
    st_cold = cold.status()
    assert st_cold["steps"]["compiles"] > 0
    assert st_cold["compile_cache"]["puts"] > 0

    # "restart": a fresh Deployment over the same cache dir resolves
    # every step executable by deserializing
    warm = _dep(model, base, tmp_path)
    warm.publish("ft", dm)
    toks_warm = _serve(warm, "ft")
    st_warm = warm.status()
    assert toks_warm == toks_cold
    assert st_warm["steps"]["compiles"] == 0
    assert st_warm["steps"]["cache_hits"] == st_cold["steps"]["compiles"]
    assert st_warm["compile_cache"]["compiles"] == 0
    assert st_warm["compile_cache"]["hits"] > 0


def test_warmup_covers_serving_and_status_counters(setup, tmp_path):
    model, base, dm = setup

    dep = _dep(model, base, tmp_path, warmup=True)
    st = dep.status()
    assert st["warmed"] is True
    compiles_after_warmup = st["steps"]["compiles"]
    assert compiles_after_warmup > 0
    assert st["metrics"]["warmup_seconds"] > 0
    assert set(st["dispatch_memo"]) >= {"hits", "misses", "evictions",
                                        "entries", "cap"}

    # traffic on base AND a published fused variant adds ZERO compiles:
    # warmup's abstract twins are structurally identical to runtime trees
    dep.publish("ft", dm)
    _serve(dep, "__base__")
    _serve(dep, "ft")
    assert dep.status()["steps"]["compiles"] == compiles_after_warmup

    # warm restart with warmup: every pair resolves without compiling
    dep2 = _dep(model, base, tmp_path)
    outcomes = dep2.warmup()
    assert outcomes and all(v in ("hit", "warm") for v in outcomes.values())
    assert dep2.status()["steps"]["compiles"] == 0
