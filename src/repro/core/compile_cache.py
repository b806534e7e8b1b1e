"""Persistent compilation cache: the wrapped→lowered→compiled split.

Restart-to-first-token is a production SLO (ROADMAP "compile-once
serving"): the engine's step pairs and the dispatch layer's shard_map
kernels each cost seconds of XLA compile (moe ``decode_fused`` alone is
11.6s at (2, 16, 16)), re-paid on every server restart even though
nothing about the program changed.  This module makes the three jit
stages explicit — ``jax.jit(fn)`` (wrapped), ``.lower(*args)``
(lowered), ``.compile()`` (compiled) — and persists the COMPILED stage
across processes via ``jax.experimental.serialize_executable`` (the
JaCe ``translation_cache.py`` exemplar, SNIPPETS.md §3).

Safety model — a stale cache can only MISS, never serve a wrong
executable:

* every key is a sha256 over (a) the caller's semantic parts — op kind,
  mesh fingerprint, plan, avals, donation/sharding fingerprints — and
  (b) an ENVIRONMENT fingerprint: jax + jaxlib versions, backend,
  device kind/count, and a content hash of every ``repro`` source file.
  Changing any of them changes the key, so upgrades and code edits
  degrade to a compile + re-populate, not a wrong answer;
* each entry file re-states its environment fingerprint in cleartext
  metadata and ``get`` re-checks it before deserializing (belt and
  braces against key collisions and hand-copied cache dirs);
* corrupt / truncated / undeserializable entries count in ``stats``
  and read as a miss — never an exception on the serving path.

Where executable serialization is unavailable (some backends refuse
``serialize``), ``.compile()`` still finds JAX's own persistent
compilation cache, which :func:`use_jax_cache` places once per process
for the entry points.

Observability: ``stats`` counts hits / misses / compiles /
compile-seconds / corrupt entries / env mismatches — surfaced through
``ServingEngine.status()`` and printed by ``benchmarks/run.py``.
"""
from __future__ import annotations

import hashlib
import os
import pathlib
import pickle
import time
from typing import Optional

import jax
import jax.numpy as jnp

_FORMAT = 1

# -- fingerprints ------------------------------------------------------------

_code_fp_cache: Optional[str] = None


def code_fingerprint() -> str:
    """sha256 over the contents of every ``repro`` source file.  A code
    edit anywhere in the package invalidates the whole cache — coarse,
    but it is the property that lets the warm path skip tracing
    entirely: if the sources are byte-identical, the jaxpr a key's
    parts describe is too."""
    global _code_fp_cache
    if _code_fp_cache is not None:
        return _code_fp_cache
    root = pathlib.Path(__file__).resolve().parents[1]   # src/repro
    h = hashlib.sha256()
    for p in sorted(root.rglob("*.py")):
        h.update(str(p.relative_to(root)).encode())
        h.update(p.read_bytes())
    _code_fp_cache = h.hexdigest()[:16]
    return _code_fp_cache


def env_fingerprint() -> tuple:
    """Everything outside the program that decides whether a serialized
    executable is loadable AND correct here: library versions, backend,
    and the device topology the executable was compiled for."""
    import jaxlib
    devs = jax.devices()
    return (jax.__version__, jaxlib.__version__,
            jax.default_backend(),
            devs[0].device_kind if devs else "none", len(devs),
            code_fingerprint())


def aval_fp(tree) -> tuple:
    """Stable fingerprint of a pytree of arrays / ShapeDtypeStructs:
    (structure string, ((shape, dtype, weak_type), ...)).  Two trees
    with equal fingerprints trace to the same jaxpr arguments."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (str(treedef),
            tuple((tuple(l.shape), jnp.dtype(l.dtype).name,
                   bool(getattr(l, "weak_type", False))) for l in leaves))


def mesh_fp(mesh) -> tuple:
    """Process-stable mesh identity: axis names, shape, device kind.
    (The Mesh object itself hashes per-process — fine for the in-memory
    memo, useless in a persistent key.)"""
    if mesh is None:
        return ("no-mesh",)
    devs = mesh.devices.reshape(-1)
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            devs[0].device_kind if devs.size else "none")


def sharding_fp(tree) -> str:
    """Fingerprint of a pytree of shardings (NamedSharding reprs are
    stable across processes for the same topology); None passes
    through."""
    if tree is None:
        return "none"
    return str(jax.tree.map(
        lambda s: str(s), tree,
        is_leaf=lambda x: x is None or hasattr(x, "devices_indices_map")))


# -- the cache ---------------------------------------------------------------

class CompileCache:
    """Directory-backed store of serialized XLA executables.

    ``get`` returns a loaded ``Compiled`` or None (miss — also on any
    corruption or environment mismatch); ``put`` serializes one; both
    never raise on the serving path.  ``load_or_compile`` is the
    one-stop wrapped→lowered→compiled helper callers use."""

    def __init__(self, path):
        self.path = pathlib.Path(path)
        self.path.mkdir(parents=True, exist_ok=True)
        self.stats = {"hits": 0, "misses": 0, "puts": 0,
                      "compiles": 0, "compile_seconds": 0.0,
                      "deserialize_seconds": 0.0,
                      "corrupt": 0, "env_mismatch": 0,
                      "serialize_failures": 0}

    # -- keys ---------------------------------------------------------------
    def key(self, *parts) -> str:
        """sha256 over the canonical repr of ``parts`` + the environment
        fingerprint.  Parts must be primitives / strings / tuples —
        callers fingerprint meshes and shardings with the helpers
        above, never pass live objects."""
        payload = repr((parts, env_fingerprint())).encode()
        return hashlib.sha256(payload).hexdigest()

    def _entry(self, key: str) -> pathlib.Path:
        return self.path / f"{key}.exe"

    # -- read / write -------------------------------------------------------
    def get(self, parts_or_key):
        """Loaded ``Compiled`` for these key parts, or None.  Corrupt
        files and environment mismatches are counted and read as a
        clean miss."""
        key = (parts_or_key if isinstance(parts_or_key, str)
               else self.key(*parts_or_key))
        p = self._entry(key)
        if not p.exists():
            self.stats["misses"] += 1
            return None
        try:
            with open(p, "rb") as f:
                entry = pickle.load(f)
            if entry.get("format") != _FORMAT:
                self.stats["corrupt"] += 1
                return None
        except Exception:
            self.stats["corrupt"] += 1
            return None
        if entry.get("env") != env_fingerprint():
            # key collisions can't produce this (env is IN the key) but
            # hand-moved cache dirs and truncated hashes could — re-check
            # in cleartext before trusting opaque executable bytes
            self.stats["env_mismatch"] += 1
            return None
        try:
            from jax.experimental import serialize_executable as se
            t0 = time.perf_counter()
            compiled = se.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"])
            self.stats["deserialize_seconds"] += time.perf_counter() - t0
        except Exception:
            self.stats["corrupt"] += 1
            return None
        self.stats["hits"] += 1
        return compiled

    def put(self, key_or_parts, compiled) -> bool:
        """Serialize ``compiled`` under the key; atomic (tmp +
        os.replace) so a crashed writer leaves a clean miss, not a torn
        entry.  Returns False when this executable refuses
        serialization (JAX's own persistent cache may still hold it)."""
        key = (key_or_parts if isinstance(key_or_parts, str)
               else self.key(*key_or_parts))
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(compiled)
        except Exception:
            self.stats["serialize_failures"] += 1
            return False
        entry = {"format": _FORMAT, "env": env_fingerprint(),
                 "payload": payload, "in_tree": in_tree,
                 "out_tree": out_tree}
        tmp = self._entry(key).with_suffix(f".tmp{os.getpid()}")
        try:
            with open(tmp, "wb") as f:
                pickle.dump(entry, f)
            os.replace(tmp, self._entry(key))
        except Exception:
            self.stats["serialize_failures"] += 1
            tmp.unlink(missing_ok=True)
            return False
        self.stats["puts"] += 1
        return True

    def load_or_compile(self, parts, jitted, args, *, ctx=None):
        """The staged path in one call: persistent hit → loaded
        executable; miss → ``jitted.lower(*args).compile()`` (inside
        ``ctx`` — mesh / shard_ctx / dispatch contexts apply at TRACE
        time) and persist.  Returns (compiled, "hit" | "compiled")."""
        import contextlib
        key = self.key(*parts)
        compiled = self.get(key)
        if compiled is not None:
            return compiled, "hit"
        t0 = time.perf_counter()
        with (ctx if ctx is not None else contextlib.nullcontext()):
            compiled = jitted.lower(*args).compile()
        self.stats["compiles"] += 1
        self.stats["compile_seconds"] += time.perf_counter() - t0
        self.put(key, compiled)
        return compiled, "compiled"


# -- JAX's own persistent cache ----------------------------------------------

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_jax_cache() -> str:
    """Place JAX's persistent compilation cache for an entry point
    (chip_smoke.py, launch/serve.py, benchmarks/run.py) and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and nothing is set here; otherwise the cache is the fixed
    ``<checkout>/.jax_cache`` — a fixed path, because the directory is
    part of what makes a later process find the entries again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# -- process default ---------------------------------------------------------
# One ambient cache per process, configured by REPRO_COMPILE_CACHE_DIR:
# the dispatch memo and the bank-write jit pick it up without plumbing a
# handle through every layer; Deployment(compile_cache_dir=...) overrides
# explicitly for its engine.  Tests install their own via set_default.

_default: object = None
_default_resolved = False


def get_default() -> Optional[CompileCache]:
    global _default, _default_resolved
    if not _default_resolved:
        _default_resolved = True
        d = os.environ.get("REPRO_COMPILE_CACHE_DIR")
        if d:
            _default = CompileCache(d)
    return _default


def set_default(cache: Optional[CompileCache]) -> Optional[CompileCache]:
    """Install (or clear, with None) the process-ambient cache; returns
    the previous one so tests can restore it."""
    global _default, _default_resolved
    prev = _default
    _default = cache
    _default_resolved = True
    return prev


class CachedCallable:
    """A jit with an explicit compiled stage behind the persistent cache.

    Call semantics match the wrapped jit exactly:

    * called with TRACERS (inlined into an outer jit trace): delegates
      to the plain jitted call — staging is meaningless mid-trace;
    * called eagerly with no ambient cache: plain jitted call;
    * called eagerly with a cache: resolve wrapped→lowered→compiled
      through it (keyed on ``parts`` + args avals + the environment)
      and call the executable directly.  One executable per distinct
      aval signature is held per instance.

    Static kwargs are supported (forwarded to ``lower`` and folded into
    the key); donation declared on the wrapped jit survives
    serialization, so donated-buffer callers keep their in-place
    semantics on the warm path.
    """

    def __init__(self, jitted, parts, *, cache="ambient"):
        self.jitted = jitted
        self.parts = tuple(parts)
        self._cache = cache
        self._exe: dict = {}

    def cache(self) -> Optional[CompileCache]:
        return get_default() if self._cache == "ambient" else self._cache

    def __call__(self, *args, **kwargs):
        if any(isinstance(a, jax.core.Tracer)
               for a in jax.tree.leaves((args, kwargs))):
            return self.jitted(*args, **kwargs)
        cc = self.cache()
        if cc is None:
            return self.jitted(*args, **kwargs)
        akey = (tuple(aval_fp(a) for a in args),
                tuple(sorted((k, repr(v)) for k, v in kwargs.items())))
        exe = self._exe.get(akey)
        if exe is None:
            exe, _ = cc.load_or_compile(self.parts + (akey,), self.jitted,
                                        args, ctx=None) \
                if not kwargs else self._load_kw(cc, akey, args, kwargs)
            self._exe[akey] = exe
        return exe(*args)

    def aot(self, *args) -> str:
        """Force the compiled stage for these (possibly abstract) args
        now — the warmup hook.  Returns "hit" (persistent cache),
        "compiled", "warm" (already staged in-process), or "none" (no
        cache attached: nothing to stage against)."""
        cc = self.cache()
        if cc is None:
            return "none"
        akey = (tuple(aval_fp(a) for a in args), ())
        if akey in self._exe:
            return "warm"
        exe, how = cc.load_or_compile(self.parts + (akey,), self.jitted,
                                      args)
        self._exe[akey] = exe
        return how

    def _load_kw(self, cc, akey, args, kwargs):
        key = cc.key(*(self.parts + (akey,)))
        compiled = cc.get(key)
        if compiled is not None:
            return compiled, "hit"
        t0 = time.perf_counter()
        compiled = self.jitted.lower(*args, **kwargs).compile()
        cc.stats["compiles"] += 1
        cc.stats["compile_seconds"] += time.perf_counter() - t0
        cc.put(key, compiled)
        return compiled, "compiled"
