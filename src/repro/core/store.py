"""Delta artifact store: serialization + manifest + version lineage.

Artifact layout (one directory per PUBLISHED VERSION of a variant):
  manifest.json   paths, shapes, axis selections, dtypes, sha256 per tensor,
                  base-checkpoint fingerprint (guards against applying a
                  delta to the wrong base), and — store v3 — version
                  lineage: variant name, monotonic version id, parent
                  version, artifact kind ("full" | "patch")
  deltas.npz      full publish: packed masks (uint8) + scale vectors (fp16)
  extras.npz      full publish: uncompressed fine-tuned leaves, fp16
  patch.npz       incremental publish: RLE-encoded XOR of the parent's
                  packed sign planes + sparse fp16 vector/extras updates
                  (core/delta.py wire helpers; exact in the wire domain)

:class:`VariantStore` arranges versions under ``root/<name>/v%04d`` with a
``versions.json`` lineage index per variant whose ``latest`` field is THE
serving pointer — publish advances it, rollback moves it back (constant
time, no artifact IO).  Manifests are finalized with tmp-file +
``os.replace`` so a crash mid-publish can never leave a readable-but-torn
manifest, and an unfinished version directory is invisible until the index
commits.

Masks stay packed end-to-end (paper §Implementation remarks) — the loader
transfers the packed buffer and unpacks on device via the Pallas kernel.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
import os
import pathlib
import threading
import zipfile
from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
import numpy.lib.format as _npformat

from repro.core import delta as D
from repro.core.calibration import DeltaEntry, DeltaModel


def _sha(arr: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(arr).tobytes()).hexdigest()[:16]


def _head(leaf, n: int = 64) -> np.ndarray:
    """The first ``n`` values of ``leaf`` in row-major order.  A leaf over
    1 MiB is sliced where it lives — index 0 on the outer dims and only
    the rows the values span along the next — so a device-resident weight
    sends a few hundred bytes to the host instead of itself; a smaller
    one is copied whole, which costs less than compiling the slice."""
    shape = tuple(leaf.shape)
    if int(np.prod(shape)) * np.dtype(leaf.dtype).itemsize <= 1 << 20:
        return np.asarray(jax.device_get(leaf)).ravel()[:n]
    d, inner = len(shape), 1
    while d > 0 and inner < n:
        d -= 1
        inner *= shape[d]
    sub = leaf[(0,) * d] if d else leaf
    sub = sub[:-(-n // (inner // shape[d]))]
    return np.asarray(jax.device_get(sub)).ravel()[:n]


def base_fingerprint(base_params) -> str:
    """Cheap fingerprint of the base checkpoint (shapes + sampled bytes)."""
    h = hashlib.sha256()
    for path, leaf in sorted(
            ((".".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path), l)
             for path, l in jax.tree_util.tree_flatten_with_path(
                 base_params)[0])):
        h.update(path.encode())
        h.update(str(leaf.shape).encode())
        h.update(_head(leaf).tobytes())
    return h.hexdigest()[:16]


STORE_VERSION = 3   # v3: version lineage (variant/version/parent/kind)
                    # v2: artifact_bytes + per-file sizes persisted on disk


def _write_manifest(out: pathlib.Path, manifest: dict) -> None:
    """Atomic finalize: the manifest appears complete or not at all.
    ``os.replace`` (not rename-semantics-by-luck) so a crash between write
    and publish leaves only the tmp file, which readers never look at."""
    tmp = out / "manifest.json.tmp"
    tmp.write_text(json.dumps(manifest, indent=2))
    os.replace(tmp, out / "manifest.json")


def read_manifest(in_dir: str) -> dict:
    """Read + structurally validate a manifest; a torn/truncated file (a
    crash that bypassed the atomic finalize, a partial copy) raises IOError
    instead of surfacing as a confusing JSON/KeyError downstream."""
    path = pathlib.Path(in_dir) / "manifest.json"
    if not path.exists():
        raise IOError(f"no manifest at {path}")
    try:
        manifest = json.loads(path.read_text())
    except json.JSONDecodeError as e:
        raise IOError(f"torn or corrupt manifest {path}: {e}") from e
    if not isinstance(manifest, dict) or \
            not {"deltas", "extras"} <= set(manifest):
        raise IOError(f"torn or corrupt manifest {path}: "
                      "missing required sections")
    return manifest


# ---------------------------------------------------------------------------
# streamed per-module ingest (the async admission pipeline's read side)
# ---------------------------------------------------------------------------

DEFAULT_CHUNK_BYTES = 4 << 20   # bounded read granularity per payload chunk


def _device_put_copies() -> bool:
    """Whether ``jax.device_put`` of a numpy array COPIES host memory on
    this backend.  CPU zero-copies (the numpy buffer becomes the device
    buffer), so a staging buffer handed to the device must never be
    recycled there; accelerators copy across PCIe and the buffer is
    reusable once the transfer future resolves.  Probed once."""
    global _DEVICE_PUT_COPIES
    if _DEVICE_PUT_COPIES is None:
        probe = np.arange(32, dtype=np.uint8)
        dev = jax.device_put(probe)
        jax.block_until_ready(dev)
        probe[0] ^= 0xFF
        _DEVICE_PUT_COPIES = int(np.asarray(dev)[0]) != int(probe[0])
    return _DEVICE_PUT_COPIES


_DEVICE_PUT_COPIES: Optional[bool] = None


class StagingPool:
    """Reusable host staging buffers for streamed ingest.

    ``take`` returns a buffer of the requested (shape, dtype), reusing a
    released buffer of the same byte size when one exists; ``give``
    releases a buffer back.  The pool keeps at most ``max_buffers`` per
    size class, so an ingest pipeline's peak host RAM is O(largest module
    × in-flight window), not O(artifact).

    On zero-copy backends (CPU: ``jax.device_put`` aliases the numpy
    buffer) ``give`` of a device-transferred buffer is refused by the
    caller passing ``transferred=True`` — recycling it would rewrite live
    bank weights."""

    def __init__(self, max_buffers: int = 2):
        self.max_buffers = max_buffers
        self._free: dict[int, list] = {}
        self.stats = {"takes": 0, "reuses": 0, "drops": 0}

    def take(self, shape, dtype) -> np.ndarray:
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        self.stats["takes"] += 1
        bucket = self._free.get(nbytes)
        if bucket:
            self.stats["reuses"] += 1
            raw = bucket.pop()
        else:
            raw = np.empty(nbytes, np.uint8)
        return raw.view(np.dtype(dtype))[: int(np.prod(shape))].reshape(shape)

    def give(self, arr: np.ndarray, *, transferred: bool = False) -> None:
        if transferred and not _device_put_copies():
            # the "host" buffer IS the device buffer now — dropping our
            # reference hands ownership to jax; recycling would corrupt
            self.stats["drops"] += 1
            return
        raw = arr.view(np.uint8).reshape(-1)
        base = raw.base if raw.base is not None else raw
        bucket = self._free.setdefault(int(raw.nbytes), [])
        if len(bucket) < self.max_buffers:
            bucket.append(np.asarray(base).view(np.uint8).reshape(-1))
        else:
            self.stats["drops"] += 1


def _stream_npz_member(zf: zipfile.ZipFile, member: str, *,
                       chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                       pool: Optional[StagingPool] = None) -> np.ndarray:
    """Read ONE .npy member of an (uncompressed) npz in bounded chunks
    into a host array, checking truncation per chunk — a short stream
    raises IOError at the first missing byte instead of np.load silently
    mis-parsing (or buffering the whole payload first)."""
    with zf.open(member) as f:
        version = _npformat.read_magic(f)
        if version == (1, 0):
            shape, fortran, dtype = _npformat.read_array_header_1_0(f)
        elif version == (2, 0):
            shape, fortran, dtype = _npformat.read_array_header_2_0(f)
        else:                       # exotic npy version: no streaming path
            return _npformat.read_array(f)
        count = int(np.prod(shape))
        out = (pool.take(shape, dtype) if pool is not None
               else np.empty(count, dtype).reshape(shape))
        buf = out.reshape(-1).view(np.uint8)
        nbytes = count * dtype.itemsize
        got = 0
        while got < nbytes:
            want = min(int(chunk_bytes), nbytes - got)
            n = f.readinto(memoryview(buf)[got:got + want])
            if not n:
                raise IOError(
                    f"truncated artifact member {member}: got {got} of "
                    f"{nbytes} bytes")
            got += n
        if fortran:                 # np.savez writes C-order; be tolerant
            out = out.reshape(-1).reshape(shape[::-1]).T
    return out


def iter_artifact_modules(in_dir: str, *, verify: bool = True,
                          chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                          pool: Optional[StagingPool] = None,
                          pacer: Optional[Callable[[], None]] = None
                          ) -> Iterator[tuple]:
    """Stream a FULL artifact module by module: yields
    ``("delta", path, info, {packed, v_row, v_col, use_row})`` then
    ``("extra", path, info, array)``, all host numpy arrays read in
    bounded chunks (peak host RAM is O(largest module), not O(artifact)).
    Per-module sha verification happens here, host-side, so a consumer on
    an ingest thread never hands a corrupt module to the device.

    The manifest-level file-size check still runs first (catches container
    truncation cheaply); the per-chunk check above catches member-level
    truncation the container sizes cannot see.

    ``pacer`` (if given) is called between module streams.  A background
    ingest thread passes a short sleep here so it yields the host between
    modules instead of monopolising cores for the whole read — on hosts
    where ingest and decode dispatch share CPUs, this bounds how much of
    the ingest any single decode step can absorb (serving-SLO pacing)."""
    path = pathlib.Path(in_dir)
    manifest = read_manifest(path)
    if manifest.get("kind", "full") != "full":
        raise ValueError(
            f"{path} holds an incremental update patch (parent version "
            f"{manifest.get('lineage', {}).get('parent_version')}); "
            "materialise it via VariantStore.load")
    if verify:
        for fname, nbytes in manifest.get("files", {}).items():
            actual = (path / fname).stat().st_size \
                if (path / fname).exists() else -1
            if actual != nbytes:
                raise IOError(
                    f"truncated artifact: {fname} is {actual} bytes, "
                    f"manifest records {nbytes}")
    with zipfile.ZipFile(path / "deltas.npz") as zf:
        for p, info in manifest["deltas"].items():
            key = p.replace(".", "__")
            fields = {f: _stream_npz_member(zf, f"{key}__{f}.npy",
                                            chunk_bytes=chunk_bytes,
                                            pool=pool)
                      for f in ("packed", "v_row", "v_col", "use_row")}
            if verify and _sha(fields["packed"]) != info["sha"]:
                raise IOError(f"corrupt mask for {p}")
            yield "delta", p, info, fields
            if pacer is not None:
                pacer()
    with zipfile.ZipFile(path / "extras.npz") as zf:
        for p, info in manifest["extras"].items():
            arr = _stream_npz_member(zf, p.replace(".", "__") + ".npy",
                                     chunk_bytes=chunk_bytes, pool=pool)
            if verify and _sha(arr) != info["sha"]:
                raise IOError(f"corrupt extra for {p}")
            yield "extra", p, info, arr
            if pacer is not None:
                pacer()


def save_artifact(dm: DeltaModel, out_dir: str, *,
                  base_fp: Optional[str] = None,
                  meta: Optional[dict] = None,
                  lineage: Optional[dict] = None) -> dict:
    """Full publish.  ``lineage`` (store v3) records
    {variant, version, parent_version} for VariantStore-managed artifacts;
    standalone artifacts (the v1/v2 call shape) simply omit it."""
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    manifest = {"version": STORE_VERSION, "kind": "full",
                "base_fingerprint": base_fp, "lineage": lineage or {},
                "meta": meta or {}, "deltas": {}, "extras": {}}
    dz, ez = {}, {}
    for path, e in dm.deltas.items():
        key = path.replace(".", "__")
        packed = np.asarray(jax.device_get(e.packed))
        use_row = np.asarray(jax.device_get(e.use_row))
        v_row = np.asarray(jax.device_get(e.v_row)).astype(np.float16)
        v_col = np.asarray(jax.device_get(e.v_col)).astype(np.float16)
        dz[f"{key}__packed"] = packed
        dz[f"{key}__v_row"] = v_row
        dz[f"{key}__v_col"] = v_col
        dz[f"{key}__use_row"] = use_row
        manifest["deltas"][path] = {
            "packed_shape": list(packed.shape),
            "scalar": bool(e.scalar),
            "sha": _sha(packed),
            "axis_counts": {
                "row": int(use_row.sum()),
                "col": int(use_row.size - use_row.sum())},
        }
    for path, v in dm.extras.items():
        key = path.replace(".", "__")
        arr = np.asarray(jax.device_get(v)).astype(np.float16)
        ez[key] = arr
        manifest["extras"][path] = {"shape": list(arr.shape),
                                    "sha": _sha(arr)}
    np.savez(out / "deltas.npz", **dz)
    np.savez(out / "extras.npz", **ez)
    # payload sizes are known once the npz files exist, so artifact_bytes
    # (and per-file sizes, for truncation detection at load) can be
    # PERSISTED in manifest.json rather than only returned to the caller
    manifest["files"] = {f: (out / f).stat().st_size
                         for f in ("deltas.npz", "extras.npz")}
    manifest["artifact_bytes"] = sum(manifest["files"].values())
    _write_manifest(out, manifest)
    return manifest


def load_artifact(in_dir: str, *, expect_base_fp: Optional[str] = None,
                  verify: bool = True,
                  chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                  pacer: Optional[Callable[[], None]] = None) -> DeltaModel:
    """Load a FULL artifact.  Accepts v1 (no size accounting), v2, and v3
    (lineage) manifests; patch artifacts need their parent and load through
    ``VariantStore.load``.

    The payload is STREAMED per module in ``chunk_bytes`` reads with
    per-chunk truncation checks (``iter_artifact_modules``) — the whole
    artifact is never buffered in host RAM before device transfer, so
    peak host footprint is O(largest module)."""
    path = pathlib.Path(in_dir)
    manifest = read_manifest(path)
    if manifest.get("kind", "full") == "full" and expect_base_fp and \
            manifest.get("base_fingerprint") and \
            manifest["base_fingerprint"] != expect_base_fp:
        raise ValueError(
            f"artifact built for base {manifest['base_fingerprint']}, "
            f"got {expect_base_fp}")
    deltas, extras = {}, {}
    for kind, p, info, payload in iter_artifact_modules(
            path, verify=verify, chunk_bytes=chunk_bytes, pacer=pacer):
        if kind == "delta":
            deltas[p] = DeltaEntry(
                packed=jnp.asarray(payload["packed"]),
                v_row=jnp.asarray(payload["v_row"]).astype(jnp.float32),
                v_col=jnp.asarray(payload["v_col"]).astype(jnp.float32),
                use_row=jnp.asarray(payload["use_row"]),
                scalar=info["scalar"])
        else:
            extras[p] = jnp.asarray(payload)
    return DeltaModel(deltas=deltas, extras=extras)


# ---------------------------------------------------------------------------
# incremental update patches (store v3, kind="patch")
# ---------------------------------------------------------------------------

def _wire_entry(e: DeltaEntry) -> dict:
    """One delta entry in the WIRE domain (what a full publish stores):
    uint8 packed planes, fp16 vectors, bool selector."""
    return {"packed": np.asarray(jax.device_get(e.packed), np.uint8),
            "v_row": np.asarray(jax.device_get(e.v_row)).astype(np.float16),
            "v_col": np.asarray(jax.device_get(e.v_col)).astype(np.float16),
            "use_row": np.asarray(jax.device_get(e.use_row), bool)}


def save_update_patch(parent_dm: DeltaModel, new_dm: DeltaModel,
                      out_dir: str, *, base_fp: Optional[str] = None,
                      meta: Optional[dict] = None,
                      lineage: Optional[dict] = None) -> dict:
    """Incremental publish: write ``new_dm`` as a patch against
    ``parent_dm`` (the materialised parent VERSION, i.e. wire-domain
    values).  Per changed module: RLE-encoded XOR of the packed sign
    planes + sparse fp16 vector/selector/extras updates.  Unchanged
    modules cost nothing.  The manifest records the sha of each patched
    module's RESULT so materialisation verifies against the same integrity
    bar as a full publish (and applying to the wrong parent is caught).

    Raises ValueError when the module structure changed (added/removed
    modules, shape or scalar-mode changes) — publish a full version then.
    """
    if set(parent_dm.deltas) != set(new_dm.deltas) or \
            set(parent_dm.extras) != set(new_dm.extras):
        raise ValueError(
            "module structure changed between versions; publish full")
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    manifest = {"version": STORE_VERSION, "kind": "patch",
                "base_fingerprint": base_fp, "lineage": lineage or {},
                "meta": meta or {}, "deltas": {}, "extras": {}}
    pz = {}

    def encode(key: str, field: str, old: np.ndarray, new: np.ndarray
               ) -> bool:
        starts, lens, lits = D.zrle_encode(D.xor_bytes(old, new))
        if starts.size == 0:
            return False
        pz[f"{key}__{field}_starts"] = starts
        pz[f"{key}__{field}_lens"] = lens
        pz[f"{key}__{field}_lits"] = lits
        return True

    for path, ne in new_dm.deltas.items():
        pe = parent_dm.deltas[path]
        if pe.scalar != ne.scalar:
            raise ValueError(
                f"{path}: scalar mode changed between versions; publish full")
        old, new = _wire_entry(pe), _wire_entry(ne)
        key = path.replace(".", "__")
        changed = [f for f in ("packed", "v_row", "v_col", "use_row")
                   if encode(key, f, old[f], new[f])]
        if not changed:
            continue                    # module untouched by this version
        manifest["deltas"][path] = {
            "packed_shape": list(new["packed"].shape),
            "scalar": bool(ne.scalar),
            "sha": _sha(new["packed"]),
            "changed": changed,
            "sizes": {f: int(new[f].nbytes)
                      for f in ("packed", "v_row", "v_col", "use_row")}}
    for path, nv in new_dm.extras.items():
        old = np.asarray(jax.device_get(parent_dm.extras[path])
                         ).astype(np.float16)
        new = np.asarray(jax.device_get(nv)).astype(np.float16)
        key = path.replace(".", "__")
        if not encode(key, "x", old, new):
            continue
        manifest["extras"][path] = {"shape": list(new.shape),
                                    "sha": _sha(new)}
    np.savez(out / "patch.npz", **pz)
    manifest["files"] = {"patch.npz": (out / "patch.npz").stat().st_size}
    manifest["artifact_bytes"] = manifest["files"]["patch.npz"]
    _write_manifest(out, manifest)
    return manifest


def load_update_patch(in_dir: str, *, verify: bool = True
                      ) -> tuple[dict, dict, dict]:
    """Read a patch artifact -> (manifest, delta_patches, extras_patches)
    in the decoded form ``loader.apply_update`` consumes (dense XOR
    buffers, sparse index/value arrays)."""
    path = pathlib.Path(in_dir)
    manifest = read_manifest(path)
    if manifest.get("kind") != "patch":
        raise ValueError(f"{path} is not an update patch")
    if verify:
        for fname, nbytes in manifest.get("files", {}).items():
            actual = (path / fname).stat().st_size \
                if (path / fname).exists() else -1
            if actual != nbytes:
                raise IOError(
                    f"truncated patch: {fname} is {actual} bytes, "
                    f"manifest records {nbytes}")
    pz = np.load(path / "patch.npz")

    def decode(key: str, field: str, nbytes: int) -> np.ndarray:
        if f"{key}__{field}_starts" not in pz:
            return np.zeros(nbytes, np.uint8)      # field untouched
        return D.zrle_decode(pz[f"{key}__{field}_starts"],
                             pz[f"{key}__{field}_lens"],
                             pz[f"{key}__{field}_lits"], nbytes)

    delta_patches, extras_patches = {}, {}
    for p, info in manifest["deltas"].items():
        key = p.replace(".", "__")
        sz = info["sizes"]
        delta_patches[p] = {
            "packed": decode(key, "packed", sz["packed"]),
            "v_row": decode(key, "v_row", sz["v_row"]).view(np.uint16),
            "v_col": decode(key, "v_col", sz["v_col"]).view(np.uint16),
            "use_row": decode(key, "use_row", sz["use_row"]
                              ).view(np.bool_)}
    for p, info in manifest["extras"].items():
        key = p.replace(".", "__")
        nbytes = 2 * int(np.prod(info["shape"]))
        extras_patches[p] = decode(key, "x", nbytes).view(np.uint16)
    return manifest, delta_patches, extras_patches


# ---------------------------------------------------------------------------
# VariantStore: versioned variant library (the publish side of the
# lifecycle control plane; serving/api.Deployment is the serving side)
# ---------------------------------------------------------------------------

class VariantStore:
    """A library of variants, each a lineage of immutable versions.

    Layout::

        root/<name>/versions.json      lineage index + ``latest`` pointer
        root/<name>/v0001/             full publish (manifest v3 + npz)
        root/<name>/v0002/             full OR patch (parent_version=1)

    Version ids are monotonic per variant (rollback moves the pointer, a
    later publish still gets max+1).  Version directories are immutable
    once the index commits, so in-memory materialisation caching is always
    valid and rollback is a constant-time pointer move.  The cache is
    LRU-BOUNDED (``cache_versions``): under the frequent-update workload
    every version would otherwise stay alive forever — the serving side
    already frees stale residents, so the store must not re-leak them."""

    INDEX = "versions.json"

    def __init__(self, root, *, base_fp: Optional[str] = None,
                 cache_versions: int = 4, param_shardings=None):
        self.root = pathlib.Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.base_fp = base_fp
        self.cache_versions = max(1, cache_versions)
        # optional base-weight shardings tree (sharded deployments set it
        # — serving/api.Deployment): chain-walk patches then apply on the
        # derived per-leaf placements (loader.apply_update), so a freshly
        # materialised version starts life sharded instead of being
        # re-laid-out at its first serve
        self.param_shardings = param_shardings
        self._cache: "collections.OrderedDict[tuple, DeltaModel]" = \
            collections.OrderedDict()
        # publish (control thread) and load (admission-pipeline ingest
        # thread) share the materialisation cache + index files: serialise
        # them (reentrant: publish_update loads its parent)
        self._lock = threading.RLock()

    # -- index -------------------------------------------------------------
    def _vdir(self, name: str, version: int) -> pathlib.Path:
        return self.root / name / f"v{version:04d}"

    def _read_index(self, name: str) -> dict:
        p = self.root / name / self.INDEX
        if not p.exists():
            raise KeyError(f"unknown variant {name!r}")
        try:
            return json.loads(p.read_text())
        except json.JSONDecodeError as e:
            raise IOError(f"torn or corrupt index {p}: {e}") from e

    def _write_index(self, name: str, idx: dict) -> None:
        d = self.root / name
        d.mkdir(parents=True, exist_ok=True)
        tmp = d / (self.INDEX + ".tmp")
        tmp.write_text(json.dumps(idx, indent=2))
        os.replace(tmp, d / self.INDEX)     # pointer moves are atomic

    def names(self) -> list:
        return sorted(p.parent.name
                      for p in self.root.glob(f"*/{self.INDEX}"))

    def versions(self, name: str) -> list:
        return sorted(int(v) for v in self._read_index(name)["versions"])

    def latest(self, name: str) -> int:
        return int(self._read_index(name)["latest"])

    def version_info(self, name: str, version: int) -> dict:
        idx = self._read_index(name)
        try:
            return idx["versions"][str(version)]
        except KeyError:
            raise KeyError(f"variant {name!r} has no version {version}")

    def lineage(self, name: str, version: Optional[int] = None) -> list:
        """Version chain [full, ..., version] in patch-apply order."""
        v = self.latest(name) if version is None else version
        chain = []
        while True:
            info = self.version_info(name, v)
            chain.append(v)
            if info["kind"] == "full":
                return list(reversed(chain))
            v = int(info["parent"])

    # -- publish / update / rollback ---------------------------------------
    def _next_version(self, name: str) -> tuple[dict, int]:
        try:
            idx = self._read_index(name)
        except KeyError:
            idx = {"schema": 1, "latest": 0, "versions": {}}
        vers = [int(v) for v in idx["versions"]]
        return idx, max(vers, default=0) + 1

    @staticmethod
    def _check_name(name: str) -> None:
        """Variant names become directory names: restrict to a safe
        charset and forbid path traversal ('.', '..') — '@' is reserved
        for the registry's ``name@vN`` version addressing."""
        ok = bool(name) and name not in (".", "..") and \
            all(c.isalnum() or c in "._-" for c in name)
        if not ok:
            raise ValueError(f"invalid variant name {name!r}")

    def publish(self, name: str, dm: DeltaModel, *,
                meta: Optional[dict] = None) -> int:
        """Full publish: next monotonic version, latest pointer advances.
        Crash-safe ordering: payload npz -> atomic manifest -> atomic
        index; an unfinished version never becomes visible."""
        self._check_name(name)
        with self._lock:
            return self._publish_locked(name, dm, meta=meta)

    def _publish_locked(self, name: str, dm: DeltaModel, *,
                        meta: Optional[dict] = None) -> int:
        idx, v = self._next_version(name)
        manifest = save_artifact(
            dm, self._vdir(name, v), base_fp=self.base_fp, meta=meta,
            lineage={"variant": name, "version": v, "parent_version": None})
        idx["versions"][str(v)] = {
            "kind": "full", "parent": None,
            "dir": self._vdir(name, v).name,
            "artifact_bytes": manifest["artifact_bytes"]}
        idx["latest"] = v
        self._write_index(name, idx)
        return v

    def publish_update(self, name: str, dm: DeltaModel, *,
                       meta: Optional[dict] = None) -> int:
        """Incremental publish: ``dm`` becomes the next version as a patch
        against the CURRENT latest (which must exist — publish full
        first).  Typically moves far fewer bytes than a full publish: the
        version-to-version residual is small (BitDelta's observation), so
        the XOR planes RLE down and the fp16 diffs stay sparse."""
        self._check_name(name)
        with self._lock:
            return self._publish_update_locked(name, dm, meta=meta)

    def _publish_update_locked(self, name: str, dm: DeltaModel, *,
                               meta: Optional[dict] = None) -> int:
        parent_v = self.latest(name)
        parent = self.load(name, parent_v)
        idx, v = self._next_version(name)
        manifest = save_update_patch(
            parent, dm, self._vdir(name, v), base_fp=self.base_fp,
            meta=meta, lineage={"variant": name, "version": v,
                                "parent_version": parent_v})
        idx["versions"][str(v)] = {
            "kind": "patch", "parent": parent_v,
            "dir": self._vdir(name, v).name,
            "artifact_bytes": manifest["artifact_bytes"]}
        idx["latest"] = v
        self._write_index(name, idx)
        return v

    def rollback(self, name: str, to_version: Optional[int] = None) -> int:
        """Move the ``latest`` pointer back — constant time, no artifact
        IO.  Default target: the highest version id below the current
        pointer."""
        with self._lock:
            return self._rollback_locked(name, to_version)

    def _rollback_locked(self, name: str, to_version: Optional[int]) -> int:
        idx = self._read_index(name)
        cur = int(idx["latest"])
        if to_version is None:
            older = [int(v) for v in idx["versions"] if int(v) < cur]
            if not older:
                raise ValueError(
                    f"variant {name!r} has no version below {cur}")
            to_version = max(older)
        if str(to_version) not in idx["versions"]:
            raise KeyError(f"variant {name!r} has no version {to_version}")
        idx["latest"] = int(to_version)
        self._write_index(name, idx)
        return int(to_version)

    # -- materialisation ---------------------------------------------------
    def load(self, name: str, version: Optional[int] = None, *,
             verify: bool = True,
             pacer: Optional[Callable[[], None]] = None) -> DeltaModel:
        """Materialise a version: load the nearest full ancestor, apply
        patches forward (one jitted op per module,
        ``loader.apply_update``).  Results are cached per (name, version)
        — version dirs are immutable, so the cache never goes stale.

        ``pacer`` propagates to the streamed artifact read and runs between
        chain steps (see :func:`iter_artifact_modules`); note the store
        lock is held across the pacing sleeps, so a pacing ingest delays
        concurrent publishes, never corrupts them."""
        with self._lock:
            return self._load_locked(name, version, verify=verify,
                                     pacer=pacer)

    def _load_locked(self, name: str, version: Optional[int], *,
                     verify: bool,
                     pacer: Optional[Callable[[], None]] = None
                     ) -> DeltaModel:
        from repro.core import loader as L
        v = self.latest(name) if version is None else int(version)
        if (name, v) in self._cache:
            self._cache.move_to_end((name, v))
            return self._cache[(name, v)]
        chain = self.lineage(name, v)
        # start at the DEEPEST cached ancestor: with the steady-state
        # cache holding the previous version, an incremental update never
        # re-reads (or re-verifies) the full root artifact from disk
        start = 0
        for i in range(len(chain) - 1, -1, -1):
            if (name, chain[i]) in self._cache:
                start = i
                break
        for step in chain[start:]:
            if (name, step) in self._cache:
                self._cache.move_to_end((name, step))
                continue
            vdir = self._vdir(name, step)
            info = self.version_info(name, step)
            if info["kind"] == "full":
                dm = load_artifact(vdir, expect_base_fp=self.base_fp,
                                   verify=verify, pacer=pacer)
            else:
                manifest, dpatch, epatch = load_update_patch(vdir,
                                                             verify=verify)
                if self.base_fp and manifest.get("base_fingerprint") and \
                        manifest["base_fingerprint"] != self.base_fp:
                    raise ValueError(
                        f"patch built for base "
                        f"{manifest['base_fingerprint']}, got {self.base_fp}")
                dm = L.apply_update(self._cache[(name, int(info["parent"]))],
                                    dpatch, epatch,
                                    param_shardings=self.param_shardings)
                if verify:
                    self._verify_patched(manifest, dm, vdir)
            self._cache[(name, step)] = dm
            if pacer is not None:
                pacer()
        dm = self._cache[(name, v)]
        self._cache.move_to_end((name, v))
        # trim OUTSIDE the chain walk (a parent must never vanish before
        # its patch applies); the bound frees old versions' device arrays
        while len(self._cache) > self.cache_versions:
            self._cache.popitem(last=False)
        return dm

    @staticmethod
    def _verify_patched(manifest: dict, dm: DeltaModel,
                        vdir: pathlib.Path) -> None:
        """Patched modules must hash to the sha of the NEW version the
        publisher recorded — catches corruption AND wrong-parent apply."""
        for p, info in manifest["deltas"].items():
            got = _sha(np.asarray(jax.device_get(dm.deltas[p].packed),
                                  np.uint8))
            if got != info["sha"]:
                raise IOError(f"patched mask mismatch for {p} in {vdir}")
        for p, info in manifest["extras"].items():
            got = _sha(np.asarray(jax.device_get(dm.extras[p])
                                  ).astype(np.float16))
            if got != info["sha"]:
                raise IOError(f"patched extra mismatch for {p} in {vdir}")

    def artifact_bytes(self, name: str, version: int) -> int:
        return int(self.version_info(name, version)["artifact_bytes"])


def save_checkpoint_fp16(params, out_path: str) -> int:
    """Full fp16 checkpoint (the baseline the paper compares load against)."""
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        key = "__".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        flat[key] = np.asarray(jax.device_get(leaf)).astype(np.float16)
    p = pathlib.Path(out_path)
    p.parent.mkdir(parents=True, exist_ok=True)
    np.savez(p, **flat)
    return p.stat().st_size
