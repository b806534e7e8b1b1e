"""Partitioned kernel dispatch: per-shard Pallas delta GEMMs (DESIGN.md §12).

The sharded serving path (DESIGN.md §11) jits whole decode steps with
explicit in/out shardings and lets GSPMD partition everything inside —
including the delta kernels.  That works for the interpret-mode (CPU)
lowering, but on a real TPU mesh a ``pl.pallas_call`` is a single opaque
custom call: GSPMD cannot slice into it, so the global kernel would force
full all-gathers of the very weight tiles the mesh exists to split.  This
module is the explicit alternative: wrap each delta GEMM's sign-plane
term (the base matmul is plain XLA, which GSPMD partitions) in
``shard_map`` so every device runs the Pallas kernel on its OWN overlay
tile, with block sizes picked from shard-local dims and the one required
collective (a psum over the contracted model axis, for column-sharded
weights) stated in the open.

Axis derivation (one source of truth, shared with the storage layer):

* the caller passes the shadowed weight's logical axes ``waxes`` (the same
  ``(*lead, out_ax, in_ax)`` tuples ``models/param.py`` declares and
  ``delta_overlay.entry_axes`` consumes);
* ``resolve_spec`` maps them onto the active mesh under the active rule
  set — exactly the resolution that placed the weight, overlay and bank
  leaves on device, so shard_map's in_specs describe layouts the operands
  already have (no resharding on the hot path);
* the packed sign plane is STORED with its byte dim replicated
  (``entry_axes`` — it is 8x smaller than the weight), but when the
  weight's in-axis is model-sharded the in_specs here slice that byte dim
  to the shard: each device reads only its K-tile's bytes.

Activation: the dispatch keys off the ambient ``shard_ctx`` (mesh + rules
— serving/engine.py already traces every sharded step inside it), so ops
wrappers route here automatically on a mesh and fall back to the global
jit path single-device.  ``no_dispatch()`` restores the PR-4 GSPMD
behaviour for A/B parity and latency comparisons
(benchmarks/shard_map_kernels.py; engine ``kernel_dispatch="gspmd"``).

Fallback contract: every entry point returns ``None`` when a per-shard
lowering is not possible — unknown weight axes, or a shard-local K tile
that is not a multiple of the packing width (``_pick_block`` refuses
those) — and the ops wrapper then serves the global kernel and counts
the decline (``note_decline``): on a TPU mesh that kernel cannot be
partitioned, so a decline is a fault to fix, not a path to serve from.
Dispatch must never change results, only layouts.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

PACK = 8

_local = threading.local()


# ---------------------------------------------------------------------------
# activation
# ---------------------------------------------------------------------------

def state() -> Optional[tuple]:
    """(mesh, rules) when per-shard dispatch should engage, else None.

    Reads the ambient shard_ctx at TRACE time — the sharded engine and the
    dry-run both lower their step jits inside ``with mesh, shard_ctx(...)``,
    so kernels traced there see the pair; tier-1 single-device paths see
    None and keep the global jit wrappers byte-for-byte unchanged."""
    if getattr(_local, "off", 0):
        return None
    from repro.distributed.sharding import active_mesh, active_rules
    mesh = active_mesh()
    rules = active_rules()
    if mesh is None or rules is None:
        return None
    return mesh, rules


@contextlib.contextmanager
def no_dispatch():
    """Force the global (GSPMD-partitioned) kernel path inside an active
    mesh context — the PR-4 baseline the per-shard path is compared
    against, and the escape hatch for callers that vmap over kernels
    (vmap-of-shard_map is not a supported composition here)."""
    prev = getattr(_local, "off", 0)
    _local.off = prev + 1
    try:
        yield
    finally:
        _local.off = prev


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------

def _names(part) -> tuple:
    """Mesh-axis names of one PartitionSpec entry (None -> ())."""
    if part is None:
        return ()
    return part if isinstance(part, tuple) else (part,)


def _size(mesh, part) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return math.prod(sizes[n] for n in _names(part))


@dataclasses.dataclass(frozen=True)
class Plan:
    """Resolved partitioning of one fused delta GEMM.

    ``m_part``: mesh axes of the flattened batch rows (data-parallel lanes);
    ``o_part`` / ``i_part``: mesh axes of the weight's out / in dim — at
    most one is non-None (``resolve_spec`` never assigns a mesh axis
    twice); ``psum_axes``: contracted axes to psum over (non-empty exactly
    when the in dim is sharded, i.e. each shard holds partial sums)."""
    m_part: object
    o_part: object
    i_part: object

    @property
    def psum_axes(self) -> tuple:
        return _names(self.i_part)


def plan_matmul(mesh, rules: dict, waxes, m: Optional[int], n: int,
                k: int) -> Optional[Plan]:
    """Partitioning plan for y[m, n] = x[m, k] @ Ŵ[n, k]ᵀ, or None when
    the per-shard path cannot run (caller falls back to the global
    kernel).  ``waxes`` are the weight's logical axes (last two used);
    ``m=None`` plans weight-only ops (unpack_apply) with no batch dim."""
    if waxes is None or len(waxes) < 2:
        return None
    from repro.distributed.sharding import resolve_spec
    o_part, i_part = resolve_spec((n, k), tuple(waxes[-2:]), rules, mesh)
    m_part = None
    if m is not None:
        m_part = resolve_spec((m,), ("act_batch",), rules, mesh)[0]
        if set(_names(m_part)) & (set(_names(o_part)) | set(_names(i_part))):
            m_part = None       # pathological rule set: batch wins nothing
    if i_part is not None and (k // _size(mesh, i_part)) % PACK:
        # the shard-local K tile (and its packed byte dim) would not align
        # to the packing width — _pick_block rightly refuses such dims, so
        # this matmul stays on the global path
        return None
    # a fully replicated plan still runs per device under shard_map: the
    # global kernel is an opaque custom call GSPMD cannot place
    return Plan(m_part=m_part, o_part=o_part, i_part=i_part)


# compiled shard_map callables, memoized per (op kind, mesh, plan, operand
# shapes/dtypes, statics): every entry point below builds a FRESH closure,
# so without this cache eager callers (e.g. the registry's mesh dense
# reconstruction) would re-trace and re-lower on every call — jit'ing the
# shard_map and keying on everything the trace depends on restores the
# compile-once behaviour of the global @jax.jit wrappers.  Mesh and Plan
# are hashable; shapes/dtypes/statics are plain tuples.
#
# The memo is LRU-BOUNDED: a long-lived multi-variant server sees a new
# key per (shape, mesh, plan) combination and an unbounded dict would
# grow with every novel workload shape for the life of the process.
# Eviction only drops the python wrapper — executables already inlined
# into an engine step jit, or held by a caller, stay alive.
_MEMO_CAP = 256
_compiled: "collections.OrderedDict" = collections.OrderedDict()
memo_stats = {"hits": 0, "misses": 0, "evictions": 0,
              "persist_hits": 0, "persist_compiles": 0,
              "compile_seconds": 0.0, "declined": 0}


def memo_info() -> dict:
    """Dispatch-memo observability: counters + current occupancy
    (engine.status() and benchmarks/run.py surface this)."""
    return {**memo_stats, "entries": len(_compiled), "cap": _MEMO_CAP}


def set_memo_cap(cap: int) -> None:
    """Resize the memo bound (tests); evicts LRU down to ``cap``."""
    global _MEMO_CAP
    if cap < 1:
        raise ValueError("memo cap must be >= 1")
    _MEMO_CAP = cap
    while len(_compiled) > _MEMO_CAP:
        _compiled.popitem(last=False)
        memo_stats["evictions"] += 1


def _persist_parts(key) -> tuple:
    """Map one memo key to process-stable persistent-cache parts: the
    Mesh hashes per-process, so it is replaced by its (axes, shape,
    device-kind) fingerprint; Plans and aval tuples repr stably."""
    from repro.core import compile_cache as CC
    return tuple(CC.mesh_fp(p) if isinstance(p, jax.sharding.Mesh)
                 else repr(p) for p in key)


class _CachedFn:
    """One memo entry: the wrapped jit plus, for EAGER callers, a
    compiled stage resolved through the ambient persistent cache
    (core/compile_cache.py).  Dispatch entry points are usually traced
    inside an outer step jit — there the wrapped call inlines and the
    outer executable owns the compile — but eager callers (the
    registry's mesh dense reconstruction) pay a real per-process
    compile that a warm cache turns into a deserialize."""

    __slots__ = ("key", "jitted", "compiled")

    def __init__(self, key, fn):
        self.key = key
        self.jitted = jax.jit(fn)
        self.compiled = None

    def __call__(self, *args):
        if self.compiled is not None:
            return self.compiled(*args)
        if any(isinstance(a, jax.core.Tracer) for a in args):
            return self.jitted(*args)
        from repro.core import compile_cache as CC
        cc = CC.get_default()
        if cc is None:
            return self.jitted(*args)
        parts = ("dispatch",) + _persist_parts(self.key)
        compiled = cc.get(parts)
        if compiled is None:
            import time
            t0 = time.perf_counter()
            compiled = self.jitted.lower(*args).compile()
            memo_stats["compile_seconds"] += time.perf_counter() - t0
            memo_stats["persist_compiles"] += 1
            cc.put(cc.key(*parts), compiled)
        else:
            memo_stats["persist_hits"] += 1
        self.compiled = compiled
        return compiled(*args)


def _cached_jit(key, build):
    fn = _compiled.get(key)
    if fn is not None:
        _compiled.move_to_end(key)
        memo_stats["hits"] += 1
        return fn
    memo_stats["misses"] += 1
    fn = _CachedFn(key, build())
    _compiled[key] = fn
    while len(_compiled) > _MEMO_CAP:
        _compiled.popitem(last=False)
        memo_stats["evictions"] += 1
    return fn


def _avals(*arrays) -> tuple:
    return tuple((tuple(a.shape), jnp.dtype(a.dtype).name) for a in arrays)


def note_decline() -> None:
    """Count one delta matmul traced inside a mesh context that did not
    get a per-shard kernel (unknown weight axes, a shard-local K that is
    not a packing-width multiple): it runs as the global kernel, which
    GSPMD cannot partition on a TPU mesh.  Read through ``memo_info()``;
    the four-chip smoke run fails on any."""
    memo_stats["declined"] += 1


# ---------------------------------------------------------------------------
# shard_map'd entry points (ops.py routes here; every one may return None)
# ---------------------------------------------------------------------------

def _bank_part(mesh, rules: dict, nb: int, plan: Plan):
    """Mesh partition of the bank slot axis under the active rules (None
    = replicated, the pre-§17 layout).  Pod-local banks resolve to "pod";
    a bank axis that does not divide, or whose mesh axes already carry
    the weight's out/in dim (they share the overlay operands with the
    bank dim — a mesh axis may appear only once per spec), falls back to
    replicated.  ``plan.m_part`` using "pod" is fine: the batch rows live
    in a DIFFERENT operand."""
    from repro.distributed.sharding import resolve_spec
    bp = resolve_spec((nb,), ("bank",), rules, mesh)[0]
    if bp is None:
        return None
    used = set(_names(plan.o_part)) | set(_names(plan.i_part))
    if set(_names(bp)) & used:
        return None
    return bp


def _axes_linear_index(names: tuple):
    """Row-major linear index of this shard over the given mesh axes
    (inside shard_map) — the pod offset term of the banked vidx
    translation."""
    idx = None
    for nm in names:
        ai = jax.lax.axis_index(nm)
        idx = ai if idx is None else idx * jax.lax.psum(1, nm) + ai
    return idx


def sign_delta(st, x2: jax.Array, vidx: jax.Array, packed: jax.Array,
               v_row: jax.Array, v_col: jax.Array,
               waxes) -> Optional[jax.Array]:
    """shard_map'd sign delta of a bank: x2 (M, K) · vidx (M,) · packed
    (V, N, K/8) · v_row (V, N) · v_col (V, K) -> (M, N) fp32.  Each device
    reads its rows' slots from its OWN weight tile's bank; a K-sharded
    weight psums the partial deltas over the contracted axis.

    The bank axis is replicated by default; under pod-local rules
    (DESIGN.md §17) it shards over "pod" and ``vidx`` — which the engine
    writes as GLOBAL slot ids (pod p owns slots [p*S, (p+1)*S)) — is
    translated to the shard-local slot by subtracting this pod's offset.
    The affinity router only ever routes a row to its own pod's slots,
    so the clamp is a memory-safety bound, not a semantic path."""
    mesh, rules = st
    nb, n, _ = packed.shape
    m, k = x2.shape
    plan = plan_matmul(mesh, rules, waxes, m, n, k)
    if plan is None:
        return None
    mp, op, ip = plan.m_part, plan.o_part, plan.i_part
    bp = _bank_part(mesh, rules, nb, plan)
    lnb = nb // _size(mesh, bp)         # shard-local bank slots

    def shard_fn(xl, vi, pk, vr, vc):
        import repro.kernels.ops as O
        if bp is not None:
            off = _axes_linear_index(_names(bp)) * lnb
            vi = jnp.clip(vi - off, 0, lnb - 1)
        y = O.sign_delta_local(xl, vi, pk, vr, vc)
        if plan.psum_axes:
            y = jax.lax.psum(y, plan.psum_axes)
        return y

    operands = (x2, vidx, packed, v_row, v_col)
    fn = _cached_jit(
        ("sign", mesh, plan, bp, _avals(*operands)),
        lambda: shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(mp, ip), P(mp), P(bp, op, ip), P(bp, op),
                      P(bp, ip)),
            out_specs=P(mp, op),    # op is None whenever ip carried model
            check_vma=False))
    return fn(*operands)


def sign_delta_stacked(st, xe: jax.Array, entry,
                       waxes) -> Optional[jax.Array]:
    """shard_map'd per-expert sign deltas: xe (E, M, D) · entry leaves
    (E, F, D/8)/(E, F)/(E, D) -> (E, M, F) fp32.

    The MoE expert stacks shard their EXPERT dim over the model axis, so
    the per-shard body vmaps the kernel over the local experts —
    shard_map(vmap(kernel)), the composition that works, instead of
    vmap(shard_map(kernel)), which does not.  When experts don't divide,
    ffn/embed may carry the axis and the contraction psums."""
    mesh, rules = st
    if waxes is None or len(waxes) != 3:
        return None
    from repro.distributed.sharding import resolve_spec
    e, _, d = xe.shape
    f = entry.packed.shape[1]
    ep, fp, dp = resolve_spec((e, f, d), tuple(waxes), rules, mesh)
    if dp is not None and (d // _size(mesh, dp)) % PACK:
        return None
    psum_axes = _names(dp)

    def shard_fn(xl, pk, vr, vc):
        import repro.kernels.ops as O

        def one(x2, p2, r2, c2):
            return O.sign_delta_local(
                x2, jnp.zeros((x2.shape[0],), jnp.int32), p2[None],
                r2[None], c2[None])

        y = jax.vmap(one)(xl, pk, vr, vc)
        if psum_axes:
            y = jax.lax.psum(y, psum_axes)
        return y

    operands = (xe, entry.packed, entry.v_row, entry.v_col)
    fn = _cached_jit(
        ("stacked", mesh, (ep, fp, dp), _avals(*operands)),
        lambda: shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(ep, None, dp), P(ep, fp, dp), P(ep, fp), P(ep, dp)),
            out_specs=P(ep, None, fp),
            check_vma=False))
    return fn(*operands)


def unpack_apply(st, packed: jax.Array, v: jax.Array, w_base,
                 mode: str, out_dtype, waxes) -> Optional[jax.Array]:
    """shard_map'd Ŵ = v ⊙ unpack(B) + W_b: pure per-tile reconstruction,
    no contraction — every shard rebuilds exactly its own weight tile.
    ``w_base`` may be a QuantWeight (int8 base, per-tile dequant)."""
    from repro.kernels.ops import _unwrap_quant, _v2d
    mesh, rules = st
    wq, ws = _unwrap_quant(w_base)
    n, k = wq.shape
    plan = plan_matmul(mesh, rules, waxes, None, n, k)
    if plan is None:
        return None
    op, ip = plan.o_part, plan.i_part
    v_spec = {"row": P(op, None), "col": P(None, ip),
              "scalar": P(None, None)}[mode]

    def shard_fn(pk, v2, wb, *ws_op):
        import repro.kernels.ops as O
        return O.unpack_apply_local(pk, v2, wb, ws_op[0] if ws_op else None,
                                    out_dtype)

    v2 = _v2d(v, mode, n, k)
    in_specs = (P(op, ip), v_spec, P(op, ip))
    operands = (packed, v2, wq)
    if ws is not None:
        in_specs += (P(op),)
        operands += (ws.reshape(n),)
    fn = _cached_jit(
        ("unpack", mesh, plan, mode, jnp.dtype(out_dtype).name,
         _avals(*operands)),
        lambda: shard_map(
            shard_fn, mesh=mesh,
            in_specs=in_specs,
            out_specs=P(op, ip),
            check_vma=False))
    return fn(*operands)
