"""Serving engine: slot-scheduled continuous batching over packed deltas.

Two schedulers (DESIGN.md §9):

* ``continuous`` (mixed-variant slot scheduler) — the engine keeps ONE
  persistent decode batch of ``batch_size`` SLOTS.  Each slot carries its
  own request, variant index (into the registry's OverlayBank — slot 0 =
  base), decode position and token budget.  Every step: free slots admit
  queued requests (prefill-on-admit, cache rows written into their
  lanes), every active slot appends its pending token (one host sync per
  step), exhausted slots retire IMMEDIATELY and free their lane, and one
  jitted decode serves the whole heterogeneous batch through the banked
  fused delta GEMMs.  Requires
  fused (packed-overlay) residency for every variant.

* ``group`` (compatibility mode, dense residency path) — pending requests
  are grouped BY VARIANT (FIFO head decides), one prefill/decode pair per
  overlay structure; a group decodes to the max budget in the group.

Variants resolve to (params, overlay) in group mode: dense residents pass a
materialised copy with overlay None; fused residents pass the shared base
params plus a packed delta overlay fused into every GEMM on the fly
(serving/variants.py — residency modes and the OverlayBank).

Fault tolerance: a variant whose artifact fails to load has its requests
re-queued up to ``max_retries`` then failed individually — the engine and
other tenants keep serving.

Versioned variants (DESIGN.md §10): admission resolves the variant's
CURRENT version and pins that VERSION KEY for the request's lifetime, so
a hot-swap (``registry.set_version``) mid-flight leaves running lanes on
the version they started with while new admissions serve the new one;
``Request.served_version`` records the resolution.

Mesh-sharded serving (DESIGN.md §11): with ``mesh`` the engine jits every
step pair (plain, fused, banked) with EXPLICIT in/out shardings — batch
rows (tokens, variant_idx, cache act_batch dims, logits) data-parallel so
the continuous-batching slot lanes span the ``data`` axis, params and
overlay/bank leaves tensor-parallel on their weight axes (no per-step
weight collectives: serve rules replicate weights over ``data``).  The
persistent decode cache is pinned to its sharding via out_shardings, so
step N+1 sees exactly the layout step N produced — no resharding, no
recompiles.  Calls run under ``shard_ctx`` so model-internal logical
constraints activate.

Per-shard kernels (DESIGN.md §12): because the steps trace inside
``shard_ctx``, the fused/banked delta GEMMs lower as shard_map'd Pallas
kernels on each device's own weight/overlay tile (kernels/dispatch.py)
instead of trusting GSPMD to partition the opaque kernel call;
``kernel_dispatch="gspmd"`` pins the PR-4 global-kernel lowering for A/B
parity and latency comparisons.

Tracing: each round of the continuous loop is a ``serve.step`` profiler
span (``StepTraceAnnotation``) holding one span per part of it —
``serve.admit``, ``serve.prefill`` (``.wait``), ``serve.merge``,
``serve.emit``, ``serve.decode`` (``.wait``), ``serve.hook`` — and a step
executable resolved inside it shows as ``serve.compile``.  They record
nothing without a profiler session.  ``metrics["host_seconds"]`` sums each
round's wall time less its waits on the device, the step hook and
admission sleeps; ``metrics["prefill_rows"]`` counts the rows admission
waves admitted and ``metrics["prefill_rows_computed"]`` the rows they
computed (each wave's row bucket, ``serve.prefill``'s ``bucket``).

Admission waves (``_prefill_admitted``): a wave of R admitted rows runs
the smallest row bucket B >= R — the powers of two below ``batch_size``,
and ``batch_size`` — as ONE program (``prefill_banked_fn``): a B-row
prefill, each row's first token, and both written into the row's lane of
the persistent batch state.  Lanes sharded over several devices keep the
one full-width bucket, row i for lane i.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import compile_cache as CC
from repro.distributed.sharding import (resolve_spec, rules_for, shard_ctx,
                                        tree_shardings)
from repro.models.model_zoo import Model
from repro.serving.variants import VariantRegistry


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray            # prompt (prompt_len,)
    variant: str = "__base__"
    max_new_tokens: int = 16
    out_tokens: list = dataclasses.field(default_factory=list)
    status: str = "queued"        # queued | running | done | failed
    retries: int = 0
    error: Optional[str] = None
    served_version: Optional[int] = None   # variant version resolved at
                                           # admission (None: base or
                                           # unversioned registration)
    first_token_at: Optional[float] = None   # perf_counter at the first
                                             # emitted token (TTFT metric:
                                             # benchmarks/admission_overlap)
    submitted_at: float = 0.0     # perf_counter at submit() — with
                                  # first_token_at this is the TTFT the
                                  # engine/Deployment status surfaces
    drafted: int = 0              # speculative scheduler: draft tokens
    accepted: int = 0             # offered / accepted for THIS request
                                  # (the per-lane acceptance rate)
    route_pod: Optional[int] = None   # affinity router's sticky pod choice
                                      # (per-pod admission tickets must not
                                      # re-ingest on every poll)


@dataclasses.dataclass
class _Slot:
    """One lane of the persistent continuous-batching decode batch."""
    request: Request
    variant_slot: int             # GLOBAL bank slot index (base slot of
                                  # the lane's pod for base rows)
    remaining: int                # tokens still owed
    vkey: str = "__base__"        # pinned version key — unpinned at retire
                                  # even if the variant was hot-swapped
                                  # mid-flight
    pod: int = 0                  # pod whose bank shard holds the slot
                                  # (pin/unpin are per-pod)


class ServingEngine:
    """Fixed-shape batched serving: batch slots of ``batch_size``, prompts
    padded to ``prompt_len``, KV capacity ``max_len``.

    scheduler: "continuous" (mixed-variant slot scheduler over the overlay
    bank) or "group" (grouped-by-variant compatibility mode — required for
    dense residency).

    mesh: optional ``jax.sharding.Mesh`` with ("data", "model") axes (and
    optionally "pod") — every step jit gains explicit in/out shardings
    (batch data-parallel, weights/overlays model-parallel) and runs under
    the serving rule context.  Requires registry.param_shardings.

    kernel_dispatch: "shard_map" (default) lowers the fused/banked delta
    GEMMs as per-shard Pallas kernels under shard_map (kernels/dispatch.py
    — each device runs its own weight tile's kernel, DESIGN.md §12);
    "gspmd" restores the PR-4 behaviour of handing the global kernel to
    GSPMD to partition (the A/B baseline — on a real TPU mesh the opaque
    kernel call cannot be partitioned, so this mode exists for parity and
    latency comparison, benchmarks/shard_map_kernels.py).  Both modes must
    emit bit-identical greedy tokens.  Ignored without a mesh."""

    def __init__(self, model: Model, registry: VariantRegistry, *,
                 batch_size: int = 4, prompt_len: int = 32,
                 max_len: int = 128, max_retries: int = 1,
                 greedy: bool = True, scheduler: str = "group",
                 mesh=None, kernel_dispatch: str = "shard_map",
                 admission=None, compile_cache=None,
                 draft_k: int = 4, spec_adaptive: bool = True):
        if scheduler not in ("group", "continuous", "speculative"):
            raise ValueError(f"unknown scheduler {scheduler!r}")
        if kernel_dispatch not in ("shard_map", "gspmd"):
            raise ValueError(f"unknown kernel_dispatch {kernel_dispatch!r}")
        if admission is not None and scheduler == "group":
            raise ValueError(
                "async admission requires scheduler='continuous' (staged "
                "overlays commit into the overlay bank between decode "
                "steps; the group scheduler admits dense residents inline)")
        if scheduler == "speculative":
            from repro.models.transformer import layer_pattern
            if model.cfg.family in ("dense", "moe", "vlm") and any(
                    e["window"] > 0 for e in layer_pattern(model.cfg)):
                raise ValueError(
                    "scheduler='speculative' requires windowless KV "
                    "caches: sliding-window layers ring-buffer their "
                    "writes, so rewinding rejected draft tokens would "
                    "clobber in-window history (DESIGN.md §15)")
        # pod-local banks (DESIGN.md §17): lanes split evenly across pods
        # (act_batch shards pod-major, so lane i belongs to pod
        # i // (batch_size // pods)); the affinity router below steers
        # requests to lanes whose pod already holds their variant
        self._pods = getattr(registry, "pods", 1)
        if self._pods > 1:
            if scheduler == "speculative":
                raise ValueError(
                    "scheduler='speculative' does not support pod-local "
                    "banks (pod_banks=True): drafting serves the base "
                    "through shared params, but verify rounds would need "
                    "per-pod slot translation the round fn lacks — use "
                    "scheduler='continuous'")
            if mesh is None:
                raise ValueError(
                    "pod-local banks need the engine's mesh (the lane->"
                    "pod mapping comes from the act_batch sharding)")
            if batch_size % self._pods:
                raise ValueError(
                    f"batch_size={batch_size} must divide evenly across "
                    f"{self._pods} pods (lanes block-partition pod-major)")
        self.model = model
        self.registry = registry
        self.batch_size = batch_size
        self.prompt_len = prompt_len
        self.max_len = max_len
        self.max_retries = max_retries
        self.scheduler = scheduler
        self.mesh = mesh
        self.kernel_dispatch = kernel_dispatch
        # optional serving/admission.AdmissionPipeline: variants are
        # ingested+staged off-thread and committed between decode steps
        # (drain hook in _serve_continuous) instead of loaded inline at
        # bank_acquire; queued requests behind ingest report "admitting"
        self.admission = admission
        self._queue: collections.deque[Request] = collections.deque()
        self._done: dict[int, Request] = {}
        self._next_rid = 0

        # one compiled pair per overlay STRUCTURE: dense variants trace
        # with overlay=None, fused variants with their entry tree — the
        # packed deltas ride in as ordinary jit arguments
        def prefill_fn(params, overlay, batch):
            return model.prefill(params, batch, max_len, overlay=overlay)

        def decode_fn(params, overlay, token, cache):
            logits, cache = model.decode_step(params, token, cache,
                                              overlay=overlay)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        # banked pair: ONE compiled prefill/decode serves every mix of
        # resident variants — the bank tree and per-row variant_idx are
        # plain jit arguments, so admissions/evictions never recompile.
        # The prefill is an admission wave: it prefills the wave's rows,
        # takes each row's first token, and writes both into lane
        # ``lanes[r]`` of the batch state (a pad row's lane is out of
        # range: its write is dropped)
        cache_axes = [sp.index("act_batch") for sp in jax.tree.leaves(
            model.cache_pspecs(), is_leaf=lambda x: isinstance(x, tuple))]

        def prefill_banked_fn(params, bank, vidx, batch, lanes, token,
                              cache):
            logits, fresh = model.prefill(params, batch, max_len,
                                          overlay=bank, variant_idx=vidx)
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            old, treedef = jax.tree_util.tree_flatten(cache)
            new = jax.tree_util.tree_leaves(fresh)
            assert len(cache_axes) == len(old) == len(new), \
                "cache_pspecs out of sync with the cache structure"
            return (_write_rows(token, first, lanes, 0),
                    jax.tree_util.tree_unflatten(treedef, [
                        _write_rows(o, f, lanes, ax)
                        for o, f, ax in zip(old, new, cache_axes)]))

        def decode_banked_fn(params, bank, vidx, token, cache):
            logits, cache = model.decode_step(params, token, cache,
                                              overlay=bank,
                                              variant_idx=vidx)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        self._fns = {"prefill": prefill_fn, "decode": decode_fn,
                     "prefill_banked": prefill_banked_fn,
                     "decode_banked": decode_banked_fn}
        # arg roles drive the explicit in_shardings on a mesh; vidx shards
        # exactly like the token vector (one entry per batch lane)
        self._roles = {"prefill": ("params", "overlay", "batch"),
                       "decode": ("params", "overlay", "token", "cache"),
                       "prefill_banked": ("params", "overlay", "token",
                                          "batch", "token", "token",
                                          "cache"),
                       "decode_banked": ("params", "overlay", "token",
                                         "token", "cache")}
        # speculative rounds (serving/speculative.py): one executable per
        # draft length on the adaptive ladder — each k is a compile-time
        # scan length.  Same signature/roles as decode_banked, so the
        # sharded staging + compile cache + warmup machinery carry over.
        self.spec = None
        if scheduler == "speculative":
            from repro.serving import speculative as SPEC
            self.spec = SPEC.AcceptanceTracker(draft_k,
                                               adaptive=spec_adaptive)
            for k in self.spec.ladder:
                self._fns[f"spec_k{k}"] = SPEC.make_round_fn(model, k)
                self._roles[f"spec_k{k}"] = ("params", "overlay", "token",
                                             "token", "cache")
        # executable store: ONE AOT-compiled executable per (kind,
        # overlay structure) — the wrapped→lowered→compiled split
        # (DESIGN.md §14).  The overlay is the only argument whose
        # STRUCTURE varies between calls of one kind; every other aval
        # is fixed by the engine's shape contract, and the Compiled
        # object itself validates avals at call time, so a violated
        # assumption raises instead of mis-serving.
        self._exe: dict = {}
        # persistent compile cache (core/compile_cache.py): explicit
        # handle wins, else the process-ambient REPRO_COMPILE_CACHE_DIR
        # default; None serves compile-per-process like before
        self.compile_cache = (compile_cache if compile_cache is not None
                              else CC.get_default())
        self.warmed = False
        if mesh is not None:
            if registry.param_shardings is None:
                raise ValueError(
                    "a sharded engine needs registry.param_shardings "
                    "(resolve them with distributed.sharding."
                    "tree_shardings under the serve rules)")
            self._rules = rules_for(
                "decode", pod_banks=getattr(registry, "pod_banks", False))
            cache_struct = jax.eval_shape(
                lambda: model.init_cache(batch_size, max_len))
            self._cache_sh = tree_shardings(cache_struct,
                                            model.cache_pspecs(),
                                            self._rules, mesh)
            tok_spec = resolve_spec((batch_size,), ("act_batch",),
                                    self._rules, mesh)
            self._tok_sh = NamedSharding(mesh, tok_spec)
            # prefill logits (B, V): batch rows follow the lanes; the
            # vocab dim is gathered for the host-side argmax
            self._logits_sh = NamedSharding(
                mesh, PartitionSpec(*(list(tok_spec) + [None])))
            self._batch_axes = model.batch_pspecs("prefill")
        # admission-wave row buckets, each its own step kind (and program,
        # all named prefill_banked_fn): the powers of two below batch_size,
        # and batch_size.  Lanes sharded over several devices keep the one
        # full-width bucket — a pod-local slot is read on its lane's pod,
        # and data-parallel rows must divide the lane shards
        buckets = [1 << k for k in range(batch_size.bit_length())
                   if 1 << k < batch_size] + [batch_size]
        if mesh is not None and \
                self._tok_sh.shard_shape((batch_size,))[0] < batch_size:
            buckets = [batch_size]
        self._wave_kinds = {b: "prefill_banked" if b == batch_size
                            else f"prefill_banked/r{b}" for b in buckets}
        for kind in self._wave_kinds.values():
            self._fns[kind] = self._fns["prefill_banked"]
            self._roles[kind] = self._roles["prefill_banked"]
        # the lane state before the first wave — every lane's pending
        # token and an empty cache row, placed as the decode step keeps
        # them — staged through the persistent cache like the steps
        self._empty_lanes = CC.CachedCallable(
            jax.jit(lambda: (jnp.zeros((batch_size,), jnp.int32),
                             model.init_cache(batch_size, max_len)),
                    out_shardings=(None if mesh is None
                                   else (self._tok_sh, self._cache_sh))),
            ("engine-lanes", repr(model.cfg), batch_size, max_len,
             CC.mesh_fp(mesh)),
            cache=self.compile_cache)
        # continuous-scheduler state (persists across run_until_drained
        # calls: the decode batch is a long-lived object)
        self._slots: list[Optional[_Slot]] = [None] * batch_size
        self._cache = None
        self._next_tok = None
        # each idle lane serves ITS POD's base slot (slot p*bank_size —
        # zero deltas = exact base); a single-pod/global bank keeps the
        # historical all-zeros vector
        self._base_vidx = np.array(
            [self._lane_pod(i) * registry.bank_size if self._pods > 1
             else 0 for i in range(batch_size)], np.int32)
        self._variant_idx = self._base_vidx.copy()
        self._variant_idx_dev = None     # device copy, rebuilt on change
        # bounded TTFT reservoir behind the p50/p99 status() reports:
        # first _ttft_cap samples fill it, later ones overwrite in
        # arrival order (deterministic sliding window, no RNG)
        self._ttft_cap = 1024
        self._ttft_samples: list = []
        self.metrics = {"tokens_generated": 0,
                        "prefills": 0, "prefill_rows": 0,
                        "prefill_rows_computed": 0, "failed": 0,
                        "admitted": 0, "retired": 0, "decode_steps": 0,
                        "prefill_seconds": 0.0, "decode_seconds": 0.0,
                        "host_seconds": 0.0,
                        "async_admits": 0,
                        "step_compiles": 0, "step_cache_hits": 0,
                        "step_compile_seconds": 0.0,
                        "warmup_seconds": 0.0,
                        "spec_rounds": 0, "spec_drafted": 0,
                        "spec_accepted": 0,
                        "ttft_count": 0, "ttft_seconds_sum": 0.0,
                        "ttft_seconds_max": 0.0,
                        "affinity_hits": 0, "affinity_misses": 0}
        # warmup registry (extensible — register_warmup): each entry
        # builds its step pairs from the shared abstract-twin context, so
        # new step kinds (e.g. the speculative ladder) warm through the
        # same AOT/persistent-cache path as the core pairs
        self._warmup_reg = {"plain": self._warm_plain,
                            "fused": self._warm_fused,
                            "banked": self._warm_banked}
        if self.spec is not None:
            self._warmup_reg["speculative"] = self._warm_speculative
        # benchmark hook (benchmarks/admission_overlap.py): with
        # record_step_times=True every decode step appends
        # (perf_counter_at_end, seconds, admission_in_flight) — the
        # stall-ceiling evidence
        self.record_step_times = False
        self.step_times: list = []
        # seconds the current continuous round spent off the engine's host
        # code: blocked on the device, in the step hook, asleep on
        # admission (reset by each round, _round)
        self._off_host = 0.0

    # -- sharded step dispatch -----------------------------------------------
    def _arg_sharding(self, role: str, arg):
        """Explicit sharding for one step argument by role (mesh mode)."""
        if role == "params":
            return self.registry.param_shardings
        if role == "overlay":
            # overlay/bank leaves were committed to their derived
            # placements by loader.device_put_overlay / OverlayBank —
            # pin exactly those (None for the dense overlay-free trace)
            return jax.tree.map(lambda l: l.sharding, arg)
        if role == "token":
            if arg.shape[0] == self.batch_size:
                return self._tok_sh
            return NamedSharding(self.mesh, resolve_spec(
                arg.shape, ("act_batch",), self._rules, self.mesh))
        if role == "cache":
            return self._cache_sh
        if role == "batch":
            return {k: NamedSharding(
                self.mesh, resolve_spec(v.shape, self._batch_axes[k],
                                        self._rules, self.mesh))
                for k, v in arg.items()}
        raise ValueError(role)

    def _trace_ctx(self):
        """Context the step functions LOWER inside: mesh + serving-rule
        shard_ctx (so logical constraints apply and kernels/dispatch.py
        sees the pair at trace time) + the kernel-dispatch pin.  The
        contexts decide how the trace lowers; the resulting executable
        is context-free at call time, which is what lets a deserialized
        one skip tracing entirely."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.kernels import dispatch as _dp
        stack = contextlib.ExitStack()
        stack.enter_context(self.mesh)
        stack.enter_context(shard_ctx(self.mesh, self._rules))
        if self.kernel_dispatch == "gspmd":
            # "shard_map" lets kernels/dispatch.py lower per-shard
            # kernels; "gspmd" pins the PR-4 global-kernel path for A/B
            stack.enter_context(_dp.no_dispatch())
        return stack

    def _stage_jit(self, kind: str, args):
        """The WRAPPED stage: the step jit, with explicit in/out
        shardings on a mesh (batch lanes data-parallel, weights and
        overlays model-parallel, cache pinned in place)."""
        if self.mesh is None:
            return jax.jit(self._fns[kind])
        in_sh = tuple(self._arg_sharding(role, arg)
                      for role, arg in zip(self._roles[kind], args))
        if kind.startswith("prefill_banked"):
            out_sh = (self._tok_sh, self._cache_sh)
        elif kind.startswith("prefill"):
            out_sh = (self._logits_sh, self._cache_sh)
        elif kind.startswith("spec_k"):
            # (ver (B,T), n_acc (B,), next_tok (B,), cache): the token
            # matrix shards its rows like the lane vector, T replicated
            out_sh = (self._logits_sh, self._tok_sh, self._tok_sh,
                      self._cache_sh)
        else:
            out_sh = (self._tok_sh, self._cache_sh)
        return jax.jit(self._fns[kind], in_shardings=in_sh,
                       out_shardings=out_sh)

    def _persist_parts(self, kind: str, args) -> tuple:
        """Persistent-cache key parts for one step executable: the model
        config (two architectures can share avals but not programs), the
        engine's shape contract, the dispatch mode, mesh + sharding
        fingerprints, and every argument's avals.  Library versions,
        backend, devices and a source-tree hash ride in
        ``CompileCache.key`` — a stale entry can only miss."""
        in_sh = "none"
        if self.mesh is not None:
            in_sh = CC.sharding_fp(tuple(
                self._arg_sharding(role, arg)
                for role, arg in zip(self._roles[kind], args)))
        return ("engine-step", kind, repr(self.model.cfg),
                self.batch_size, self.prompt_len, self.max_len,
                self.kernel_dispatch, CC.mesh_fp(self.mesh), in_sh,
                tuple(CC.aval_fp(a) for a in args))

    def _get_exe(self, kind: str, args):
        """One step executable through the staged path: in-process hit →
        persistent-cache deserialize → ``lower().compile()`` (persisted
        for the next restart).  The in-process key flattens just the
        overlay tree — not the full params+cache pytrees — on the
        per-token hot path."""
        key = (kind, jax.tree_util.tree_structure(args[1]))
        exe = self._exe.get(key)
        if exe is not None:
            return exe
        with TraceAnnotation("serve.compile", kind=kind):
            cc = self.compile_cache
            if cc is not None:
                exe = cc.get(self._persist_parts(kind, args))
                if exe is not None:
                    self.metrics["step_cache_hits"] += 1
                    self._exe[key] = exe
                    return exe
            jitted = self._stage_jit(kind, args)
            t0 = time.perf_counter()
            with self._trace_ctx():
                exe = jitted.lower(*args).compile()
            self.metrics["step_compiles"] += 1
            self.metrics["step_compile_seconds"] += time.perf_counter() - t0
            if cc is not None:
                cc.put(cc.key(*self._persist_parts(kind, args)), exe)
        self._exe[key] = exe
        return exe

    def step_hlo(self) -> dict:
        """Optimized HLO text of each step program resolved so far, by
        kind (``prefill_banked/r<B>`` for a wave bucket of B rows below
        ``batch_size``).  Its instructions carry the op metadata that a
        chip's profiler trace lacks (the name stack, with the
        ``base_gemm`` and ``attention`` scopes), under the names the
        trace gives its ops."""
        return {kind: exe.as_text() for (kind, _), exe in self._exe.items()}

    def _call(self, kind: str, *args):
        """Run one step executable (resolving it on first use)."""
        return self._get_exe(kind, args)(*args)

    # -- API -----------------------------------------------------------------
    def submit(self, tokens, variant: str = "__base__",
               max_new_tokens: int = 16) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(Request(rid=rid, tokens=np.asarray(tokens),
                                   variant=variant,
                                   max_new_tokens=max_new_tokens,
                                   submitted_at=time.perf_counter()))
        return rid

    def _note_first_token(self, r: Request) -> None:
        """Stamp TTFT at a request's first emitted token and fold it into
        the engine aggregates ``status()`` surfaces."""
        if r.first_token_at is not None:
            return
        r.first_token_at = time.perf_counter()
        ttft = r.first_token_at - r.submitted_at
        n = self.metrics["ttft_count"]
        self.metrics["ttft_count"] = n + 1
        self.metrics["ttft_seconds_sum"] += ttft
        self.metrics["ttft_seconds_max"] = max(
            self.metrics["ttft_seconds_max"], ttft)
        if len(self._ttft_samples) < self._ttft_cap:
            self._ttft_samples.append(ttft)
        else:
            self._ttft_samples[n % self._ttft_cap] = ttft

    def result(self, rid: int) -> Request:
        return self._done[rid]

    def request(self, rid: int) -> Optional[Request]:
        """The Request object wherever it lives (done, in a decode slot,
        or still queued); None for unknown rids.  Never raises."""
        if rid in self._done:
            return self._done[rid]
        for s in self._slots:
            if s is not None and s.request.rid == rid:
                return s.request
        for r in self._queue:
            if r.rid == rid:
                return r
        return None

    def status(self, rid: Optional[int] = None):
        """With ``rid``: that request's lifecycle string (queued |
        admitting | running | done | failed | unknown — never raises;
        ``admitting`` means the variant is mid-ingest on the async
        admission pipeline).  Without ``rid``: the ENGINE observability
        snapshot — scheduler occupancy, step-executable counters,
        persistent-compile-cache and dispatch-memo stats (the restart
        SLO evidence benchmarks/compile_cache.py gates on)."""
        if rid is not None:
            r = self.request(rid)
            return "unknown" if r is None else r.status
        from repro.kernels import dispatch as _dp
        cc = self.compile_cache
        n_ttft = self.metrics["ttft_count"]
        reg = self.registry
        bank = reg.bank
        snap = {
            "scheduler": self.scheduler,
            "pending": self.pending(),
            "active": self.active(),
            "warmed": self.warmed,
            "steps": {"executables": len(self._exe),
                      "compiles": self.metrics["step_compiles"],
                      "cache_hits": self.metrics["step_cache_hits"],
                      "compile_seconds":
                          self.metrics["step_compile_seconds"]},
            "compile_cache": None if cc is None else dict(cc.stats),
            "dispatch_memo": _dp.memo_info(),
            # resident HBM accounting: the base weights (int8 halves this,
            # DESIGN.md §16) NEXT TO the overlay bank — the two terms of
            # the per-device serving footprint
            "hbm": {
                "base_dtype": getattr(reg, "base_dtype", "fp"),
                "base_bytes": reg.base_nbytes(),
                "base_per_device": reg.base_per_device_nbytes(),
                "bank_bytes": bank.nbytes() if bank is not None else 0,
                "bank_per_device": (bank.per_device_nbytes()
                                    if bank is not None else {}),
                # per-pod rollup (DESIGN.md §17): bank bytes + resident
                # slot keys by pod — empty dicts before the first admit
                "bank_per_pod": (bank.per_pod_nbytes()
                                 if bank is not None else {}),
                "bank_resident_per_pod": (bank.pod_resident()
                                          if bank is not None else {}),
            },
            # affinity router counters: a hit steered a request to a pod
            # already holding its variant's slot (zero admission bytes)
            "affinity": {
                "pods": self._pods,
                "hits": self.metrics["affinity_hits"],
                "misses": self.metrics["affinity_misses"],
                "hit_rate": (self.metrics["affinity_hits"]
                             / max(1, self.metrics["affinity_hits"]
                                   + self.metrics["affinity_misses"])),
            },
            # TTFT aggregates (submit -> first emitted token), fed by
            # Request.first_token_at — benchmarks read latency from here
            # instead of poking request internals; percentiles come from
            # the bounded reservoir (_ttft_samples)
            "ttft": {"count": n_ttft,
                     "mean_seconds": (self.metrics["ttft_seconds_sum"]
                                      / n_ttft if n_ttft else 0.0),
                     "max_seconds": self.metrics["ttft_seconds_max"],
                     "p50_seconds": (float(np.percentile(
                         self._ttft_samples, 50))
                         if self._ttft_samples else 0.0),
                     "p99_seconds": (float(np.percentile(
                         self._ttft_samples, 99))
                         if self._ttft_samples else 0.0)},
            "metrics": dict(self.metrics),
        }
        if self.spec is not None:
            snap["speculative"] = self.spec.snapshot()
        return snap

    def pending(self) -> int:
        return len(self._queue)

    def active(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    def register_warmup(self, name: str, builder) -> None:
        """Register (or replace) a warmup entry: ``builder(ctx)`` is
        called from ``warmup()`` with the shared abstract-twin context
        (see ``_warmup_ctx``) and warms its step kinds via
        ``ctx["warm"](tag, kind, args)``.  This is how new step kinds
        join the AOT/persistent-cache path without editing ``warmup()``
        — the speculative ladder registers itself exactly this way."""
        self._warmup_reg[name] = builder

    def warmup(self, pairs=None) -> dict:
        """AOT-compile the step executables for the declared shapes
        BEFORE accepting traffic (ROADMAP "compile-once serving").
        ``pairs`` selects entries from the warmup REGISTRY
        (``register_warmup``); None warms every registered entry — by
        default the plain pair (base model / dense residents), the fused
        pair (single-variant packed overlay + params view), the banked
        pair (the continuous scheduler's overlay bank + per-row
        variant_idx: the decode step and every admission-wave bucket)
        plus the lane state's first cache, and — under
        ``scheduler="speculative"`` — one speculative round per draft
        length on the adaptive ladder, in bank-resident AND bank-empty
        flavours.  With a persistent compile cache attached, a warm
        restart resolves every entry by DESERIALIZING — zero compiles on
        the path to the first token; cold, the compiles happen here
        instead of inside the first request's latency.

        The overlay/bank abstract twins derive from the base params'
        calibration targets (``core/calibration.is_target`` — the same
        recipe ``compress`` uses), so runtime trees of compressed
        variants hit the warmed executables structurally; on a mesh
        every twin leaf carries the same derived sharding the runtime
        device-put places it on.  Returns {pair/kind: "compiled" |
        "hit"} ("hit": resolved without a fresh compile — in-process or
        persistent)."""
        pairs = tuple(self._warmup_reg) if pairs is None else tuple(pairs)
        unknown = [p for p in pairs if p not in self._warmup_reg]
        if unknown:
            raise ValueError(
                f"unknown warmup pairs {unknown!r}; registered: "
                f"{sorted(self._warmup_reg)} (add new step kinds with "
                "register_warmup)")
        t0 = time.perf_counter()
        ctx = self._warmup_ctx()
        for name in pairs:
            self._warmup_reg[name](ctx)
        self.metrics["warmup_seconds"] += time.perf_counter() - t0
        self.warmed = True
        return ctx["outcomes"]

    def _warmup_ctx(self) -> dict:
        """Shared abstract-twin context the warmup builders draw from:
        the base params, fixed-shape batch/token/cache stand-ins, the
        delta/extra path split, and the ``warm`` closure that resolves
        one executable and records "compiled" | "hit"."""
        from repro.core.calibration import flatten_params, is_target

        reg = self.registry
        base = reg.base_params
        bs = self.batch_size
        base_flat = flatten_params(base)
        delta_paths = sorted(p for p, l in base_flat.items()
                             if is_target(p, l))
        ds = set(delta_paths)
        extra_paths = sorted(p for p in base_flat if p not in ds)
        outcomes: dict = {}

        def warm(tag, kind, args):
            c0 = self.metrics["step_compiles"]
            self._get_exe(kind, args)
            outcomes[f"{tag}/{kind}"] = (
                "compiled" if self.metrics["step_compiles"] > c0
                else "hit")

        return {"base": base, "base_flat": base_flat, "ds": ds,
                "delta_paths": delta_paths, "extra_paths": extra_paths,
                "batch": self._prompt_batch({}),
                "token": jnp.zeros((bs,), jnp.int32),
                "vidx": jnp.zeros((bs,), jnp.int32),
                "cache": jax.eval_shape(
                    lambda: self.model.init_cache(bs, self.max_len)),
                "warm": warm, "outcomes": outcomes}

    def _warm_plain(self, ctx) -> None:
        warm = ctx["warm"]
        warm("plain", "prefill", (ctx["base"], None, ctx["batch"]))
        warm("plain", "decode", (ctx["base"], None, ctx["token"],
                                 ctx["cache"]))

    def _warm_fused(self, ctx) -> None:
        from repro.core.calibration import flatten_params, unflatten_like
        from repro.models import delta_overlay as DO
        if not ctx["delta_paths"]:
            return
        ds = ctx["ds"]
        base_flat = ctx["base_flat"]
        # params VIEW: target paths alias the base weight, every other
        # leaf is the variant's fp16 extra (loader.device_put_overlay's
        # layout)
        view = unflatten_like(ctx["base"], {
            p: (l if p in ds
                else jax.ShapeDtypeStruct(l.shape, jnp.float16))
            for p, l in base_flat.items()})
        ov = DO.overlay_struct(base_flat, ctx["delta_paths"])
        if self.mesh is not None:
            ov = self._shard_struct(
                ov, ctx["delta_paths"],
                {p: DO.entry_shardings_from_weight(
                    sh, base_flat[p].ndim)
                 for p, sh in flatten_params(
                     self.registry.param_shardings).items() if p in ds})
        warm = ctx["warm"]
        warm("fused", "prefill", (view, ov, ctx["batch"]))
        warm("fused", "decode", (view, ov, ctx["token"], ctx["cache"]))

    def _bank_struct(self, ctx):
        """Abstract twin of the runtime overlay bank (structure + avals +
        derived shardings) — the banked and speculative warmup entries
        share it."""
        from repro.models import delta_overlay as DO
        # pod-local banks stack every pod's slot range on the one bank axis
        nb = self.registry.bank_size * getattr(self.registry, "pods", 1)
        bank = DO.overlay_struct(ctx["base_flat"], ctx["delta_paths"],
                                 ctx["extra_paths"], bank_size=nb)
        if self.mesh is not None:
            bank = self._shard_struct(
                bank, ctx["delta_paths"] + ctx["extra_paths"],
                DO.overlay_shardings(
                    self.registry.param_axes, ctx["base_flat"],
                    ctx["delta_paths"], ctx["extra_paths"], self._rules,
                    self.mesh, bank_size=nb))
        return bank

    def _warm_banked(self, ctx) -> None:
        if not ctx["delta_paths"]:
            return
        bank = self._bank_struct(ctx)
        warm = ctx["warm"]
        base, token, cache = ctx["base"], ctx["token"], ctx["cache"]
        vidx = ctx["vidx"]
        # pre-first-admission state: the continuous scheduler serves
        # base-only traffic with bank=None until a variant lands
        self._warm_waves(ctx, "banked-empty", None)
        warm("banked-empty", "decode_banked",
             (base, None, vidx, token, cache))
        self._warm_waves(ctx, "banked", bank)
        warm("banked", "decode_banked", (base, bank, vidx, token, cache))
        if self.scheduler in ("continuous", "speculative"):
            ctx["outcomes"]["banked/lanes"] = self._empty_lanes.aot()

    def _warm_waves(self, ctx, tag: str, bank) -> None:
        """Every admission-wave bucket's program for one bank state."""
        for rows, kind in self._wave_kinds.items():
            ctx["warm"](tag, kind, self._wave_struct(
                rows, ctx["base"], bank, ctx["token"], ctx["cache"]))

    def _wave_struct(self, rows: int, params, bank, token, cache) -> tuple:
        """Arguments of the ``rows``-row wave program, the per-row ones
        abstract."""
        row = jax.ShapeDtypeStruct((rows,), jnp.int32)
        batch = jax.eval_shape(lambda: self._prompt_batch({}, rows))
        return (params, bank, row, batch, row, token, cache)

    def _warm_speculative(self, ctx) -> None:
        """One speculative round per ladder rung (each k is its own scan
        length, hence its own executable), in both the bank-resident and
        the pre-first-admission (bank=None) flavours — the two new step
        shapes the scheduler dispatches — and the admission waves it
        shares with the continuous scheduler."""
        warm = ctx["warm"]
        base, token, cache = ctx["base"], ctx["token"], ctx["cache"]
        vidx = ctx["vidx"]
        bank = self._bank_struct(ctx) if ctx["delta_paths"] else None
        self._warm_waves(ctx, "spec-empty", None)
        if bank is not None:
            self._warm_waves(ctx, "spec", bank)
        for k in self.spec.ladder:
            warm("spec-empty", f"spec_k{k}",
                 (base, None, vidx, token, cache))
            if bank is not None:
                warm("spec", f"spec_k{k}",
                     (base, bank, vidx, token, cache))

    @staticmethod
    def _shard_struct(struct: dict, paths, flat_shardings: dict) -> dict:
        """Attach per-leaf shardings to an abstract overlay/bank tree so
        ``_arg_sharding('overlay', ...)`` reads from the twin exactly
        what the runtime device-put trees carry."""
        from repro.models import delta_overlay as DO

        def node_at(tree, path):
            for part in path.split("."):
                tree = tree[part]
            return tree

        out: dict = {}
        for p in paths:
            leaf = node_at(struct, p)
            sh = flat_shardings[p]
            if isinstance(leaf, DO.OverlayEntry):
                leaf = DO.OverlayEntry(*(
                    jax.ShapeDtypeStruct(f.shape, f.dtype, sharding=s)
                    for f, s in ((leaf.packed, sh.packed),
                                 (leaf.v_row, sh.v_row),
                                 (leaf.v_col, sh.v_col))))
            else:
                leaf = jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                            sharding=sh)
            DO.insert_entry(out, p, leaf)
        return out

    def run_until_drained(self, max_rounds: int = 1000) -> dict:
        if self.scheduler == "speculative":
            self._serve_speculative(max_rounds)
            return self.metrics
        if self.scheduler == "continuous":
            self._serve_continuous(max_rounds)
            return self.metrics
        rounds = 0
        while self._queue and rounds < max_rounds:
            self._serve_one_group()
            rounds += 1
        return self.metrics

    # -- internals -------------------------------------------------------------
    def _take_group(self) -> list:
        """Pop up to batch_size requests of the same variant (FIFO head
        decides the variant — simple fairness).  Scanning stops as soon as
        the group is full; skipped requests go back to the front in their
        original order."""
        if not self._queue:
            return []
        variant = self._queue[0].variant
        group, skipped = [], []
        while self._queue and len(group) < self.batch_size:
            r = self._queue.popleft()
            if r.variant == variant:
                group.append(r)
            else:
                skipped.append(r)
        self._queue.extendleft(reversed(skipped))
        return group

    def _serve_one_group(self) -> None:
        group = self._take_group()
        if not group:
            return
        variant = group[0].variant
        try:
            params, overlay = self.registry.resolve(variant)
            # group admission resolves the serving pointer ONCE — the whole
            # group serves the version current at this moment
            version = self.registry.current_version(variant)
        except Exception as e:  # artifact failure: re-queue or fail
            for r in group:
                r.retries += 1
                if r.retries > self.max_retries:
                    r.status, r.error = "failed", str(e)
                    self._done[r.rid] = r
                    self.metrics["failed"] += 1
                else:
                    self._queue.append(r)
            return
        for r in group:
            r.served_version = version

        batch = self._prompt_batch(
            {i: r for i, r in enumerate(group)})

        t0 = time.perf_counter()
        last_logits, cache = self._call("prefill", params, overlay, batch)
        jax.block_until_ready(last_logits)
        self.metrics["prefill_seconds"] += time.perf_counter() - t0
        self.metrics["prefills"] += 1

        next_tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        n_steps = max(r.max_new_tokens for r in group)
        t0 = time.perf_counter()
        for step in range(n_steps):
            # ONE host sync per step: per-slot int(next_tok[i]) forces a
            # device round-trip per slot per token — pull the whole token
            # vector once and append from the host buffer
            host_tok = np.asarray(next_tok)
            n_active = 0
            for i, r in enumerate(group):
                # retired slots (past their own max_new_tokens) still
                # occupy a batch lane but neither emit nor count
                if step < r.max_new_tokens:
                    r.out_tokens.append(int(host_tok[i]))
                    self._note_first_token(r)
                    n_active += 1
            self.metrics["tokens_generated"] += n_active
            if step + 1 >= n_steps:
                break   # every slot has its full budget: skip the decode
                        # whose output nobody would consume
            next_tok, cache = self._call("decode", params, overlay,
                                         next_tok, cache)
        jax.block_until_ready(next_tok)
        self.metrics["decode_seconds"] += time.perf_counter() - t0

        for r in group:
            r.status = "done"
            self._done[r.rid] = r

    # -- continuous slot scheduler (mixed-variant batches) -------------------
    def _lane_pod(self, i: int) -> int:
        """Pod owning batch lane ``i``: act_batch shards pod-major over
        ("pod", "data"), so lanes block-partition into contiguous per-pod
        ranges."""
        return i // (self.batch_size // self._pods)

    def _route_pod(self, r: Request, free: list) -> int:
        """Affinity router (DESIGN.md §17): steer the request to a pod
        with a free lane that ALREADY holds its variant's bank slot
        (hit — no admission traffic at all); cold variants go to the
        free-est pod and admit on demand there (miss).  The choice is
        STICKY per request — the async pipeline's tickets are per
        (variant, pod), so re-routing a mid-ingest request would start a
        second ingest instead of finishing the first."""
        if self._pods == 1:
            return 0
        if r.route_pod is not None:
            return r.route_pod
        free_per_pod = collections.Counter(self._lane_pod(i) for i in free)
        holding = ([] if r.variant == "__base__"
                   else self.registry.bank_pods_holding(r.variant))
        warm = [p for p in sorted(free_per_pod) if p in holding]
        if warm:
            pod = warm[0]
        else:
            pod = max(sorted(free_per_pod), key=lambda p: free_per_pod[p])
        if r.variant != "__base__":
            self.metrics["affinity_hits" if pod in holding
                         else "affinity_misses"] += 1
        r.route_pod = pod
        return pod

    def _admit_free_slots(self) -> list:
        """Pop queued requests into free lanes: route each request to a
        pod (affinity first, _route_pod), resolve its variant to a bank
        slot IN THAT POD (loading + admitting the artifact on a miss) and
        pin it for the request's lifetime.  Artifact failures re-queue up
        to max_retries then fail; a fully-pinned bank re-queues the head
        and waits for retirements."""
        with TraceAnnotation("serve.admit") as span:
            newly: list = []
            skipped: list = []
            free = [i for i in range(self.batch_size)
                    if self._slots[i] is None]
            while free and self._queue:
                r = self._queue.popleft()
                pod = self._route_pod(r, free)
                if not any(self._lane_pod(i) == pod for i in free):
                    # sticky pod's lanes all busy: hold the request until a
                    # retirement frees one (re-routing would thrash per-pod
                    # admission tickets and bank slots)
                    skipped.append(r)
                    continue
                if self.admission is not None and r.variant != "__base__":
                    # async path: never load on the serving thread — consult
                    # the pipeline (auto-prefetching unseen variants) and skip
                    # the request while its version is still ingesting
                    try:
                        state = self.admission.poll(r.variant, pod=pod)
                    except Exception as e:   # ingest failed: same retry budget
                        r.retries += 1       # as the sync artifact-load path
                        if r.retries > self.max_retries:
                            r.status, r.error = "failed", str(e)
                            self._done[r.rid] = r
                            self.metrics["failed"] += 1
                        else:
                            r.status = "queued"
                            self._queue.append(r)
                        continue
                    if state != "admitted":
                        r.status = "admitting"
                        skipped.append(r)
                        continue
                try:
                    # admission-time resolution: a queued request follows
                    # the serving pointer at THIS moment — a version
                    # published (or rolled back) while it waited is what it
                    # serves.  The acquire pins the resolved VERSION KEY, so
                    # a later swap cannot evict the bank slot this lane
                    # decodes from.
                    vslot, vkey = self.registry.bank_acquire(r.variant, pod)
                except RuntimeError:
                    # every bank slot pinned by in-flight requests: transient
                    # capacity pressure — retry after retirements free pins
                    self._queue.appendleft(r)
                    break
                except Exception as e:
                    r.retries += 1
                    if r.retries > self.max_retries:
                        r.status, r.error = "failed", str(e)
                        self._done[r.rid] = r
                        self.metrics["failed"] += 1
                    else:
                        self._queue.append(r)
                    continue
                i = next(j for j in free if self._lane_pod(j) == pod)
                free.remove(i)
                r.served_version = self.registry.current_version(r.variant)
                self._slots[i] = _Slot(request=r, variant_slot=vslot,
                                       remaining=r.max_new_tokens, vkey=vkey,
                                       pod=pod)
                self._variant_idx[i] = vslot
                self._variant_idx_dev = None
                r.status = "running"
                newly.append(i)
                self.metrics["admitted"] += 1
            # skipped (mid-admission) requests return to the FRONT in their
            # original order: admission order stays FIFO once staging lands
            self._queue.extendleft(reversed(skipped))
            span.set_metadata(admitted=len(newly), queued=len(self._queue))
        return newly

    def _prefill_admitted(self, newly: list) -> None:
        """Prefill-on-admit: one program per admission wave, on the
        smallest row bucket that holds the admitted lanes.  Below full
        width, row r is the r-th admitted lane and pad rows write nothing;
        at full width, row i is lane i."""
        bs = self.batch_size
        rows = next(b for b in self._wave_kinds if b >= len(newly))
        kind = self._wave_kinds[rows]
        with TraceAnnotation("serve.prefill", rows=len(newly), lanes=bs,
                             bucket=rows):
            place = ({i: i for i in newly} if rows == bs
                     else dict(enumerate(newly)))       # row -> lane
            pvidx = self._base_vidx[:rows].copy()
            lanes = np.full(rows, bs, np.int32)
            for r, i in place.items():
                pvidx[r] = self._slots[i].variant_slot
                lanes[r] = i
            batch = self._prompt_batch(
                {r: self._slots[i].request for r, i in place.items()}, rows)
            if self._cache is None:
                self._next_tok, self._cache = self._empty_lanes()
            bank = self.registry.bank.tree if self.registry.bank else None
            t0 = time.perf_counter()
            if (kind, jax.tree_util.tree_structure(bank)) not in self._exe:
                # first wave of this bank structure: resolve every bucket
                # now, so that no later wave compiles
                for b, k in self._wave_kinds.items():
                    self._get_exe(k, self._wave_struct(
                        b, self.registry.base_params, bank, self._next_tok,
                        self._cache))
            tok, cache = self._call(
                kind, self.registry.base_params, bank, jnp.asarray(pvidx),
                batch, jnp.asarray(lanes), self._next_tok, self._cache)
            self._wait(tok, "serve.prefill.wait")
            self.metrics["prefill_seconds"] += time.perf_counter() - t0
            self.metrics["prefills"] += 1
            self.metrics["prefill_rows"] += len(newly)
            self.metrics["prefill_rows_computed"] += rows
        with TraceAnnotation("serve.merge"):
            self._next_tok, self._cache = tok, cache

    def _retire(self, i: int) -> None:
        """Release lane ``i``: mark its request done, unpin the bank slot
        it decoded from, and free the lane for the next admission wave."""
        s = self._slots[i]
        s.request.status = "done"
        self._done[s.request.rid] = s.request
        self.registry.bank_unpin(s.vkey, s.pod)
        self._slots[i] = None
        self._variant_idx[i] = self._base_vidx[i]
        self._variant_idx_dev = None
        self.metrics["retired"] += 1

    @contextlib.contextmanager
    def _round(self):
        """One round of the continuous loop: a ``serve.step`` span (step
        number = decode steps so far) and its wall time, less what it
        spent off the engine's host code (``_off_host``), added to
        ``host_seconds``."""
        t0 = time.perf_counter()
        self._off_host = 0.0
        with StepTraceAnnotation("serve.step",
                                 step_num=self.metrics["decode_steps"]):
            try:
                yield
            finally:
                self.metrics["host_seconds"] += (
                    time.perf_counter() - t0 - self._off_host)

    def _wait(self, x, span: str) -> None:
        """``block_until_ready(x)`` inside ``span``: time blocked on the
        device, not host time."""
        t0 = time.perf_counter()
        with TraceAnnotation(span):
            jax.block_until_ready(x)
        self._off_host += time.perf_counter() - t0

    def _record_step(self, dt: float, admission_busy: bool) -> None:
        """Hand one decode step to ``step_times`` (a caller's hook, timed
        as the caller's and not the engine's)."""
        if not self.record_step_times:
            return
        t0 = time.perf_counter()
        try:
            with TraceAnnotation("serve.hook"):
                # steps overlapping admission inherit the scatter the jax
                # dependency chain ordered before them — exactly the stall
                # the benchmark's 2x ceiling gates
                self.step_times.append((t0, dt, admission_busy))
        finally:
            self._off_host += time.perf_counter() - t0

    def _serve_continuous(self, max_rounds: int) -> None:
        # max_rounds bounds STALLED rounds (no admission, no token, no
        # failure), not decode steps — productive rounds are already
        # bounded by the submitted token budgets, so a large workload
        # drains fully instead of stranding requests mid-flight
        stalls = 0
        while (self._queue or self.active()) and stalls < max_rounds:
            with self._round():
                # admission drain hook: commit AT MOST ONE staged overlay per
                # step (one donated scatter dispatch, no fence) — the bounded
                # on-thread cost of async admission (DESIGN.md §13)
                drained = 0
                if self.admission is not None:
                    drained = self.admission.drain(max_admits=1)
                    self.metrics["async_admits"] += drained
                failed0 = self.metrics["failed"]
                newly = self._admit_free_slots()
                if newly:
                    self._prefill_admitted(newly)
                if not self.active():
                    if not self._queue:
                        break
                    # admissions failed this round; retry (counts as a stall
                    # unless requests were failed — retries terminate)
                    if self.metrics["failed"] > failed0 or drained:
                        stalls = 0
                    elif self.admission is not None \
                            and self.admission.in_flight():
                        # every queued request is behind ingest and no lane is
                        # decoding: sleep on pipeline progress, don't busy-spin
                        # (terminates: ingest stages, fails, or commits once
                        # retirements release pins)
                        t0 = time.perf_counter()
                        self.admission.wait_progress(0.05)
                        self._off_host += time.perf_counter() - t0
                        stalls = 0
                    else:
                        stalls += 1
                    continue
                stalls = 0
                with TraceAnnotation("serve.emit"):
                    # ONE host sync per step: every active slot has exactly one
                    # pending token in next_tok — append from the host buffer
                    t0 = time.perf_counter()
                    host_tok = np.asarray(self._next_tok)
                    self._off_host += time.perf_counter() - t0
                    retired = []
                    for i, s in enumerate(self._slots):
                        if s is None:
                            continue
                        s.request.out_tokens.append(int(host_tok[i]))
                        self._note_first_token(s.request)
                        s.remaining -= 1
                        self.metrics["tokens_generated"] += 1
                        if s.remaining <= 0:
                            retired.append(i)
                    # retire exhausted slots IMMEDIATELY — their lanes are free
                    # for the next admission wave instead of padding to the
                    # batch max
                    for i in retired:
                        self._retire(i)
                if not (self.active() or self._queue):
                    break           # drained: skip the dangling decode
                if not self.active():
                    continue        # lanes empty but queue pending: admit next
                with TraceAnnotation("serve.decode", slots=len(
                        set(self._variant_idx.tolist()))):
                    bank = (self.registry.bank.tree if self.registry.bank
                            else None)
                    if self._variant_idx_dev is None:
                        self._variant_idx_dev = jnp.asarray(
                            self._variant_idx)
                    admission_busy = drained > 0 or (
                        self.admission is not None
                        and self.admission.in_flight() > 0)
                    t0 = time.perf_counter()
                    self._next_tok, self._cache = self._call(
                        "decode_banked", self.registry.base_params, bank,
                        self._variant_idx_dev, self._next_tok, self._cache)
                    self._wait(self._next_tok, "serve.decode.wait")
                    dt = time.perf_counter() - t0
                    self.metrics["decode_seconds"] += dt
                    self.metrics["decode_steps"] += 1
                self._record_step(dt, admission_busy)

    def _serve_speculative(self, max_rounds: int) -> None:
        """The continuous slot scheduler with the per-token decode swapped
        for base-as-draft speculative ROUNDS (serving/speculative.py): the
        same admission / prefill-on-admit / retire machinery, but each
        jitted call drafts k tokens on the base weights and verifies them
        through the lane's banked overlay, emitting up to k+1 tokens per
        dispatch.  Token streams are bit-exact with scheduler="continuous"
        for any k (the round accepts only the variant's own greedy chain).

        ``self._next_tok`` holds each lane's PENDING token — already part
        of the variant's chain (prefill argmax or a verify correction) but
        not yet appended; the loop top emits it, then the round extends
        the chain by n_acc matched drafts + the next correction."""
        stalls = 0
        while (self._queue or self.active()) and stalls < max_rounds:
            drained = 0
            if self.admission is not None:
                drained = self.admission.drain(max_admits=1)
                self.metrics["async_admits"] += drained
            failed0 = self.metrics["failed"]
            newly = self._admit_free_slots()
            if newly:
                self._prefill_admitted(newly)
            if not self.active():
                if not self._queue:
                    break
                if self.metrics["failed"] > failed0 or drained:
                    stalls = 0
                elif self.admission is not None \
                        and self.admission.in_flight():
                    self.admission.wait_progress(0.05)
                    stalls = 0
                else:
                    stalls += 1
                continue
            stalls = 0
            # emit the pending token (one host sync), retire exhausted
            host_tok = np.asarray(self._next_tok)
            for i, s in enumerate(self._slots):
                if s is None:
                    continue
                s.request.out_tokens.append(int(host_tok[i]))
                self._note_first_token(s.request)
                s.remaining -= 1
                self.metrics["tokens_generated"] += 1
                if s.remaining <= 0:
                    self._retire(i)
            if not (self.active() or self._queue):
                break           # drained: skip the dangling round
            if not self.active():
                continue        # lanes empty but queue pending: admit next
            params, bank = self.registry.spec_resolve()
            if self._variant_idx_dev is None:
                self._variant_idx_dev = jnp.asarray(self._variant_idx)
            k = self.spec.current_k
            admission_busy = drained > 0 or (
                self.admission is not None
                and self.admission.in_flight() > 0)
            t0 = time.perf_counter()
            ver, n_acc, self._next_tok, self._cache = self._call(
                f"spec_k{k}", params, bank, self._variant_idx_dev,
                self._next_tok, self._cache)
            jax.block_until_ready(self._next_tok)
            dt = time.perf_counter() - t0
            self.metrics["decode_seconds"] += dt
            self.metrics["decode_steps"] += 1
            self.metrics["spec_rounds"] += 1
            self._record_step(dt, admission_busy)
            # second host sync of the round: the accepted prefixes
            host_ver = np.asarray(ver)
            host_n = np.asarray(n_acc)
            acc_total = 0
            lanes = 0
            for i, s in enumerate(self._slots):
                if s is None:
                    continue
                lanes += 1
                n = int(host_n[i])
                acc_total += n
                r = s.request
                r.drafted += k
                r.accepted += n
                take = min(n, s.remaining)
                for j in range(take):
                    r.out_tokens.append(int(host_ver[i, j]))
                self.metrics["tokens_generated"] += take
                s.remaining -= take
                if s.remaining <= 0:
                    # budget exhausted inside the round: the pending
                    # correction token is beyond max_new_tokens — drop it
                    self._retire(i)
            self.metrics["spec_drafted"] += k * lanes
            self.metrics["spec_accepted"] += acc_total
            self.spec.observe(k, acc_total, lanes)

    def _prompt_batch(self, requests: dict, rows: Optional[int] = None
                      ) -> dict:
        """Fixed-shape (rows, prompt_len) prefill batch, ``batch_size``
        rows by default: row i holds requests[i]'s prompt tail,
        zero-padded; unmapped rows stay zero.  The ONE place prompt
        padding happens — both schedulers must build bit-identical
        batches or their tokens diverge."""
        bs = self.batch_size if rows is None else rows
        toks = np.zeros((bs, self.prompt_len), np.int32)
        for i, r in requests.items():
            p = r.tokens[-self.prompt_len:]
            toks[i, :len(p)] = p
        batch = {"tokens": jnp.asarray(toks)}
        batch.update(self._frontend_stub(bs))
        return batch

    def _frontend_stub(self, bs: int) -> dict:
        cfg = self.model.cfg
        if cfg.family == "audio":
            return {"frames": jnp.zeros((bs, cfg.encoder_frames,
                                         cfg.d_model), jnp.float32)}
        if cfg.family == "vlm":
            return {"image_embeds": jnp.zeros(
                (bs, cfg.num_image_tokens, cfg.d_model), jnp.float32)}
        return {}


def _write_rows(old, fresh, lanes, axis: int):
    """``old`` with row r of ``fresh`` (along ``axis``) written at index
    ``lanes[r]``; a row whose lane is out of range writes nothing.  A
    full-width ``fresh`` holds lane i in row i: an elementwise select,
    which keeps a sharded lane axis local.  A narrower one scatters."""
    n = old.shape[axis]
    if fresh.shape[axis] == n:
        shape = [n if d == axis else 1 for d in range(old.ndim)]
        return jnp.where((lanes == jnp.arange(n)).reshape(shape), fresh, old)
    return old.at[(slice(None),) * axis + (lanes,)].set(fresh, mode="drop")
