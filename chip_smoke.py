#!/usr/bin/env python3
"""Bring-up check of the delta-serving path on a TPU.

    python3 chip_smoke.py                # one chip: deepseek-7b
    python3 chip_smoke.py --four-chips   # four chips: deepseek-moe-16b

One chip (the default) runs two phases:

* ``kernels`` — every delta GEMM of the serving path (fused, banked and
  unpack_apply; fp and int8 base) at deepseek-7b's projection shapes, with
  M at decode and at prefill size, against the plain oracles of
  ``kernels/ref.py``.  Each prints its max error beside its tolerance.
* ``serve`` — deepseek-7b at full published width (30 layers, d_model
  4096, d_ff 11008, vocab 102400) with the int8 base, through the normal
  entry point (``Deployment`` -> ``ServingEngine`` -> banked delta GEMMs):
  two variants drawn from a seed, requests for the base and both variants
  decoded in one continuous batch.  Every request must complete with its
  full token budget, and the base lanes must emit exactly the tokens of
  the plain no-overlay forward.

``--four-chips`` runs only the sharded phase: deepseek-moe-16b (16.3 B
parameters, more than one chip holds) with the int8 base over a
(data=1, model=4) mesh, per-shard kernels (``kernel_dispatch="shard_map"``),
base and variant lanes mixed.  Base lanes must equal the plain forward on
the same mesh token for token; each variant's lanes must stay within a
logit tolerance of that variant reconstructed densely on the same mesh;
the state must span all four devices and no matmul may fall back to the
unpartitioned kernel.  Routing is dropless there (capacity = group size),
so a lane's tokens do not depend on what else shares its batch.

The script exits non-zero, printing no result, when JAX's first device is
not a TPU or when the repository's package cannot be imported.  Its last
line on success is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
BATCH, PROMPT_LEN, MAX_LEN, NEW_TOKENS = 4, 16, 64, 8
BANK_SLOTS = 3                  # the base slot + two variants
# A variant here carries planes and vectors only.  One that also carried
# deepseek-7b's embed and unembed as fine-tuned extras would add
# 2 * 102400 * 4096 * 2 B = 1.68 GB to EVERY bank slot (extras are banked
# per slot, models/delta_overlay.bank_extra_base): three slots would then
# need 7.75 + 3 * (0.76 + 1.68) = 15.1 GB, more than the chip leaves free.
KERNEL_SHAPES = [(4096, 4096), (11008, 4096), (4096, 11008)]
KERNEL_M = (BATCH, BATCH * PROMPT_LEN)      # decode and prefill rows
# bf16 outputs: the kernels round the base GEMM and the delta separately
# (about 2 ulp), the fp32 oracle rounds once; 2^-6 of the largest output
# is twice that bound
GEMM_TOL = 2.0 ** -6
UNPACK_TOL = 2.0 ** -7          # one rounding of a bf16/fp16 Ŵ, with margin
# dense-vs-delta logits: how far below the dense model's best logit the
# token the delta path chose may sit (logits of these random models have
# unit scale; bf16 rounding differences across the layers stay ~1e-2)
LOGIT_GAP_TOL = 0.125


class Failed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


def log(*parts) -> None:
    print(*parts, flush=True)


class Compiles:
    """Counts XLA backend compiles in this process (and their seconds)."""

    def __init__(self, jax):
        self.n, self.seconds = 0, 0.0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.seconds += duration
        jax.monitoring.register_event_duration_secs_listener(listen)


def peak_hbm(jax) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


# ---------------------------------------------------------------------------
# phase: kernels vs kernels/ref.py
# ---------------------------------------------------------------------------

def phase_kernels(jax, jnp):
    import numpy as np
    from repro.core import quantize as Q
    from repro.kernels import ops as K
    from repro.kernels import ref as R

    def rel_err(got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        check(np.isfinite(got).all(), "non-finite kernel output")
        return float(np.abs(got - want).max() / np.abs(want).max())

    worst = 0.0
    for n, k in KERNEL_SHAPES:
        key = jax.random.PRNGKey(n * 7 + k)
        kw, kp, kv, kx = jax.random.split(key, 4)
        w = (k ** -0.5 * jax.random.normal(kw, (n, k))).astype(jnp.bfloat16)
        packed = jax.random.bits(kp, (BANK_SLOTS, n, k // 8), jnp.uint8)
        vs = 0.05 * k ** -0.5 * jnp.abs(
            jax.random.normal(kv, (2, BANK_SLOTS, max(n, k))))
        v_row = vs[0, :, :n].at[0].set(0).astype(jnp.float16)
        v_col = vs[1, :, :k].at[0].set(0).astype(jnp.float16)
        for base in ("fp", "int8"):
            wb = Q.quantize_weight(w) if base == "int8" else w
            wq, ws = (wb.q, wb.scale) if base == "int8" else (w, None)
            for m in KERNEL_M:
                x = jax.random.normal(jax.random.fold_in(kx, m),
                                      (m, k)).astype(jnp.bfloat16)
                vidx = jnp.arange(m, dtype=jnp.int32) % BANK_SLOTS
                with jax.default_matmul_precision("highest"):
                    want_f = R.bitlinear_axes_ref(
                        x, packed[1], v_row[1], v_col[1], wq, w_scale=ws)
                    want_b = R.bitlinear_axes_banked_ref(
                        x, vidx, packed, v_row, v_col, wq, w_scale=ws)
                got_f = K.bitlinear_axes(x, packed[1], v_row[1], v_col[1],
                                         wb)
                got_b = K.bitlinear_axes_banked(x, vidx, packed, v_row,
                                                v_col, wb)
                for name, got, want in (("fused", got_f, want_f),
                                        ("banked", got_b, want_b)):
                    err = rel_err(got, want)
                    worst = max(worst, err / GEMM_TOL)
                    log(f"kernel {name:6s} {base:4s} M={m:<3d} N={n:<5d} "
                        f"K={k:<5d} max_err={err:.3e} tol={GEMM_TOL:.3e}")
                    check(err <= GEMM_TOL, f"{name} {base} M={m} N={n} "
                          f"K={k}: error {err:.3e} over {GEMM_TOL:.3e}")
            out_dtype = jnp.float16 if base == "int8" else jnp.bfloat16
            got_u = K.unpack_apply(packed[1], v_row[1], wb, mode="row",
                                   out_dtype=out_dtype)
            want_u = R.unpack_apply_ref(packed[1], v_row[1], wq, "row",
                                        dtype=jnp.float32, w_scale=ws)
            err = rel_err(got_u, want_u)
            worst = max(worst, err / UNPACK_TOL)
            log(f"kernel unpack {base:4s}        N={n:<5d} K={k:<5d} "
                f"max_err={err:.3e} tol={UNPACK_TOL:.3e}")
            check(err <= UNPACK_TOL, f"unpack_apply {base} N={n} K={k}: "
                  f"error {err:.3e} over {UNPACK_TOL:.3e}")
    return {"worst_err_over_tol": worst}


# ---------------------------------------------------------------------------
# serving helpers
# ---------------------------------------------------------------------------

def submit_mix(dep, rng, lanes) -> list:
    """One request per lane, each a full-length prompt; returns the
    (rid, variant, prompt) triples in lane order."""
    out = []
    for variant in lanes:
        prompt = rng.integers(1, dep.model.cfg.vocab_size, size=PROMPT_LEN)
        rid = dep.submit(prompt, variant=variant,
                         max_new_tokens=NEW_TOKENS)
        out.append((rid, variant, prompt))
    return out


def step_times(dep) -> str:
    """Median decode-step wall time (host clock around the step's
    ``block_until_ready``), leaving out the first step, which compiles."""
    import numpy as np
    dts = [dt for _, dt, _ in dep.engine.step_times[1:]]
    if not dts:
        return "no steady decode step"
    return (f"decode step median {1e3 * float(np.median(dts)):.2f} ms, "
            f"min {1e3 * min(dts):.2f} ms over {len(dts)} steps")


def check_completed(dep, reqs) -> dict:
    """Every request done, none failed, each with its full budget."""
    m = dep.metrics
    check(m["failed"] == 0, f"{m['failed']} request(s) failed")
    tokens = {}
    for rid, variant, _ in reqs:
        r = dep.result(rid)
        check(r.status == "done", f"request {rid} ({variant}) ended "
              f"{r.status!r}: {r.error}")
        check(len(r.out_tokens) == NEW_TOKENS,
              f"request {rid} ({variant}) produced {len(r.out_tokens)} of "
              f"{NEW_TOKENS} tokens")
        tokens[rid] = [int(t) for t in r.out_tokens]
    return tokens


def plain_greedy(jax, jnp, model, params, prompts, ctx, shardings=None):
    """Greedy decode of the plain model (no overlay) over one prompt
    batch: the reference for base lanes.  Returns (B, NEW_TOKENS)."""
    import numpy as np

    def prefill(p, toks):
        logits, cache = model.prefill(p, {"tokens": toks}, MAX_LEN)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    def step(p, tok, cache):
        logits, cache = model.decode_step(p, tok, cache)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    with ctx():
        tok, cache = jax.jit(prefill)(params, jnp.asarray(prompts))
        out = [tok]
        step_j = jax.jit(step, donate_argnums=(2,))
        for _ in range(NEW_TOKENS - 1):
            tok, cache = step_j(params, tok, cache)
            out.append(tok)
    return np.stack([np.asarray(t) for t in out], axis=1)


# ---------------------------------------------------------------------------
# phase: deepseek-7b on one chip
# ---------------------------------------------------------------------------

def phase_serve(jax, jnp):
    import contextlib

    import numpy as np
    from repro.configs import get_config
    from repro.launch import build as B
    from repro.models import build_model
    from repro.serving import Deployment

    model = build_model(get_config("deepseek-7b"))
    plan = B.memory_plan(model, base_dtype="int8", bank_size=BANK_SLOTS,
                         kv_tokens=BATCH * MAX_LEN)
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit", 0)
    log(f"memory plan (deepseek-7b, int8 base): base "
        f"{plan['base'] / 1e9:.2f} GB + {BANK_SLOTS} bank slots x "
        f"{plan['slot'] / 1e9:.3f} GB + KV {plan['kv_per_token'] / 1e6:.3f}"
        f" MB/token x {BATCH * MAX_LEN} tokens = {plan['total'] / 1e9:.2f} "
        f"GB of {limit / 1e9:.2f} GB")
    check(not limit or plan["total"] < limit, "memory plan over the chip")

    base, _, _ = B.init_base(model, SEED, base_dtype="int8")
    jax.block_until_ready(base)
    log(f"base built: peak HBM {peak_hbm(jax) / 1e9:.2f} GB")
    dep = Deployment(model, base, mode="fused", scheduler="continuous",
                     batch_size=BATCH, prompt_len=PROMPT_LEN,
                     max_len=MAX_LEN, bank_size=BANK_SLOTS,
                     base_dtype="int8")
    flat, _, _ = B.base_struct(model)
    for i in range(2):
        dep.publish(f"v{i}", B.random_variant(flat, SEED + 1 + i))
    rng = np.random.default_rng(SEED)
    # one continuous batch of base + both variants, then a second wave
    # admitted into the lanes the first one retires
    reqs = submit_mix(dep, rng, ["v0", "__base__", "v1", "__base__",
                                 "v1", "v0"])
    dep.engine.record_step_times = True
    dep.drain()
    log(step_times(dep))
    tokens = check_completed(dep, reqs)
    st = dep.status()
    log(f"served {len(reqs)} requests, {dep.metrics['tokens_generated']} "
        f"tokens, {dep.metrics['decode_steps']} decode steps "
        f"({dep.metrics['decode_seconds']:.3f} s), "
        f"{dep.metrics['prefills']} prefills "
        f"({dep.metrics['prefill_seconds']:.3f} s); hbm base "
        f"{st['hbm']['base_bytes'] / 1e9:.2f} GB, bank "
        f"{st['hbm']['bank_bytes'] / 1e9:.2f} GB")

    # base lanes of the first wave against the plain forward
    first = reqs[:BATCH]
    prompts = np.stack([p for _, _, p in first])
    ref = plain_greedy(jax, jnp, model, dep.registry.base_params, prompts,
                       contextlib.nullcontext)
    for lane, (rid, variant, _) in enumerate(first):
        if variant == "__base__":
            check(tokens[rid] == ref[lane].tolist(),
                  f"base lane {lane}: {tokens[rid]} != plain "
                  f"{ref[lane].tolist()}")
    log("base lanes equal the plain forward token for token")
    dep.close()
    return {"decode_steps": dep.metrics["decode_steps"]}


# ---------------------------------------------------------------------------
# phase: deepseek-moe-16b over four chips
# ---------------------------------------------------------------------------

def dense_variant(jax, jnp, base_flat, fp_shardings, dm):
    """The variant reconstructed densely (bf16) on the base's mesh, one
    layer at a time: dequantized base + v ⊙ unpack(signs)."""
    from repro.core import quantize as Q
    from repro.core.calibration import DeltaEntry
    from repro.models import delta_overlay as DO

    out = dict(base_flat)
    for p, e in dm.deltas.items():
        qw = base_flat[p]
        sh = fp_shardings[p]
        place = DO.entry_shardings_from_weight(sh, qw.ndim)
        ent = (jax.device_put(e.packed, place.packed),
               jax.device_put(e.v_row, place.v_row),
               jax.device_put(e.v_col, place.v_col),
               jnp.asarray(e.use_row))

        def one(args):
            q, s, pk, vr, vc, ur = args
            return DeltaEntry(packed=pk, v_row=vr, v_col=vc,
                              use_row=ur).reconstruct(
                Q.dequantize(Q.QuantWeight(q=q, scale=s)),
                dtype=jnp.bfloat16)

        def rebuild(q, s, pk, vr, vc, ur):
            if q.ndim == 2:
                return one((q, s, pk, vr, vc, ur))
            return jax.lax.map(one, (q, s, pk, vr, vc, ur))

        out[p] = jax.jit(rebuild, out_shardings=sh)(qw.q, qw.scale, *ent)
    return out


def phase_four_chips(jax, jnp):
    import contextlib

    import numpy as np
    from repro.configs import get_config
    from repro.core.calibration import flatten_params, unflatten_like
    from repro.distributed.sharding import rules_for, shard_ctx
    from repro.kernels import dispatch as KD
    from repro.launch import build as B
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.serving import Deployment

    check(len(jax.devices()) >= 4, f"--four-chips needs 4 devices, found "
          f"{len(jax.devices())}")
    cfg = get_config("deepseek-moe-16b")
    # dropless routing: capacity = group size (cap = n * top_k / E * cf)
    cfg = dataclasses.replace(cfg, capacity_factor=cfg.num_experts
                              / cfg.top_k + 1)
    model = build_model(cfg)
    mesh = make_host_mesh(data=1, model=4)
    plan = B.memory_plan(model, base_dtype="int8", bank_size=BANK_SLOTS,
                         kv_tokens=BATCH * MAX_LEN)
    log(f"memory plan (deepseek-moe-16b, int8 base, 4 chips): base "
        f"{plan['base'] / 1e9:.2f} GB + {BANK_SLOTS} bank slots x "
        f"{plan['slot'] / 1e9:.3f} GB, {plan['total'] / 4e9:.2f} GB per "
        f"chip")

    base, axes, shardings = B.init_base(model, SEED, base_dtype="int8",
                                        mesh=mesh)
    jax.block_until_ready(base)
    log(f"base built: peak HBM {peak_hbm(jax) / 1e9:.2f} GB per chip")
    dep = Deployment(model, base, mode="fused", scheduler="continuous",
                     batch_size=BATCH, prompt_len=PROMPT_LEN,
                     max_len=MAX_LEN, bank_size=BANK_SLOTS, mesh=mesh,
                     param_axes=axes, param_shardings=shardings,
                     kernel_dispatch="shard_map", base_dtype="int8")
    del base                    # the registry holds the one resident base
    flat, _, _ = B.base_struct(model)
    variants = {f"v{i}": B.random_variant(flat, SEED + 1 + i)
                for i in range(2)}
    for name, dm in variants.items():
        dep.publish(name, dm)
    declined0 = KD.memo_info()["declined"]
    rng = np.random.default_rng(SEED)
    lanes = ["v0", "__base__", "v1", "__base__"]
    reqs = submit_mix(dep, rng, lanes)
    dep.engine.record_step_times = True
    dep.drain()
    log(step_times(dep))
    tokens = check_completed(dep, reqs)
    declined = KD.memo_info()["declined"] - declined0
    log(f"served {len(reqs)} requests over {mesh.shape}: "
        f"{dep.metrics['decode_steps']} decode steps "
        f"({dep.metrics['decode_seconds']:.3f} s); declined matmuls "
        f"{declined}")
    check(declined == 0, f"{declined} matmul(s) fell back to the "
          "unpartitioned kernel")
    st = dep.status()["hbm"]
    for what in ("base_per_device", "bank_per_device"):
        per = st[what]
        log(f"{what}: " + ", ".join(f"{d}={b / 1e9:.3f} GB"
                                    for d, b in sorted(per.items())))
        check(len(per) == 4 and min(per.values()) > 0,
              f"{what} spans {len(per)} device(s), not 4")
        check(max(per.values()) < 0.3 * sum(per.values()),
              f"{what} is not split over the four devices")
    prompts = np.stack([p for _, _, p in reqs])
    engine_toks = np.asarray([tokens[rid] for rid, _, _ in reqs])
    rules = rules_for("decode")

    def ctx():
        stack = contextlib.ExitStack()
        stack.enter_context(mesh)
        stack.enter_context(shard_ctx(mesh, rules))
        return stack

    ref = plain_greedy(jax, jnp, model, dep.registry.base_params, prompts,
                       ctx)
    for lane, variant in enumerate(lanes):
        if variant == "__base__":
            check(engine_toks[lane].tolist() == ref[lane].tolist(),
                  f"base lane {lane}: {engine_toks[lane].tolist()} != "
                  f"plain {ref[lane].tolist()}")
    log("base lanes equal the plain forward on the mesh token for token")
    base_params = dep.registry.base_params
    dep.close()
    del dep                     # frees the bank and the KV cache
    gc.collect()

    # variant lanes: teacher-force the engine's tokens through the
    # variant reconstructed densely; at each step the token the delta
    # path chose must sit within LOGIT_GAP_TOL of the dense best logit
    base_flat = flatten_params(base_params)
    fp_sh = flatten_params(shardings)
    fp_sh = {p: (s.q if hasattr(s, "q") else s) for p, s in fp_sh.items()}

    def prefill(p, toks):
        return model.prefill(p, {"tokens": toks}, MAX_LEN)

    def step(p, tok, cache):
        return model.decode_step(p, tok, cache)

    prefill_j, step_j = jax.jit(prefill), jax.jit(step, donate_argnums=(2,))
    worst = 0.0
    for name, dm in variants.items():
        dense = unflatten_like(base_params,
                               dense_variant(jax, jnp, base_flat, fp_sh, dm))
        with ctx():
            logits, cache = prefill_j(dense, jnp.asarray(prompts))
            steps = [logits]
            for t in range(NEW_TOKENS - 1):
                logits, cache = step_j(dense, jnp.asarray(engine_toks[:, t]),
                                       cache)
                steps.append(logits)
        del dense, cache
        for lane, variant in enumerate(lanes):
            if variant != name:
                continue
            gaps, agree = [], 0
            for t, lg in enumerate(steps):
                row = np.asarray(lg[lane], np.float32)
                check(np.isfinite(row).all(), "non-finite dense logits")
                chosen = int(engine_toks[lane, t])
                gaps.append(float(row.max() - row[chosen]))
                agree += int(row.argmax() == chosen)
            worst = max(worst, max(gaps))
            log(f"{name} lane {lane}: max logit gap {max(gaps):.4f} "
                f"(tol {LOGIT_GAP_TOL}), dense argmax agrees on "
                f"{agree}/{NEW_TOKENS} tokens")
            check(max(gaps) <= LOGIT_GAP_TOL,
                  f"{name} lane {lane}: logit gap {max(gaps):.4f} over "
                  f"{LOGIT_GAP_TOL}")
    return {"worst_logit_gap": worst}


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the deepseek-moe-16b phase over a "
                         "(data=1, model=4) mesh")
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import jax
        import jax.numpy as jnp
        from repro.core.compile_cache import use_jax_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repository's package from "
              f"{ROOT}/src: {e}", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); nothing was run", file=sys.stderr)
        return 1
    cache_dir = use_jax_cache()
    compiles = Compiles(jax)
    count = len(jax.devices())
    log(f"device: {dev.device_kind} x{count}; jax {jax.__version__}; "
        f"compile cache {cache_dir}")
    phases = ([("four_chips", phase_four_chips)] if args.four_chips
              else [("kernels", phase_kernels), ("serve", phase_serve)])
    for name, fn in phases:
        t0 = time.perf_counter()
        c0, s0 = compiles.n, compiles.seconds
        try:
            info = fn(jax, jnp)
        except Failed as e:
            print(f"chip_smoke: phase {name} FAILED: {e}", file=sys.stderr)
            return 1
        log(f"phase {name}: ok in {time.perf_counter() - t0:.1f} s; "
            f"{compiles.n - c0} compiles ({compiles.seconds - s0:.1f} s); "
            f"peak HBM {peak_hbm(jax) / 1e9:.2f} GB; {info}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
