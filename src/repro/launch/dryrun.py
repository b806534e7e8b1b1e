"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST be the process entry point (python -m repro.launch.dryrun ...): the
first lines pin JAX to the CPU and force 512 host platform devices BEFORE
any jax import so ``jax.make_mesh`` can build the production meshes.  This
is a CPU analysis of a modelled TPU v5e: it never touches an accelerator,
and the per-cell child processes inherit the same CPU pin.  Never set
these flags globally — smoke tests and benchmarks must see 1 device.

Per cell this:
  1. builds the mesh ((16,16) data×model, or (2,16,16) pod×data×model),
  2. resolves parameter/batch/cache shardings from the logical rules,
  3. ``jax.jit(step).lower(abstract args).compile()``,
  4. records memory_analysis, cost_analysis and the parsed collective
     schedule to results/dryrun/<cell>.json.

The decode_fused / decode_banked serving cells lower their fused delta
GEMMs as shard_map'd PER-SHARD Pallas kernels (kernels/dispatch.py —
DESIGN.md §12) at both meshes; ``--opt gspmd_kernels`` restores the PR-4
GSPMD-partitioned global-kernel lowering for comparison.

The driver (--all) runs each cell in a SUBPROCESS so an XLA failure or OOM
in one cell cannot kill the sweep, and finished cells are skipped on
restart (the dry-run is itself fault-tolerant / resumable).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

# the chip the roofline models (hlo_analysis.PEAKS key)
TARGET_DEVICE_KIND = "TPU v5 lite"

import argparse
import json
import pathlib
import subprocess
import sys
import time
import traceback


RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             opt_flags: tuple = (), cache_dir=None) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import (BANKED_SLOTS, SHAPES, get_config,
                               cell_skip_reason)
    from repro.distributed import hlo_analysis as H
    from repro.distributed.sharding import (rules_for, shard_ctx,
                                            tree_shardings)
    from repro.launch.mesh import make_production_mesh
    from repro.models import build_model
    from repro.models.param import split
    from repro.optim.adamw import AdamWState
    from repro.train.step import (TrainState, make_banked_decode_step,
                                  make_decode_step, make_fused_decode_step,
                                  make_prefill_step, make_train_step)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = cell_skip_reason(cfg, shape)
    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "seq_len": shape.seq_len, "global_batch": shape.global_batch,
            "kind": shape.kind, "opt_flags": list(opt_flags)}
    if skip:
        return {**meta, "status": "skip", "reason": skip}

    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    long_ctx = shape_name == "long_500k"
    rules = rules_for(shape.kind, long_context=long_ctx)

    t0 = time.time()
    params_p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_struct, params_axes = split(params_p)
    param_sh = tree_shardings(params_struct, params_axes, rules, mesh)

    batch_struct = model.input_specs(shape.seq_len, shape.global_batch,
                                     kind=shape.kind)
    batch_sh = tree_shardings(batch_struct, model.batch_pspecs(shape.kind),
                              rules, mesh)
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    if shape.kind == "train":
        opt_struct = AdamWState(
            mu=jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                            params_struct),
            nu=jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                            params_struct),
            count=jax.ShapeDtypeStruct((), jnp.int32))
        opt_sh = AdamWState(mu=param_sh, nu=param_sh, count=repl)
        state_struct = TrainState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            params=params_struct, opt=opt_struct)
        state_sh = TrainState(step=repl, params=param_sh, opt=opt_sh)
        step_fn = make_train_step(model, param_axes=params_axes)
        args = (state_struct, batch_struct)
        shardings = (state_sh, batch_sh)
        # pin output shardings: new state must land exactly on the input
        # layout (grads then reduce-scatter into the FSDP shards instead of
        # all-reducing full tensors); metrics are replicated scalars
        with mesh, shard_ctx(mesh, rules):
            _, metrics_struct = jax.eval_shape(step_fn, *args)
        out_shardings = (state_sh, jax.tree.map(lambda _: repl,
                                                metrics_struct))
    else:
        out_shardings = None
        # serving: bf16 params
        serve_struct = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
            params_struct)
        if shape.kind == "prefill":
            step_fn = make_prefill_step(model, max_len=shape.seq_len)
            args = (serve_struct, batch_struct)
            shardings = (param_sh, batch_sh)
        else:  # decode
            # sequence-shard the KV cache over `model` whenever kv heads
            # don't divide the axis (hillclimb A: 5× decode win); can be
            # forced/disabled via --opt kv_seq_shard / no_kv_seq_shard
            kv_auto = (cfg.num_kv_heads % 16 != 0 and not long_ctx
                       and cfg.family not in ("ssm", "hybrid"))
            kv_seq = ("kv_seq_shard" in opt_flags
                      or (kv_auto and "no_kv_seq_shard" not in opt_flags))
            cache_struct = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            cache_sh = tree_shardings(cache_struct,
                                      model.cache_pspecs(
                                          long_ctx, kv_seq_shard=kv_seq),
                                      rules, mesh)
            token_struct = batch_struct["tokens"]
            token_sh = batch_sh["tokens"]

            def quantize_cell(serve_struct, flat, delta_paths, param_sh):
                # --opt quantized_base: int8 base under the fused delta
                # GEMMs (DESIGN.md §16) — target leaves become abstract
                # QuantWeight twins (int8 payload + fp16 scales) and their
                # shardings get the same spec surgery the registry applies
                from repro.core.calibration import (flatten_params,
                                                    unflatten_like)
                from repro.core.quantize import (quant_sharding,
                                                 quantize_struct)
                qflat = quantize_struct(flat, delta_paths)
                serve_struct = unflatten_like(serve_struct, qflat)
                psh_flat = flatten_params(param_sh)
                for p in delta_paths:
                    psh_flat[p] = quant_sharding(psh_flat[p], flat[p].ndim)
                return serve_struct, unflatten_like(param_sh, psh_flat)

            if shape.fused:
                # single-variant on-the-fly serving cell: decode against
                # ONE packed overlay on its derived shardings — inside
                # shard_ctx the fused delta GEMMs lower as shard_map'd
                # per-shard Pallas kernels (kernels/dispatch.py,
                # DESIGN.md §12; --opt gspmd_kernels pins the PR-4
                # GSPMD-partitioned lowering for comparison)
                from repro.core.calibration import (flatten_params,
                                                    is_target)
                from repro.models import delta_overlay as DO
                flat = flatten_params(serve_struct)
                delta_paths = sorted(p for p, l in flat.items()
                                     if is_target(p, l))
                ov_struct = DO.overlay_struct(flat, delta_paths)
                ov_axes = DO.overlay_pspecs(params_axes, delta_paths)
                ov_sh = tree_shardings(ov_struct, ov_axes, rules, mesh)
                if "quantized_base" in opt_flags:
                    serve_struct, param_sh = quantize_cell(
                        serve_struct, flat, delta_paths, param_sh)
                step_fn = make_fused_decode_step(model)
                args = (serve_struct, ov_struct, token_struct, cache_struct)
                shardings = (param_sh, ov_sh, token_sh, cache_sh)
            elif shape.banked:
                # mixed-variant serving cell: decode against a banked
                # overlay whose leaves land on their derived shardings
                # (weight-axis tiles, replicated bank axis) — validates
                # the DESIGN.md §11 collective schedule: batch lanes over
                # `data`, fused delta GEMMs over `model`, no per-step
                # weight or overlay all-gathers
                from repro.core.calibration import (flatten_params,
                                                    is_target)
                from repro.models import delta_overlay as DO
                flat = flatten_params(serve_struct)
                delta_paths = sorted(p for p, l in flat.items()
                                     if is_target(p, l))
                ds = set(delta_paths)
                extra_paths = sorted(p for p in flat if p not in ds)
                bank_struct = DO.overlay_struct(
                    flat, delta_paths, extra_paths, bank_size=BANKED_SLOTS)
                bank_axes = DO.overlay_pspecs(
                    params_axes, delta_paths, extra_paths, bank=True)
                bank_sh = tree_shardings(bank_struct, bank_axes, rules,
                                         mesh)
                vidx_struct = jax.ShapeDtypeStruct(
                    (shape.global_batch,), jnp.int32)
                if "quantized_base" in opt_flags:
                    serve_struct, param_sh = quantize_cell(
                        serve_struct, flat, delta_paths, param_sh)
                step_fn = make_banked_decode_step(model)
                args = (serve_struct, bank_struct, vidx_struct,
                        token_struct, cache_struct)
                shardings = (param_sh, bank_sh, token_sh, token_sh,
                             cache_sh)
            else:
                step_fn = make_decode_step(model)
                args = (serve_struct, token_struct, cache_struct)
                shardings = (param_sh, token_sh, cache_sh)

    jit_kwargs = {"in_shardings": shardings}
    if out_shardings is not None:
        jit_kwargs["out_shardings"] = out_shardings
    import contextlib

    from repro.kernels import dispatch as _dp
    # serving cells lower the shard_map'd per-shard delta kernels by
    # default (the shard_ctx below activates kernels/dispatch.py);
    # --opt gspmd_kernels pins the PR-4 GSPMD-partitioned lowering
    dp_ctx = (_dp.no_dispatch() if "gspmd_kernels" in opt_flags
              else contextlib.nullcontext())
    cc_cache, cc_how = None, None
    if cache_dir:
        # --populate-cache: the dry-run doubles as the fleet's cache
        # warmer (DESIGN.md §14) — a later serve/warmup with the same
        # env + avals deserializes instead of compiling.  set_default
        # lets the dispatch-layer shard_map kernels persist too.
        from repro.core import compile_cache as CCm
        cc_cache = CCm.CompileCache(cache_dir)
        CCm.set_default(cc_cache)
        cc_parts = ("dryrun-cell", arch, shape_name, meta["mesh"],
                    tuple(sorted(opt_flags)), CCm.aval_fp(args),
                    CCm.sharding_fp(shardings),
                    CCm.sharding_fp(out_shardings))
    with mesh, shard_ctx(mesh, rules), dp_ctx:
        compiled = hlo_text = None
        if cc_cache is not None:
            compiled = cc_cache.get(cc_parts)
            if compiled is not None:
                try:
                    hlo_text = compiled.as_text()
                    cc_how, t_lower = "hit", 0.0
                    t_compile = cc_cache.stats["deserialize_seconds"]
                except Exception:
                    compiled = None   # loadable but not inspectable
        if compiled is None:
            lowered = jax.jit(step_fn, **jit_kwargs).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            hlo_text = compiled.as_text()
            if cc_cache is not None:
                cc_how = "compiled"
                cc_cache.put(cc_parts, compiled)
        summary = H.cost_summary(compiled, hlo_text)
        # trip-count-aware static analysis (cost_analysis counts while
        # bodies once — useless for scanned models); this is the roofline
        # source of truth
        from repro.distributed import hlo_cost as HCOST
        tc = HCOST.analyze(hlo_text)
        summary["flops"] = tc["flops"]
        summary["bytes_accessed"] = tc["bytes"]
        summary["collectives"] = tc["collectives"]
        summary["top_flop_ops"] = tc["top_flop_ops"]

    n_chips = 512 if multi_pod else 256

    # MODEL_FLOPS: 6·N·D train / 2·N·D forward, N = active matmul params
    from repro.configs.base import param_counts
    pc = param_counts(cfg)
    n_matmul = pc["active"] - cfg.vocab_size * cfg.d_model  # embed lookup free
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * n_matmul * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_matmul * tokens
    else:  # decode: one token per sequence
        model_flops = 2 * n_matmul * shape.global_batch
    terms = H.roofline_terms(summary["flops"], summary["bytes_accessed"],
                             summary["collectives"]["total_wire_bytes"],
                             model_flops_per_device=model_flops / n_chips,
                             device_kind=TARGET_DEVICE_KIND)
    if cc_how is not None:
        meta["cache"] = cc_how
    return {**meta, "status": "ok", "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2), "n_chips": n_chips,
            "model_flops_total": model_flops,
            "params_total": pc["total"], "params_active": pc["active"],
            "cost": summary, "roofline": terms,
            "hlo_bytes": len(hlo_text)}


def cell_path(arch, shape, multi_pod, tag="") -> pathlib.Path:
    mesh = "multi" if multi_pod else "single"
    suffix = f".{tag}" if tag else ""
    return RESULTS_DIR / f"{arch}__{shape}__{mesh}{suffix}.json"


def run_all(multi_pod_only=None, force=False, tag="",
            cache_dir=None) -> int:
    """Subprocess-per-cell sweep; resumable. Returns #failures."""
    from repro.configs import cells
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    failures = 0
    todo = []
    for arch, shape, skip in cells():
        for mp in ((False, True) if multi_pod_only is None
                   else (multi_pod_only,)):
            todo.append((arch, shape, mp, skip))
    for i, (arch, shape, mp, skip) in enumerate(todo):
        out = cell_path(arch, shape, mp, tag)
        if out.exists() and not force:
            print(f"[{i+1}/{len(todo)}] skip-done {out.name}")
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape, "--out", str(out)]
        if mp:
            cmd.append("--multi-pod")
        if tag:
            cmd += ["--tag", tag]
        if cache_dir:
            cmd += ["--populate-cache", str(cache_dir)]
        print(f"[{i+1}/{len(todo)}] {arch} × {shape} × "
              f"{'multi' if mp else 'single'} ...", flush=True)
        t0 = time.time()
        r = subprocess.run(cmd, capture_output=True, text=True,
                           env={**os.environ,
                                "PYTHONPATH": os.environ.get("PYTHONPATH", "")})
        dt = time.time() - t0
        if r.returncode != 0:
            failures += 1
            err = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if mp else "16x16", "status": "error",
                   "stderr": r.stderr[-4000:], "elapsed_s": round(dt, 1)}
            out.write_text(json.dumps(err, indent=2))
            print(f"    FAILED in {dt:.0f}s: {r.stderr.strip().splitlines()[-1] if r.stderr.strip() else '?'}")
        else:
            print(f"    ok in {dt:.0f}s")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--tag", default="", help="result filename suffix "
                    "(perf-iteration variants)")
    ap.add_argument("--opt", action="append", default=[],
                    help="optimization flags (repeatable), e.g. "
                         "--opt kv_seq_shard")
    ap.add_argument("--populate-cache", default=None, metavar="DIR",
                    help="persist every compiled cell executable into "
                         "this compile-cache dir (DESIGN.md §14) so a "
                         "matching serve --warmup deserializes it")
    args = ap.parse_args()

    if args.all:
        sys.exit(1 if run_all(force=args.force, tag=args.tag,
                              cache_dir=args.populate_cache) else 0)

    assert args.arch and args.shape, "--arch/--shape required without --all"
    try:
        res = run_cell(args.arch, args.shape, args.multi_pod,
                       opt_flags=tuple(args.opt),
                       cache_dir=args.populate_cache)
    except Exception:
        res = {"arch": args.arch, "shape": args.shape,
               "mesh": "2x16x16" if args.multi_pod else "16x16",
               "status": "error", "traceback": traceback.format_exc()[-6000:]}
    out = (pathlib.Path(args.out) if args.out
           else cell_path(args.arch, args.shape, args.multi_pod, args.tag))
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(res, indent=2))
    print(json.dumps({k: res[k] for k in res
                      if k in ("arch", "shape", "mesh", "status",
                               "compile_s", "cache")}))
    if res["status"] == "error":
        print(res.get("traceback", res.get("reason", "")), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
