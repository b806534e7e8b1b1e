"""Serving launcher: versioned multi-tenant deployment over synthetic
delta variants, driven through the serving/api.Deployment control plane.

The base weights and the variant artifacts are random, drawn from
fixed seeds by ``launch/build.py`` in their serving form (bf16 or int8
base, packed sign planes + fp16 vectors per variant), so the launcher
runs at published widths as well as ``--reduced``::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
        --variants 3 --requests 12 --mode fused --scheduler continuous \
        --updates 1

    # deepseek-7b at full width on one 16 GB chip (int8 base)
    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b \
        --base-dtype int8 --mode fused --scheduler continuous --variants 2

--mode fused keeps variants resident as packed delta overlays (on-the-fly
fused GEMMs, ~1/16 the HBM per variant); --mode dense materialises full
copies (the classic hot-swap path).  --scheduler continuous serves MIXED
variants in one decode batch via the overlay bank (requires --mode fused;
DESIGN.md §9); group batches one variant at a time.  --updates N performs
N incremental publish_update + hot-swap cycles on the first variant
mid-workload (DESIGN.md §10), then rolls the last one back.

--speculative layers base-as-draft speculative decoding on the continuous
scheduler (DESIGN.md §15): each round drafts --draft-k tokens per lane
with the resident base weights and verifies all of them through the
lane's banked variant overlay in ONE call — token streams stay bit-exact
with plain continuous decode, and the printed acceptance rate shows how
often base and variant agree (the paper's small-delta premise)::

    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b \
        --reduced --mode fused --speculative --draft-k 4 \
        --variants 3 --requests 12 --warmup

--mesh DATA,MODEL serves the whole deployment data×model-parallel
(DESIGN.md §11): base weights and every overlay/bank leaf land
tensor-parallel over ``model``, decode lanes span ``data``.  Needs
DATA*MODEL visible devices, e.g.::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python -m repro.launch.serve --arch qwen3-8b --reduced \
        --mode fused --scheduler continuous --mesh 2,2
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--variants", type=int, default=3)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--mode", choices=("dense", "fused"), default="dense")
    ap.add_argument("--base-dtype", choices=("fp", "int8"), default="fp",
                    help="resident base-weight dtype: fp is bf16; int8 "
                         "stores every shadowed target symmetric "
                         "per-channel (core/quantize.py) — ~0.5x resident "
                         "base HBM (DESIGN.md §16); deepseek-7b at full "
                         "width fits one 16 GB chip only as int8")
    ap.add_argument("--scheduler", choices=("group", "continuous"),
                    default="group")
    ap.add_argument("--max-resident", type=int, default=0,
                    help="0 -> 2 for dense, 8 for fused")
    ap.add_argument("--updates", type=int, default=0,
                    help="incremental update+hot-swap cycles on variant v0")
    ap.add_argument("--store-dir", default=None,
                    help="persist artifacts here (default: in-memory)")
    ap.add_argument("--mesh", default=None,
                    metavar="DATA,MODEL | POD,DATA,MODEL",
                    help="serve on a (data, model) mesh — or, with three "
                         "values, a (pod, data, model) mesh (default: "
                         "single device)")
    ap.add_argument("--pod-banks", action="store_true",
                    help="pod-local overlay banks + affinity routing "
                         "(DESIGN.md §17): bank slots shard over the "
                         "mesh's pod axis, requests steer to the pod "
                         "already holding their variant (requires a "
                         "3-value --mesh and --scheduler continuous)")
    ap.add_argument("--admission-pacing", type=float, default=0.002,
                    metavar="SECONDS",
                    help="async-admission ingest pacing: worker sleep "
                         "between artifact module streams (0 disables; "
                         "default 0.002)")
    ap.add_argument("--kernel-dispatch", choices=("shard_map", "gspmd"),
                    default="shard_map",
                    help="mesh-mode delta-GEMM lowering: per-shard "
                         "shard_map kernels (default) or the PR-4 "
                         "GSPMD-partitioned global kernels")
    ap.add_argument("--speculative", action="store_true",
                    help="base-as-draft speculative decoding on the "
                         "continuous scheduler (requires --mode fused; "
                         "DESIGN.md §15) — bit-exact tokens, fewer "
                         "dispatches per emitted token")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="speculative draft length (adaptive ladder "
                         "down-shifts under low acceptance)")
    ap.add_argument("--async-admission", action="store_true",
                    help="ingest+stage variant artifacts on a background "
                         "pipeline and commit between decode steps "
                         "(publish/update return without blocking; "
                         "requires --scheduler continuous)")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-compile every decode step pair before "
                         "serving (DESIGN.md §14) — with --compile-cache "
                         "a warm restart deserializes instead of "
                         "recompiling")
    ap.add_argument("--compile-cache", default=None, metavar="DIR",
                    help="persistent compile-cache directory (also "
                         "honours REPRO_COMPILE_CACHE_DIR)")
    args = ap.parse_args()
    if args.speculative:
        if args.mode != "fused":
            ap.error("--speculative requires --mode fused (verify runs "
                     "through the packed overlay bank)")
        args.scheduler = "continuous"   # Deployment(speculative=True)
                                        # upgrades it to "speculative"
    if args.scheduler == "continuous" and args.mode != "fused":
        ap.error("--scheduler continuous requires --mode fused "
                 "(mixed batches serve from the packed overlay bank)")
    if args.async_admission and args.scheduler != "continuous":
        ap.error("--async-admission requires --scheduler continuous "
                 "(staged overlays commit into the overlay bank between "
                 "decode steps)")

    import numpy as np
    from repro.configs import get_config
    from repro.core.compile_cache import use_jax_cache
    from repro.launch import build as B
    from repro.models import build_model
    from repro.serving import Deployment

    use_jax_cache()

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_host_mesh
        try:
            parts = [int(p) for p in args.mesh.split(",")]
            if len(parts) == 2:
                pod, (data, model_par) = 0, parts
            elif len(parts) == 3:
                pod, data, model_par = parts
            else:
                raise ValueError(args.mesh)
        except ValueError:
            ap.error("--mesh expects DATA,MODEL or POD,DATA,MODEL, "
                     "e.g. --mesh 2,2 or --mesh 2,2,2")
        mesh = make_host_mesh(data, model_par, pod=pod)
    if args.pod_banks:
        if mesh is None or "pod" not in mesh.axis_names:
            ap.error("--pod-banks needs a 3-value --mesh POD,DATA,MODEL")
        if args.scheduler != "continuous":
            ap.error("--pod-banks requires --scheduler continuous "
                     "(the affinity router lives in the slot scheduler)")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    # the base is built in its resident dtype and the variants are drawn
    # as packed artifacts (launch/build.py): nothing full-precision or
    # dense per variant is ever materialised, so published widths fit
    bank_size = args.variants + 2
    plan = B.memory_plan(model, base_dtype=args.base_dtype,
                         bank_size=bank_size, kv_tokens=args.batch * 64)
    print(f"memory plan: base {plan['base'] / 1e9:.3f} GB, bank slot "
          f"{plan['slot'] / 1e9:.3f} GB x {bank_size}, KV "
          f"{plan['kv_per_token'] / 1e6:.4f} MB/token; total "
          f"{plan['total'] / 1e9:.3f} GB")
    base, param_axes, param_shardings = B.init_base(
        model, 0, base_dtype=args.base_dtype, mesh=mesh)

    max_resident = args.max_resident or (8 if args.mode == "fused" else 2)
    dep = Deployment(model, base, root_dir=args.store_dir,
                     mode=args.mode, scheduler=args.scheduler,
                     batch_size=args.batch, prompt_len=16, max_len=64,
                     max_resident=max_resident,
                     bank_size=bank_size,
                     mesh=mesh, param_axes=param_axes if mesh else None,
                     param_shardings=param_shardings,
                     kernel_dispatch=args.kernel_dispatch,
                     async_admission=args.async_admission,
                     speculative=args.speculative, draft_k=args.draft_k,
                     warmup=args.warmup,
                     compile_cache_dir=args.compile_cache,
                     base_dtype=args.base_dtype,
                     pod_banks=args.pod_banks,
                     admission_pacing_s=args.admission_pacing)
    if args.base_dtype == "int8":
        qs = dep.registry.quant_stats
        print(f"int8 base: {qs['targets']} targets, "
              f"{qs['fp_bytes']} -> {qs['int8_bytes']} bytes "
              f"(ratio {qs['ratio']:.3f})")
    del base                    # the deployment holds the resident base
    flat_shapes, _, _ = B.base_struct(model)
    tunes = {}
    for i in range(args.variants):
        tunes[f"v{i}"] = B.random_variant(flat_shapes, 100 + i)
        dep.publish(f"v{i}", tunes[f"v{i}"])

    rng = np.random.default_rng(0)
    names = dep.variants()
    for i in range(args.requests):
        dep.submit(rng.integers(1, cfg.vocab_size, size=8),
                   variant=names[i % len(names)],
                   max_new_tokens=args.new_tokens)
    dep.drain()

    for u in range(args.updates):
        # a small residual on v0, shipped as a patch
        tunes["v0"] = B.perturb_variant(tunes["v0"], 200 + u)
        v = dep.update("v0", tunes["v0"])
        print(f"update {u}: v0 -> version {v}")
        for _ in range(args.batch):
            dep.submit(rng.integers(1, cfg.vocab_size, size=8),
                       variant="v0", max_new_tokens=args.new_tokens)
        dep.drain()
    if args.updates:
        v = dep.rollback("v0")
        print(f"rollback: v0 -> version {v}")
        dep.submit(rng.integers(1, cfg.vocab_size, size=8), variant="v0",
                   max_new_tokens=args.new_tokens)
        dep.drain()

    print("metrics:", dep.metrics)
    print("registry:", dep.stats)
    st = dep.status()
    if "speculative" in st:
        sp = st["speculative"]
        print(f"speculative: acceptance={sp['acceptance']:.3f} "
              f"rounds={sp['rounds']} current_k={sp['current_k']} "
              f"ttft_mean={st['ttft']['mean_seconds']:.4f}s")
    print("compiles:", st["steps"])
    if st["compile_cache"] is not None:
        print("compile-cache:", st["compile_cache"])
    if dep.admission is not None:
        print("admission:", dep.admission.stats)
    print("hbm:", {k: st["hbm"][k] for k in ("base_dtype", "base_bytes",
                                             "bank_bytes")})
    if mesh is not None:
        print("base per-device bytes:", st["hbm"]["base_per_device"])
        if dep.registry.bank is not None:
            print("bank per-device bytes:",
                  st["hbm"]["bank_per_device"])
    if args.pod_banks:
        af = st["affinity"]
        print(f"affinity: pods={af['pods']} hits={af['hits']} "
              f"misses={af['misses']} hit_rate={af['hit_rate']:.3f}")
        print("bank per-pod bytes:", st["hbm"]["bank_per_pod"])
        print("bank residents per pod:",
              st["hbm"]["bank_resident_per_pod"])
        bank = dep.registry.bank
        if bank is not None:
            print(f"admission bytes: in-pod="
                  f"{bank.stats['admit_bytes_in_pod']} cross-pod="
                  f"{bank.stats['admit_bytes_cross_pod']}")
    print(f"ttft: p50={st['ttft']['p50_seconds']:.4f}s "
          f"p99={st['ttft']['p99_seconds']:.4f}s "
          f"(n={st['ttft']['count']})")
    dep.close()


if __name__ == "__main__":
    main()
