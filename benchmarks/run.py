"""Benchmark driver: one section per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV rows.  Sections:
  table1   quality: baseline / BitDelta scalar / per-axis vector
  table2   artifact sizes for all 10 assigned architectures
  load     cold-start: delta apply vs full fp16 checkpoint
  axis     Fig. 2 analog: row/col selection counts per sub-type
  kernel   Pallas kernel byte accounting + correctness
  serving  multi-tenant hot-swap engine throughput
  fused    on-the-fly (packed-overlay) vs swap-then-dense serving
  continuous mixed-variant continuous batching vs grouped-by-variant
  speculative_decoding base-as-draft speculative rounds vs plain
           continuous decode: speedup, acceptance, exact token parity
           (DESIGN.md §15)
  update_latency incremental publish_update + hot-swap vs full republish
  sharded_serving banked decode on a host mesh: parity + per-device bytes
  pod_affinity pod-local overlay banks + affinity routing vs the global
           bank on a (2,2,2) mesh: cross-pod admission bytes, affinity
           hit rate, publish→first-token, token parity (DESIGN.md §17)
  shard_map_kernels per-shard vs GSPMD-partitioned delta kernels: latency
           + kernel/token parity at forced 4 host devices (DESIGN.md §12)
  admission_overlap async vs inline admission on a busy node: publish→
           first-token, decode-stall ceiling, token parity (DESIGN.md §13)
  compile_cache cold vs warm restart-to-first-token through the
           persistent compile cache, in forced subprocesses: speedup,
           zero-warm-compiles, token parity (DESIGN.md §14)
  quantized_base int8 base + fused tile dequant vs fp base: resident
           bytes per device, greedy-token agreement, drain throughput
           (DESIGN.md §16)
  roofline dry-run roofline terms per (arch × shape × mesh)

Sections that need several devices (sharded_serving, pod_affinity,
shard_map_kernels, compile_cache) run them in child processes pinned to
the CPU with forced host devices — this process may hold the
accelerator — and tag their rows ``platform=cpu``.

``--strict`` exits nonzero when any section errors (CI gate — by default
a crash is swallowed into a ``*/ERROR,0,...`` CSV row and the driver
exits 0, which hides regressions).  ``--sections a,b`` runs a subset.
``--json OUT`` additionally writes the rows as machine-readable JSON:
per-section row list with the ``derived`` k=v fields parsed into typed
metrics and a per-section/global pass verdict (every ``pass_*`` field
true and no ERROR rows) — the artifact CI uploads per run.
"""
from __future__ import annotations

import sys
import traceback


def _section(name: str, fn) -> list:
    try:
        return fn()
    except Exception:
        tb = traceback.format_exc().strip().splitlines()[-1]
        return [f"{name}/ERROR,0,{tb[:160]}"]


def serving_bench() -> list:
    import numpy as np
    from benchmarks.common import row, tiny_pair
    from repro.core import calibration as C
    from repro.serving import ServingEngine, VariantRegistry
    model, base, ft, _, _ = tiny_pair()
    reg = VariantRegistry(base, max_resident=2)
    reg.register("v1", C.compress(base, ft))
    reg.register("v2", C.compress(base, ft, scalar=True))
    eng = ServingEngine(model, reg, batch_size=4, prompt_len=16, max_len=64)
    import time
    t0 = time.perf_counter()
    for i in range(12):
        eng.submit(np.arange(1, 9), variant=["__base__", "v1", "v2"][i % 3],
                   max_new_tokens=8)
    eng.run_until_drained()
    dt = time.perf_counter() - t0
    m = eng.metrics
    tput = m["tokens_generated"] / max(m["decode_seconds"], 1e-9)
    return [row("serving/12req_3variants", dt * 1e6,
                f"tokens={m['tokens_generated']};decode_tps={tput:.0f};"
                f"swaps={reg.stats['swaps']};failed={m['failed']}")]


def _parse_derived(derived: str) -> dict:
    """Type the ``k=v;k=v`` derived field of one CSV row: bools, ints and
    floats become native JSON values, everything else stays a string."""
    out: dict = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, _, v = part.partition("=")
        if v in ("True", "False"):
            out[k] = v == "True"
            continue
        try:
            out[k] = int(v)
        except ValueError:
            try:
                out[k] = float(v)
            except ValueError:
                out[k] = v
    return out


def _json_report(by_section: dict) -> dict:
    """Machine-readable run report: per-section parsed rows + pass
    verdicts.  A section passes when it emitted no ERROR row and every
    ``pass_*`` metric it declared is true."""
    sections = {}
    for name, rows in by_section.items():
        parsed = []
        ok = True
        for r in rows:
            rname, _, rest = r.partition(",")
            us, _, derived = rest.partition(",")
            metrics = _parse_derived(derived)
            if "/ERROR," in r:
                ok = False
            if any(k.startswith("pass_") and v is False
                   for k, v in metrics.items()):
                ok = False
            try:
                us_val = float(us)
            except ValueError:
                us_val = 0.0
            parsed.append({"name": rname, "us_per_call": us_val,
                           "metrics": metrics})
        sections[name] = {"rows": parsed, "ok": ok}
    return {"sections": sections,
            "ok": all(s["ok"] for s in sections.values())}


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 if any section emits an ERROR row")
    ap.add_argument("--sections", default=None,
                    help="comma-separated subset of sections to run")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write the rows as a machine-readable JSON "
                         "report (parsed metrics + per-section pass "
                         "verdicts) to this path")
    args = ap.parse_args()

    from repro.core.compile_cache import use_jax_cache
    use_jax_cache()
    from benchmarks import (admission_overlap, axis_stats, compile_cache,
                            continuous_batching, fused_serving, kernel_bench,
                            load_time, pod_affinity, quantized_base,
                            roofline, shard_map_kernels, sharded_serving,
                            speculative_decoding, table1_quality,
                            table2_sizes, update_latency)
    sections = [                                      # cheap first
        ("table2", table2_sizes.run),
        ("kernel", kernel_bench.run),
        ("load_time", load_time.run),
        ("table1", table1_quality.run),
        ("axis_stats", axis_stats.run),
        ("serving", serving_bench),
        ("fused", fused_serving.run),
        ("continuous_batching", continuous_batching.run),
        ("speculative_decoding", speculative_decoding.run),
        ("update_latency", update_latency.run),
        ("admission_overlap", admission_overlap.run),
        ("compile_cache", compile_cache.run),
        ("quantized_base", quantized_base.run),
        ("sharded_serving", sharded_serving.run),
        ("pod_affinity", pod_affinity.run),
        ("shard_map_kernels", shard_map_kernels.run),
        ("roofline", roofline.run),
    ]
    if args.sections:
        wanted = {s.strip() for s in args.sections.split(",")}
        unknown = wanted - {n for n, _ in sections}
        if unknown:
            ap.error(f"unknown sections: {sorted(unknown)}")
        sections = [(n, f) for n, f in sections if n in wanted]
    rows = []
    by_section: dict = {}
    for name, fn in sections:
        by_section[name] = _section(name, fn)
        rows += by_section[name]
    print("name,us_per_call,derived")
    print("\n".join(rows))
    if args.json:
        import json
        import pathlib
        report = _json_report(by_section)
        p = pathlib.Path(args.json)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(report, indent=2))
        print(f"json report -> {p} (ok={report['ok']})", file=sys.stderr)
    errors = [r for r in rows if "/ERROR," in r]
    if args.strict and errors:
        print(f"STRICT: {len(errors)} section error(s)", file=sys.stderr)
        for r in errors:
            print(f"  {r}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
