"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``): the cell's configuration is deployed from
the seed — base made on the device, variants published into the overlay
bank, the banked step pair compiled or read from JAX's compilation cache
in ``<checkout>/.jax_cache`` — and two admission waves run through the
served path.  Then the window: the cell's traffic for ``--seconds``
seconds, stamped token by token.  Then, with the program's state freed,
the served tokens are compared with the plain reference (``check.py``).

``--trace 1`` profiles the window's last seconds and reports the cell's
per-layer metrics (after the window, the trace's ops are joined to the
step programs' HLO text for their name scopes); ``--trace 0`` reports its
end-to-end metrics.  The last line of standard output is one JSON object;
the numbers compared for ``correct`` end standard error and the result
line.  Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TRACE_SECONDS = 10.0


def log(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


class Compiles:
    """Counts XLA backend compiles in this process."""

    def __init__(self, jax):
        self.n = 0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


class Run:
    """What a metric reader reads: the window's records and context."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def engine_counters(eng, compiles: Compiles) -> dict:
    """Every number the engine counts (``ServingEngine.metrics``), and the
    process's backend compiles."""
    out = {k: v for k, v in eng.metrics.items()
           if isinstance(v, (int, float))}
    out["compiles"] = compiles.n
    return out


def peak_memory(jax) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def execute(cell, seed: int, seconds: float, trace: bool,
            control: str | None = None) -> dict:
    """Deploy, warm, measure, check; returns the result object.
    ``control`` (``calibrate.py`` only) also reads the lower-precision
    control on the same prompts and tokens."""
    import jax

    from bench import check, drive, peaks, traffic as T
    from bench import trace as TR
    from bench.system import System
    from repro.core.compile_cache import use_jax_cache

    cache = use_jax_cache()
    compiles = Compiles(jax)
    devices = jax.devices()[:cell.chips]
    kind = devices[0].device_kind
    peak = peaks.peaks(kind) if devices[0].platform == "tpu" else None
    conf, mix = cell.config, cell.traffic
    log(f"cell {cell.name} seed {seed} on {kind} x{len(devices)}; "
        f"compile cache {cache}")

    system = System(conf, seed, log)
    system.warm_traffic(conf["vocab_size"])
    requests = T.generate(mix, seed, variants=sorted(system.variants),
                          vocab=conf["vocab_size"], seconds=seconds)
    open_loop = mix["arrivals"] == "open"
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    window = drive.Window(
        system.dep, requests, open_loop=open_loop,
        queue_depth=int(mix.get("queue_per_lane", 0)) * system.lanes,
        seconds=seconds, trace_dir=trace_dir,
        trace_seconds=min(TRACE_SECONDS, seconds))
    eng = system.engine
    rec = window.run(lambda: engine_counters(eng, compiles))
    setup_s = rec["t0"] - T_START
    memory_peak = peak_memory(jax)
    sent = window.sent
    in_window = [s for s in sent
                 if not open_loop or s.due < rec["t_end"]]
    lateness = sorted(s.sent - s.due for s in sent if open_loop)
    if lateness:
        print(json.dumps({"generator_late_s": {
            "median": lateness[len(lateness) // 2],
            "max": lateness[-1], "requests": len(lateness)}}), flush=True)
    log(f"window: {len(in_window)} requests sent, "
        f"{sum(len(s.handle.out_tokens) for s in sent)} tokens, "
        f"{rec['after']['decode_steps'] - rec['before']['decode_steps']} "
        f"decode steps, {rec['after']['compiles'] - rec['before']['compiles']}"
        f" compiles inside the window; set-up {system.phases}")

    reduced = None
    if trace_dir is not None:
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if window.trace_t is not None and files:
            start, stop = window.trace_t
            reduced = TR.reduce(TR.load(files[0]), eng.step_hlo())
            reduced["host_interval"] = (start, stop)
        shutil.rmtree(trace_dir, ignore_errors=True)

    run = Run(cell=cell, conf=conf, traffic=mix, seconds=seconds,
              open_loop=open_loop, t0=rec["t0"], t_end=rec["t_end"],
              before=rec["before"], after=rec["after"], sent=sent,
              steps=window.steps, trace=reduced, peak=peak,
              chips=len(devices), setup_s=setup_s,
              prompt_len=system.prompt_len, lanes=system.lanes)
    metrics = {}
    for m in cell.metrics(trace):
        value = cell.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the program's state goes before the reference runs: only the
    # benchmark's own weights stay on the device
    failed = sum(1 for s in in_window if s.handle.status == "failed")
    truncated = sum(1 for s in sent if s.handle.status == "done"
                    and len(s.handle.out_tokens) != s.req.max_new_tokens)
    seqs = check.sequences(check.sample(sent, seed))
    weights, variants = system.weights, system.variants
    window.dep = eng.step_times = None
    system.close()
    del system, window, eng
    gc.collect()

    t_ref = time.perf_counter()
    widest = control_gap = None
    if seqs:
        g = check.gaps(cell.reference(), conf, weights, variants, seqs,
                       control=control)
        widest = check.widest(g["program"])
        if control is not None:
            control_gap = check.widest(g["control"])
    log(f"reference over {len(seqs)} requests, "
        f"{sum(len(s[3]) for s in seqs)} served tokens, in "
        f"{time.perf_counter() - t_ref:.1f} s")
    limit = cell.limits["max_logit_gap"]["limit"]
    checks = {
        "max_logit_gap": {"value": widest, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
        "truncated_requests": {"value": truncated, "limit": 0},
    }
    correct = check.verdict(checks)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": len(in_window),
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if control is not None:
        # the control in the program's place, through the same comparison
        result["control"] = {
            "correct": check.verdict(dict(checks, max_logit_gap={
                "value": control_gap, "limit": limit})),
            "max_logit_gap": control_gap}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench.spec import Cell
    cell = Cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU found (JAX's first device is "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}; nothing was run", file=sys.stderr)
        return 1
    result = execute(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
