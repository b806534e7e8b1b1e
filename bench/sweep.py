"""Find a cell's knee once: the highest offered rate whose queue does not
grow through the window.

    python3 bench/sweep.py --workload <open cell> --seconds <s> --rates 0.5 0.8 ...

One process deploys the cell's configuration once, then offers its
traffic at each rate for ``--seconds`` and lets what is left finish
untimed before the next rate.  For each rate it prints the requests sent
and finished in the window, the waiting requests (due, no first token
yet) at a third of the window and at its end, their growth per second
(a least-squares slope over the window), TTFT p90 and tokens/s.  Its last
line gives the knee (``knee``) and the cell's rate, 0.8 times the knee;
both go into the traffic file and PERF.md by hand: the benchmark's cells
offer fixed rates and never search.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


GROWTH_PER_S = 0.1      # waiting requests gained per second, at most


def knee(rows: list, lanes: int):
    """The highest swept rate up to which, at every rate, the waiting
    requests grew by at most ``GROWTH_PER_S`` a second and ended the
    window no more than ``lanes``; None when the lowest rate already
    fails."""
    best = None
    for r in sorted(rows, key=lambda r: r["rate_rps"]):
        if (r["waiting_slope_per_s"] > GROWTH_PER_S
                or r["waiting_end"] > lanes):
            break
        best = r["rate_rps"]
    return best


def waiting_at(sent: list, t: float) -> int:
    return sum(1 for s in sent if s.due <= t and not (
        s.handle.out_tokens.times and s.handle.out_tokens.times[0] <= t))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    import jax
    import numpy as np

    from bench import drive, traffic as T
    from bench.readers import percentile
    from bench.spec import Cell
    from bench.system import System
    from repro.core.compile_cache import use_jax_cache
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU found", file=sys.stderr)
        return 1
    use_jax_cache()
    cell = Cell(args.workload)
    conf = cell.config
    system = System(conf, args.seed, lambda *a: print(*a, file=sys.stderr))
    system.warm_traffic(conf["vocab_size"])
    rows = []
    for i, rate in enumerate(args.rates):
        mix = dict(cell.traffic, rate_rps=rate)
        reqs = T.generate(mix, args.seed + i, variants=sorted(
            system.variants), vocab=conf["vocab_size"],
            seconds=args.seconds)
        w = drive.Window(system.dep, reqs, open_loop=True, queue_depth=0,
                         seconds=args.seconds)
        eng = system.engine
        rec = w.run(lambda: dict(eng.metrics))
        t0, t1 = rec["t0"], rec["t_end"]
        grid = np.linspace(t0 + args.seconds / 10, t1, 20)
        wait = [waiting_at(w.sent, t) for t in grid]
        slope = float(np.polyfit(grid - t0, wait, 1)[0])
        ttft = [((s.handle.out_tokens.times or [t1])[0] if (
            s.handle.out_tokens.times and
            s.handle.out_tokens.times[0] <= t1) else t1) - s.due
            for s in w.sent]
        toks = sum(1 for s in w.sent for t in s.handle.out_tokens.times
                   if t0 <= t <= t1)
        done = sum(1 for s in w.sent if s.handle.status == "done")
        rows.append({
            "rate_rps": rate, "sent": len(w.sent), "finished": done,
            "waiting_third": waiting_at(w.sent, t0 + args.seconds / 3),
            "waiting_end": wait[-1], "waiting_slope_per_s": slope,
            "ttft_p90_ms": 1e3 * percentile(ttft, 90),
            "tokens_per_s": toks / args.seconds,
            "decode_step_ms": 1e3 * (rec["after"]["decode_seconds"]
                                     - rec["before"]["decode_seconds"])
            / max(1, rec["after"]["decode_steps"]
                  - rec["before"]["decode_steps"]),
            "prefill_share": (rec["after"]["prefill_seconds"]
                              - rec["before"]["prefill_seconds"])
            / args.seconds})
        print(json.dumps(rows[-1]), flush=True)
        eng.step_times = []
        system.dep.drain()          # finish what is left, untimed
    system.close()
    k = knee(rows, system.lanes)
    print(json.dumps({"knee": k,
                      "rate_rps": None if k is None else round(0.8 * k, 3)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
