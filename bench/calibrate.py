"""Readings that set a cell's limits: the program and the control.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 ...

In one process, for each seed: the cell's run at its own load (a window
of ``--seconds``), its served tokens compared with the float32 reference
(the program's reading: the widest logit gap) and, on the same prompts and
tokens, the control's reading: the reference computed with the int4 base
put in the program's place, read as the gap under the float32 reference of
the token it puts first.  Prints one JSON line per seed and a summary:
the lower reading is the largest the program gives, the upper the
smallest the control gives.  The control goes through the same
comparison as the program (``check.verdict``) against the cell's limit,
so each seed's line also says whether the control came out correct: it
must not.  Never run by the benchmark's own runs.
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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", default="int4")
    args = ap.parse_args()
    import jax

    from bench import run as R
    from bench.spec import Cell
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU found", file=sys.stderr)
        return 1
    program, control = [], []
    for seed in args.seeds:
        res = R.execute(Cell(args.workload), seed, args.seconds, False,
                        control=args.control)
        gap = res["checks"]["max_logit_gap"]["value"]
        program.append(gap)
        control.append(res["control"]["max_logit_gap"])
        print(json.dumps({"seed": seed, "program_gap": gap,
                          "control_gap": res["control"]["max_logit_gap"],
                          "correct": res["correct"],
                          "control_correct": res["control"]["correct"],
                          "memory_peak_bytes":
                              res["device"]["memory_peak_bytes"],
                          "metrics": res["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload, "control": args.control,
                      "lower": max(program), "upper": min(control),
                      "program": program, "control_readings": control}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
