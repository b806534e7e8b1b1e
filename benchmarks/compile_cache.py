"""Cold vs warm restart-to-first-token with the persistent compile cache
(DESIGN.md §14).

Restart cost is measured where it is actually paid: in a FRESH process.
The parent spawns the same child twice against one shared cache
directory —

* cold: empty cache; ``Deployment(warmup=True)`` AOT-compiles every step
  pair and the admission scatter, populating the cache;
* warm: same program, same shapes; every executable deserializes.

Each child times Deployment construction + warmup + publish + first
generated token (the restart-to-first-token SLO), then serves a longer
greedy request for the parity check.  The parent gates on:

* ``token_parity=True`` — a deserialized executable must emit exactly
  the tokens the freshly compiled one emits;
* ``warm_compiles=0`` — the warm path performed ZERO XLA compiles
  (engine steps + CachedCallable jits + dispatch memo combined);
* ``pass_ge_5x`` — warm restart at least 5× faster than cold.

CI greps these markers out of the CSV (``--strict`` in benchmarks/run.py
only gates crashes, not semantics).
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

_MARK = "CCBENCH:"


def _child(cache_dir: str) -> None:
    import time

    import numpy as np
    from benchmarks.common import tiny_pair
    from repro.core import calibration as C
    from repro.serving import Deployment

    model, base, ft, _, _ = tiny_pair()
    dm = C.compress(base, ft)

    # the restart span: construct + warm + publish + first token
    t0 = time.perf_counter()
    dep = Deployment(model, base, batch_size=2, prompt_len=8, max_len=32,
                     bank_size=4, compile_cache_dir=cache_dir, warmup=True)
    dep.publish("ft", dm)
    rid = dep.submit(np.arange(1, 7), variant="ft", max_new_tokens=1)
    dep.drain()
    span = time.perf_counter() - t0
    first = [int(t) for t in dep.result(rid).out_tokens]

    # parity payload: a longer greedy request (same avals — no compiles)
    rid2 = dep.submit(np.arange(1, 7), variant="ft", max_new_tokens=8)
    dep.drain()
    tokens = [int(t) for t in dep.result(rid2).out_tokens]

    st = dep.status()
    print(_MARK + json.dumps({
        "span_s": span, "first": first, "tokens": tokens,
        "step_compiles": st["steps"]["compiles"],
        "step_cache_hits": st["steps"]["cache_hits"],
        "warmup_s": st["metrics"]["warmup_seconds"],
        "cc": st["compile_cache"],
        "memo_persist_hits": st["dispatch_memo"]["persist_hits"],
        "memo_persist_compiles": st["dispatch_memo"]["persist_compiles"],
    }))


def _spawn(cache_dir: str) -> dict:
    # a fresh process per restart; it runs on the CPU (this process may
    # already hold the accelerator), and the rows say so
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", ""), ".") if p)
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", cache_dir],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if r.returncode != 0:
        tail = (r.stderr or r.stdout).strip().splitlines()[-1:]
        raise RuntimeError(f"compile_cache child failed: {tail}")
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith(_MARK)]
    if not lines:
        raise RuntimeError("compile_cache child printed no result line")
    return json.loads(lines[-1][len(_MARK):])


def run() -> list:
    from benchmarks.common import row
    from repro.core.compile_cache import CHECKOUT

    # a fixed directory, emptied before the cold child and after the warm
    cache_dir = str(CHECKOUT / ".jax_cache" / "compile_cache_bench")
    shutil.rmtree(cache_dir, ignore_errors=True)
    try:
        cold = _spawn(cache_dir)
        warm = _spawn(cache_dir)
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)

    speedup = cold["span_s"] / max(warm["span_s"], 1e-9)
    parity = (cold["tokens"] == warm["tokens"]
              and cold["first"] == warm["first"])
    warm_compiles = (warm["step_compiles"] + warm["cc"]["compiles"]
                     + warm["memo_persist_compiles"])
    return [
        row("compile_cache/cold_restart_first_token", cold["span_s"] * 1e6,
            f"step_compiles={cold['step_compiles']};"
            f"cc_puts={cold['cc']['puts']};"
            f"warmup_s={cold['warmup_s']:.2f}"),
        row("compile_cache/warm_restart_first_token", warm["span_s"] * 1e6,
            f"warm_compiles={warm_compiles};"
            f"step_cache_hits={warm['step_cache_hits']};"
            f"cc_hits={warm['cc']['hits']};"
            f"deserialize_s={warm['cc']['deserialize_seconds']:.2f}"),
        row("compile_cache/restart_speedup", 0,
            f"speedup={speedup:.1f}x;pass_ge_5x={speedup >= 5.0};"
            f"token_parity={parity};platform=cpu"),
    ]


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _child(sys.argv[2])
    else:
        print("\n".join(run()))
