"""Per-shard (shard_map) vs GSPMD-partitioned delta kernels (DESIGN.md §12).

On a forced 4-host-device (2, 2) data×model mesh this measures, at toy
size:

* kernel-level latency + parity: the fused delta GEMM lowered per-shard
  under shard_map (kernels/dispatch.py) vs the PR-4 path of handing the
  global Pallas call to GSPMD — row-sharded and col-sharded (psum)
  weights, plus the banked mixed-variant kernel;
* engine-level ACCEPTANCE: the continuous-batching engine must emit
  bit-identical greedy tokens under ``kernel_dispatch="shard_map"`` and
  ``"gspmd"`` for the same mixed workload (token_parity gates the
  sharded-smoke CI job), with drain latency reported for both.

Host-device emulation: latencies are plumbing numbers, not performance
claims — the point on real hardware is that the per-shard lowering EXISTS
(GSPMD cannot slice an opaque kernel call), not that it wins on a CPU.

jax fixes its device count at first init, so with fewer than 4 visible
devices the measurement re-execs in a subprocess with
``--xla_force_host_platform_device_count=4`` (the dry-run pattern).
"""
from __future__ import annotations

import os
import subprocess
import sys

TRAFFIC = ["v0", "v1", "v0", "v2", "v1", "v0", "v2", "v1"]
MAX_NEW = 8
BATCH = 4
REPS = 20


def _timed(fn, reps=REPS):
    import time

    import jax
    jax.block_until_ready(fn())            # warm / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def _kernel_rows(mesh) -> list:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import row
    from repro.distributed import sharding as S
    from repro.kernels import dispatch as D
    from repro.kernels import ops as K

    rules = S.rules_for("decode")
    rng = np.random.default_rng(0)
    rows = []
    cases = {
        # (n, k, waxes): row-sharded weight / col-sharded (psum) weight
        "row_sharded": (256, 128, ("ffn", "embed")),
        "col_sharded_psum": (128, 256, ("embed", "ffn")),
    }
    for name, (n, k, waxes) in cases.items():
        packed = jnp.asarray(rng.integers(0, 256, (n, k // 8), np.uint8))
        vr = jnp.asarray(rng.normal(size=(n,)).astype(np.float16))
        vc = jnp.zeros((k,), jnp.float16)
        wb = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
        x = jnp.asarray(rng.normal(size=(BATCH * 2, k)).astype(np.float32))
        args = (x, packed, vr, vc, wb)

        # one jit per lowering; the dispatch decision is read at TRACE
        # time, so each traces inside its own context and the timed loop
        # runs the compiled executable (apples to apples)
        jit_shard = jax.jit(lambda *a: K.bitlinear_axes(*a, waxes=waxes))
        jit_gspmd = jax.jit(lambda *a: K.bitlinear_axes(*a, waxes=waxes))
        with mesh, S.shard_ctx(mesh, rules):
            got = np.asarray(jit_shard(*args))
            with D.no_dispatch():
                want = np.asarray(jit_gspmd(*args))
        parity = bool(np.allclose(got, want, rtol=2e-5, atol=2e-5))
        us_shard = _timed(lambda: jit_shard(*args))
        us_gspmd = _timed(lambda: jit_gspmd(*args))
        rows.append(row(f"shard_map_kernels/{name}", us_shard,
                        f"gspmd_us={us_gspmd:.0f};kernel_parity={parity}"))
    return rows


def _measure() -> list:
    import time

    import jax
    import numpy as np
    from benchmarks.common import row, tiny_pair
    from repro.core import calibration as C
    from repro.launch.mesh import make_host_mesh
    from repro.models.param import split
    from repro.serving import Deployment

    mesh = make_host_mesh(2, 2)
    rows = _kernel_rows(mesh)

    model, base, ft, _, _ = tiny_pair("deepseek-7b", layers=2,
                                      base_steps=20, ft_steps=10)
    _, param_axes = split(model.init(jax.random.PRNGKey(0)))
    dms = {f"v{i}": C.compress(base, jax.tree.map(
        lambda b, f, s=i: b + (1 + 0.1 * s) * (f - b), base, ft))
        for i in range(3)}

    def run(kernel_dispatch):
        dep = Deployment(model, base, batch_size=BATCH, prompt_len=16,
                         max_len=64, bank_size=5, mesh=mesh,
                         param_axes=param_axes,
                         kernel_dispatch=kernel_dispatch)
        for name, dm in dms.items():
            dep.publish(name, dm)
        warm = [dep.submit(np.arange(1, 9), variant=f"v{i % 3}",
                           max_new_tokens=2) for i in range(BATCH + 1)]
        dep.drain()
        assert all(dep.result(w).status == "done" for w in warm)
        rids = [dep.submit(np.arange(1, 9), variant=v,
                           max_new_tokens=MAX_NEW) for v in TRAFFIC]
        t0 = time.perf_counter()
        dep.drain()
        dt = time.perf_counter() - t0
        return [dep.result(r).out_tokens for r in rids], dt

    toks_shard, dt_shard = run("shard_map")
    toks_gspmd, dt_gspmd = run("gspmd")
    parity = toks_shard == toks_gspmd
    generated = sum(len(t) for t in toks_shard)
    rows.append(row("shard_map_kernels/engine_2x2_continuous",
                    dt_shard * 1e6,
                    f"tokens={generated};gspmd_us={dt_gspmd * 1e6:.0f};"
                    f"token_parity={parity}"))
    return rows


def run() -> list:
    import jax
    if len(jax.devices()) >= 4:
        return _measure()
    # the child forces host devices: it runs on the CPU (this process may
    # already hold the accelerator), and its rows say so
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", ""), ".") if p)
    r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    if r.returncode != 0:
        tail = (r.stderr or r.stdout).strip().splitlines()[-1:]
        raise RuntimeError(f"shard_map subprocess failed: {tail}")
    return [ln + ";platform=cpu" for ln in r.stdout.splitlines()
            if ln.startswith("shard_map_kernels/")]


if __name__ == "__main__":
    print("\n".join(run()))
