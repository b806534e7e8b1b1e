"""Mesh-sharded banked decode at toy size (DESIGN.md §11).

Lowers the mixed-variant continuous-batching path onto a (2, 2) host
(data × model) mesh and reports:

* greedy-token parity: the sharded engine must emit exactly the tokens
  the single-device engine emits for the same mixed workload (sharding is
  a layout decision, not a numerics decision);
* per-device resident bank bytes (the sharded bank splits weight-axis
  tiles across ``model``; vectors and the bank axis are replicated);
* drained throughput on the mesh (host-device emulation — the number is
  a plumbing check, not a performance claim).

jax fixes its device count at first init, so when the current process
sees fewer than 4 devices the measurement runs in a SUBPROCESS with
``--xla_force_host_platform_device_count=4`` (the dry-run pattern) and
the CSV rows are passed through.
"""
from __future__ import annotations

import os
import subprocess
import sys


TRAFFIC = ["v0", "v1", "v0", "v2", "v1", "v0", "v2", "v1"]
MAX_NEW = 8
BATCH = 4


def _measure() -> list:
    import time

    import jax
    import numpy as np
    from benchmarks.common import row, tiny_pair
    from repro.core import calibration as C
    from repro.launch.mesh import make_host_mesh
    from repro.serving import Deployment

    model, base, ft, _, _ = tiny_pair("deepseek-7b", layers=2,
                                      base_steps=20, ft_steps=10)
    from repro.models.param import split
    _, param_axes = split(model.init(jax.random.PRNGKey(0)))
    dms = {f"v{i}": C.compress(base, jax.tree.map(
        lambda b, f, s=i: b + (1 + 0.1 * s) * (f - b), base, ft))
        for i in range(3)}

    def run(mesh):
        dep = Deployment(model, base, batch_size=BATCH, prompt_len=16,
                         max_len=64, bank_size=5, mesh=mesh,
                         param_axes=param_axes if mesh else None)
        for name, dm in dms.items():
            dep.publish(name, dm)
        # warm: compile + make every variant bank-resident
        warm = [dep.submit(np.arange(1, 9), variant=f"v{i % 3}",
                           max_new_tokens=2) for i in range(BATCH + 1)]
        dep.drain()
        assert all(dep.result(w).status == "done" for w in warm)
        rids = [dep.submit(np.arange(1, 9), variant=v,
                           max_new_tokens=MAX_NEW) for v in TRAFFIC]
        t0 = time.perf_counter()
        dep.drain()
        dt = time.perf_counter() - t0
        toks = [dep.result(r).out_tokens for r in rids]
        return toks, dt, dep

    toks_single, _, _ = run(None)
    mesh = make_host_mesh(2, 2)
    toks_mesh, dt, dep = run(mesh)
    parity = toks_mesh == toks_single
    generated = sum(len(t) for t in toks_mesh)
    per_dev = dep.registry.bank.per_device_nbytes()
    dev_vals = sorted(per_dev.values())
    return [
        row("sharded_serving/banked_decode_2x2",
            dt * 1e6,
            f"tokens={generated};tput_tps={generated / dt:.1f};"
            f"devices={len(per_dev)};token_parity={parity}"),
        row("sharded_serving/per_device_bank_bytes", 0,
            f"min={dev_vals[0]};max={dev_vals[-1]};"
            f"total={dep.registry.bank.nbytes()};"
            f"resident_bytes={dep.stats['resident_bytes']}"),
    ]


def run() -> list:
    import jax
    if len(jax.devices()) >= 4:
        return _measure()
    # re-exec with forced host devices (mirrors launch/dryrun.py)
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
        raise RuntimeError(f"sharded subprocess failed: {tail}")
    return [ln + ";platform=cpu" for ln in r.stdout.splitlines()
            if ln.startswith("sharded_serving/")]


if __name__ == "__main__":
    print("\n".join(run()))
