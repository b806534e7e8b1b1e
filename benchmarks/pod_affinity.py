"""Pod-local overlay banks + affinity routing on a (2, 2, 2) mesh
(DESIGN.md §17).

A/B of the same skewed mixed-variant workload on an 8-device
(pod, data, model) host mesh:

* **global** (pod_banks=False) — the PR-5 bank: every slot replicated on
  all devices, so each admission payload lands once per pod;
* **pod-local** (pod_banks=True) — bank slots shard over the pod axis;
  the affinity router steers requests to the pod already holding their
  variant, and an admission scatter writes exactly one pod's shard.

Reported (and strict-gated in CI):

* greedy-token parity between the two bank modes — slot placement is a
  layout/routing decision, never a numerics decision;
* layout-derived admission traffic: bytes landing inside the admitting
  pod vs bytes crossing the pod interconnect — pod-local must move
  <= 0.6x the global bank's cross-pod bytes (it moves zero);
* affinity hit AND miss counters — the skewed traffic must exercise both
  the steering path and the cold-pod admit-on-demand path;
* publish -> first-token latency for a freshly published variant under
  pod-local banks, plus TTFT p50/p99 from the engine reservoir.

jax fixes its device count at first init, so with fewer than 8 visible
devices the measurement re-execs in a subprocess with
``--xla_force_host_platform_device_count=8`` (the dry-run pattern) and
the CSV rows pass through.
"""
from __future__ import annotations

import os
import subprocess
import sys

# skewed traffic: v0 is hot (affinity hits once resident), v1/v2 colder
# (their first touches on a second pod are cold-pod misses)
TRAFFIC = ["v0", "v0", "v1", "v0", "v2", "v0", "v1", "v0",
           "v2", "v0", "v0", "v1"]
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
    mesh = make_host_mesh(2, 2, pod=2)

    def run(pod_banks):
        dep = Deployment(model, base, batch_size=BATCH, prompt_len=16,
                         max_len=64, bank_size=5, mesh=mesh,
                         param_axes=param_axes, pod_banks=pod_banks)
        for name, dm in dms.items():
            dep.publish(name, dm)
        rids = [dep.submit(np.arange(1, 9), variant=v,
                           max_new_tokens=MAX_NEW) for v in TRAFFIC]
        t0 = time.perf_counter()
        dep.drain()
        dt = time.perf_counter() - t0
        toks = [dep.result(r).out_tokens for r in rids]
        assert all(dep.result(r).status == "done" for r in rids)
        return toks, dt, dep

    toks_global, _, dep_global = run(False)
    toks_pod, dt, dep = run(True)
    parity = toks_pod == toks_global
    generated = sum(len(t) for t in toks_pod)

    gstats = dep_global.registry.bank.stats
    pstats = dep.registry.bank.stats
    # cross-pod admission traffic: the layout-derived replication term
    # (global bank: payload x (pods - 1); pod-local: zero)
    cross_g = gstats["admit_bytes_cross_pod"]
    cross_p = pstats["admit_bytes_cross_pod"]
    ratio = cross_p / max(1, cross_g)
    st = dep.status()
    af = st["affinity"]
    per_pod = st["hbm"]["bank_per_pod"]
    pod_vals = sorted(per_pod.values())

    # publish -> first token under pod-local banks: a FRESH variant (cold
    # everywhere) admitted on demand into whichever pod the router picks
    dep.publish("v3", C.compress(base, jax.tree.map(
        lambda b, f: b + 1.4 * (f - b), base, ft)))
    t0 = time.perf_counter()
    rid = dep.submit(np.arange(1, 9), variant="v3", max_new_tokens=2)
    dep.drain()
    pub_ttft = time.perf_counter() - t0
    assert dep.result(rid).status == "done"
    ttft = dep.status()["ttft"]

    return [
        row("pod_affinity/banked_decode_2x2x2",
            dt * 1e6,
            f"tokens={generated};tput_tps={generated / dt:.1f};"
            f"token_parity={parity};pass_token_parity={parity}"),
        row("pod_affinity/admission_bytes", 0,
            f"in_pod={pstats['admit_bytes_in_pod']};"
            f"cross_pod={cross_p};cross_pod_global={cross_g};"
            f"ratio={ratio:.3f};pass_bytes_le_0_6x={ratio <= 0.6}"),
        row("pod_affinity/affinity", 0,
            f"pods={af['pods']};hits={af['hits']};misses={af['misses']};"
            f"hit_rate={af['hit_rate']:.3f};"
            f"pass_hits={af['hits'] > 0};pass_misses={af['misses'] > 0}"),
        row("pod_affinity/bank_per_pod_bytes", 0,
            f"min={pod_vals[0]};max={pod_vals[-1]};"
            f"total={dep.registry.bank.nbytes()};"
            f"global_total={dep_global.registry.bank.nbytes()}"),
        row("pod_affinity/publish_to_first_token", pub_ttft * 1e6,
            f"ttft_p50_s={ttft['p50_seconds']:.4f};"
            f"ttft_p99_s={ttft['p99_seconds']:.4f};"
            f"ttft_n={ttft['count']}"),
    ]


def run() -> list:
    import jax
    if len(jax.devices()) >= 8:
        return _measure()
    # re-exec with forced host devices (mirrors launch/dryrun.py)
    # the child forces host devices: it runs on the CPU (this process may
    # already hold the accelerator), and its rows say so
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", ""), ".") if p)
    r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    if r.returncode != 0:
        tail = (r.stderr or r.stdout).strip().splitlines()[-1:]
        raise RuntimeError(f"pod_affinity subprocess failed: {tail}")
    return [ln + ";platform=cpu" for ln in r.stdout.splitlines()
            if ln.startswith("pod_affinity/")]


if __name__ == "__main__":
    print("\n".join(run()))
