"""Compile a configuration's served programs for a described TPU v5e and
reckon the peak device memory of a cell, before any chip run.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py <config> [<config> ...]

Compiles the program's banked prefill and decode step (the pair the
continuous scheduler runs) and the admission merge at the configuration's
shapes for one chip of a described ``v5e:2x2`` topology, prints each
program's ``memory_analysis``, and adds what lives beside them while they
run: the base, the bank, and the engine's batch KV cache (the prefill
builds a fresh cache beside it, and the merge writes a third).  No chip
runs: memory only, never a time.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def rehearse(conf: dict) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import system, work
    from repro.core import quantize as Q
    from repro.kernels import ops as K
    from repro.models import build_model
    from repro.models import delta_overlay as DO

    K._interpret = lambda: False        # compile the Mosaic kernels
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])
    d = conf["deployment"]
    lanes, plen, max_len = d["lanes"], d["prompt_len"], d["max_len"]
    model = build_model(system.program_config(conf))
    shapes = system.served_shapes(model)
    from bench.weights import is_target
    paths = sorted(p for p, s in shapes.items() if is_target(p, s))
    flat = {p: jax.ShapeDtypeStruct(s, jnp.bfloat16) for p, s in
            shapes.items()}
    flat = Q.quantize_struct(flat, paths)
    bank = DO.overlay_struct(flat, paths, [], bank_size=d["bank_size"])

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sh), tree)

    params = place(system.program_params(model, {
        p: ({"q": w.q, "scale": w.scale} if isinstance(w, Q.QuantWeight)
            else w) for p, w in flat.items()}))
    bank = place(bank)
    vidx = jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=sh)
    token = vidx
    batch = {"tokens": jax.ShapeDtypeStruct((lanes, plen), jnp.int32,
                                            sharding=sh)}
    cache = place(jax.eval_shape(lambda: model.init_cache(lanes, max_len)))

    def prefill(p, b, v, x):
        return model.prefill(p, x, max_len, overlay=b, variant_idx=v)

    def decode(p, b, v, t, c):
        lg, c = model.decode_step(p, t, c, overlay=b, variant_idx=v)
        return jnp.argmax(lg, -1).astype(jnp.int32), c

    specs = jax.tree.leaves(model.cache_pspecs(),
                            is_leaf=lambda x: isinstance(x, tuple))

    def merge(old, fresh, mask):        # the engine's admission merge
        o_l, tdef = jax.tree_util.tree_flatten(old)
        f_l = jax.tree_util.tree_leaves(fresh)
        out = []
        for o, f, sp in zip(o_l, f_l, specs):
            shape = [1] * o.ndim
            shape[sp.index("act_batch")] = lanes
            out.append(jnp.where(mask.reshape(shape), f, o))
        return jax.tree_util.tree_unflatten(tdef, out)

    out = {}
    for name, fn, args in (
            ("prefill_banked", prefill, (params, bank, vidx, batch)),
            ("decode_banked", decode, (params, bank, vidx, token, cache)),
            ("merge", merge, (cache, cache, jax.ShapeDtypeStruct(
                (lanes,), jnp.bool_, sharding=sh)))):
        ma = jax.jit(fn).lower(*args).compile().memory_analysis()
        out[name] = {k: int(getattr(ma, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes")}
    plan = work.params_bytes_summary(conf, d["bank_size"], lanes * max_len)
    kv = lanes * max_len * plan["kv_per_token"]
    resident = plan["base"] + d["bank_size"] * plan["slot"]
    pf, dc, mg = out["prefill_banked"], out["decode_banked"], out["merge"]
    out["plan"] = plan
    out["peak_estimate"] = {
        "prefill": resident + kv + pf["output_size_in_bytes"]
        + pf["temp_size_in_bytes"],
        "decode": resident + kv + dc["output_size_in_bytes"]
        + dc["temp_size_in_bytes"],
        "merge": resident + 2 * kv + mg["output_size_in_bytes"]
        + mg["temp_size_in_bytes"],
    }
    return out


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.spec import BENCH
    for name in sys.argv[1:]:
        conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        res = rehearse(conf)
        print(json.dumps({"config": name, **{
            k: ({kk: round(vv / 1e9, 3) for kk, vv in v.items()}
                if isinstance(v, dict) else v) for k, v in res.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
