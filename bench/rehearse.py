"""Compile a configuration's served programs for a described TPU v5e and
reckon the peak device memory of a cell, before any chip run.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py <config> [<config> ...]

Compiles the programs the continuous scheduler runs, at the
configuration's shapes, for one chip of a described ``v5e:2x2`` topology:
the banked decode step and one admission wave per row bucket (the powers
of two below the lanes, and the lanes), each wave a prefill of its rows
whose first tokens and cache rows are written into the lanes' state.  It
prints each program's ``memory_analysis`` and the device bytes of the
deployment (``work.params_bytes_summary``: base, bank and the lanes' KV
cache), and estimates each program's peak as those bytes plus its outputs
and temporaries: a wave holds the old cache (an argument), the new cache
(its output) and its rows' prefill (temporaries).  No chip runs: memory
only, never a time.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def wave_buckets(lanes: int) -> list:
    """The engine's admission-wave row buckets for ``lanes`` lanes."""
    return [1 << k for k in range(lanes.bit_length())
            if 1 << k < lanes] + [lanes]


def rehearse(conf: dict, sharding=None) -> dict:
    """Memory of the served programs on ``sharding``'s device; by default
    one chip of a described v5e, with the Mosaic kernels compiled."""
    import jax
    import jax.numpy as jnp

    from bench import system, weights, work
    from repro.core import quantize as Q
    from repro.models import build_model
    from repro.models import delta_overlay as DO
    from repro.serving.engine import _write_rows

    if sharding is None:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        from repro.kernels import ops as K
        K._interpret = lambda: False        # compile the Mosaic kernels
        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
    d = conf["deployment"]
    lanes, plen, max_len = d["lanes"], d["prompt_len"], d["max_len"]
    model = build_model(system.program_config(conf))
    shapes = system.served_shapes(model)
    paths = weights.variant_paths(shapes, conf.get("leaf_roles"))
    flat = {p: jax.ShapeDtypeStruct(s, jnp.bfloat16) for p, s in
            shapes.items()}
    flat = Q.quantize_struct(flat, paths)
    bank = DO.overlay_struct(flat, paths, [], bank_size=d["bank_size"])

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    def ints(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding)

    params = place(system.program_params(model, {
        p: ({"q": w.q, "scale": w.scale} if isinstance(w, Q.QuantWeight)
            else w) for p, w in flat.items()}))
    bank = place(bank)
    cache = place(jax.eval_shape(lambda: model.init_cache(lanes, max_len)))
    axes = [sp.index("act_batch") for sp in jax.tree.leaves(
        model.cache_pspecs(), is_leaf=lambda x: isinstance(x, tuple))]

    def wave(p, b, v, x, rows_lane, token, c):   # engine.prefill_banked_fn
        logits, fresh = model.prefill(p, x, max_len, overlay=b,
                                      variant_idx=v)
        first = jnp.argmax(logits, -1).astype(jnp.int32)
        old, tdef = jax.tree_util.tree_flatten(c)
        return (_write_rows(token, first, rows_lane, 0),
                jax.tree_util.tree_unflatten(tdef, [
                    _write_rows(o, f, rows_lane, ax) for o, f, ax in
                    zip(old, jax.tree_util.tree_leaves(fresh), axes)]))

    def decode(p, b, v, t, c):
        lg, c = model.decode_step(p, t, c, overlay=b, variant_idx=v)
        return jnp.argmax(lg, -1).astype(jnp.int32), c

    programs = {"decode_banked": (decode, (params, bank, ints(lanes),
                                           ints(lanes), cache))}
    for rows in wave_buckets(lanes):
        batch = {"tokens": jax.ShapeDtypeStruct((rows, plen), jnp.int32,
                                                sharding=sharding)}
        kind = ("prefill_banked" if rows == lanes
                else f"prefill_banked/r{rows}")
        programs[kind] = (wave, (params, bank, ints(rows), batch, ints(rows),
                                 ints(lanes), cache))
    plan = work.params_bytes_summary(conf, d["bank_size"], lanes * max_len)
    out, peak = {}, {}
    for kind, (fn, args) in programs.items():
        ma = jax.jit(fn).lower(*args).compile().memory_analysis()
        out[kind] = {k: int(getattr(ma, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes")}
        peak[kind] = (plan["total"] + out[kind]["output_size_in_bytes"]
                      + out[kind]["temp_size_in_bytes"])
    out["plan"] = plan
    out["peak_estimate"] = peak
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
