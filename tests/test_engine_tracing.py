"""The serving engine's own record of where a step's time goes: host spans
(``serve.*``) in the profiler's trace, counters in ``metrics``, and the
name scopes on the base GEMM and attention that a device trace reads."""
import dataclasses
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import calibration as C
from repro.models import build_model
from repro.models.param import split
from repro.serving import ServingEngine, VariantRegistry

SPANS = {"serve.step", "serve.admit", "serve.prefill", "serve.prefill.wait",
         "serve.merge", "serve.emit", "serve.decode", "serve.decode.wait",
         "serve.hook", "serve.compile"}


@pytest.fixture(scope="module")
def pair():
    cfg = dataclasses.replace(get_config("deepseek-7b").reduced(),
                              num_layers=2, compute_dtype="float32",
                              remat=False)
    model = build_model(cfg)
    base, _ = split(model.init(jax.random.PRNGKey(0)))
    pert, _ = split(model.init(jax.random.PRNGKey(1)))
    ft = jax.tree.map(lambda b, f: b + 0.05 * f, base, pert)
    return model, base, C.compress(base, ft)


def _engine(pair, step_times=None):
    model, base, dm = pair
    reg = VariantRegistry(base, mode="fused", bank_size=3)
    reg.register("v1", dm)
    eng = ServingEngine(model, reg, batch_size=3, prompt_len=8, max_len=32,
                        scheduler="continuous")
    if step_times is not None:
        eng.record_step_times = True
        eng.step_times = step_times
    # budgets that retire lanes at different steps: later waves admit
    # fewer rows than there are lanes
    for i, (v, m) in enumerate([("v1", 2), ("__base__", 5), ("v1", 3),
                                ("__base__", 2), ("v1", 4)]):
        eng.submit(np.arange(1 + i, 7 + i), variant=v, max_new_tokens=m)
    return eng


def test_prefill_rows_count_the_rows_each_wave_admitted(pair):
    eng = _engine(pair)
    waves = []
    prefill = eng._prefill_admitted

    def recording(newly):
        waves.append(len(newly))
        prefill(newly)
    eng._prefill_admitted = recording
    eng.run_until_drained()
    assert len(waves) == eng.metrics["prefills"] >= 2
    assert eng.metrics["prefill_rows"] == sum(waves) == 5
    assert eng.metrics["prefill_rows"] < eng.metrics["prefills"] * 3
    assert "batches" not in eng.metrics


def test_host_seconds_lie_within_the_loop_wall_time(pair):
    eng = _engine(pair)
    eng.run_until_drained()         # compiles: host time, not device time
    for i in range(4):
        eng.submit(np.arange(2 + i, 8 + i), max_new_tokens=3)
    before = eng.metrics["host_seconds"]
    t0 = time.perf_counter()
    eng.run_until_drained()
    wall = time.perf_counter() - t0
    host = eng.metrics["host_seconds"] - before
    assert 0.0 < host <= wall


class SleepyHook(list):
    def append(self, entry):
        time.sleep(0.05)
        super().append(entry)


def test_a_step_hook_that_sleeps_adds_nothing_to_host_seconds(pair):
    eng = _engine(pair)
    eng.run_until_drained()
    for i in range(4):
        eng.submit(np.arange(2 + i, 8 + i), max_new_tokens=3)
    eng.record_step_times = True
    eng.step_times = hook = SleepyHook()
    before = eng.metrics["host_seconds"]
    t0 = time.perf_counter()
    eng.run_until_drained()
    wall = time.perf_counter() - t0
    host = eng.metrics["host_seconds"] - before
    assert len(hook) >= 4
    assert 0.0 < host <= wall - 0.05 * len(hook)


def _host_spans(path: str) -> list:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append({"thread": (plane.name, line.name),
                                "name": ev.name, "start": ev.start_ns,
                                "end": ev.end_ns, "stats": dict(ev.stats)})
    return out


def test_a_trace_holds_every_serve_span_nested_in_its_step(pair, tmp_path):
    eng = _engine(pair, step_times=[])
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run_until_drained()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans = _host_spans(path)
    # bare names: the arguments come out as stats
    assert {s["name"] for s in spans} == SPANS
    steps = [s for s in spans if s["name"] == "serve.step"]
    assert [s["stats"]["step_num"] for s in steps] == sorted(
        s["stats"]["step_num"] for s in steps)
    for s in spans:
        if s["name"] == "serve.step":
            continue
        assert any(p["thread"] == s["thread"]
                   and p["start"] <= s["start"] and s["end"] <= p["end"]
                   for p in steps), s["name"]
    decodes = [s for s in spans if s["name"] == "serve.decode"]
    assert len(decodes) == eng.metrics["decode_steps"]
    assert all(1 <= s["stats"]["slots"] <= 3 for s in decodes)
    waves = [s for s in spans if s["name"] == "serve.prefill"]
    assert len(waves) == eng.metrics["prefills"]
    assert sum(s["stats"]["rows"] for s in waves) == 5
    assert {s["stats"]["lanes"] for s in waves} == {3}
    assert all(s["stats"]["bucket"] >= s["stats"]["rows"] for s in waves)
    assert sum(s["stats"]["bucket"] for s in waves) == \
        eng.metrics["prefill_rows_computed"]
    admits = [s for s in spans if s["name"] == "serve.admit"]
    assert sum(s["stats"]["admitted"] for s in admits) == 5
    assert admits[0]["stats"]["queued"] == 2


def test_scopes_name_the_base_gemm_and_attention_ops(pair):
    eng = _engine(pair)
    eng.run_until_drained()
    hlo = eng.step_hlo()
    # one admission-wave program per row bucket (1, 2 and all 3 lanes)
    assert set(hlo) == {"prefill_banked", "prefill_banked/r1",
                        "prefill_banked/r2", "decode_banked"}
    for text in hlo.values():
        stacks = [line.split('op_name="')[1].split('"')[0]
                  for line in text.splitlines() if 'op_name="' in line]
        assert any("/base_gemm/" in s for s in stacks)
        assert any("/attention/" in s for s in stacks)
