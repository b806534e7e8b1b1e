"""A whole run of the harness on the CPU at a tiny size.

It skips the look for a chip and drives the rest of a run: deployment
from the seed, the window, the stamped tokens, the readers, the reference
and the result line.  Then the timed path is broken underneath and
``correct`` has to come out false: once with a token altered where it is
produced, once with a decode step that returns its state unchanged.  The
int4 control has to fail the limit that the sound run meets.  The mixed
cell and the base-only cell are each driven so.
"""
import copy
import json
import os
import subprocess
import sys

import pytest

from bench import run as R
from bench import spec

SEED = 2 ** 33 + 17
CELL = "ds7b.mixed.open"
BASE_CELL = "ds7b.base.open"
LIMIT = 0.1             # the tiny model's sound gap reads about 0.02


def tiny_cell(name: str, config: str, traffic: str) -> spec.Cell:
    """Cell ``name`` (added when BENCHMARK.json has no such cell) of
    ``config`` under ``traffic``, every width cut to a size the CPU
    holds."""
    bench = copy.deepcopy(spec.load_benchmark())
    if name not in [w["name"] for w in bench["workloads"]]:
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "x"})
    conf = json.loads((spec.BENCH / "configs" / f"{config}.json")
                      .read_text())
    small = dict(hidden_size=64, intermediate_size=128,
                 num_attention_heads=4, num_key_value_heads=4,
                 vocab_size=256, num_hidden_layers=2)
    conf.update(small)
    conf["deployment"].update(lanes=4, bank_size=3, variants=2,
                              prompt_len=16, max_len=48)
    mix = json.loads((spec.BENCH / "traffic" / f"{traffic}.json")
                     .read_text())
    mix.update(prompt_len=16,
               output={"median": 4, "sigma": 0.9, "min": 2, "max": 24})
    if mix["arrivals"] == "open":
        mix["rate_rps"] = 6.0
    return spec.Cell.of(bench, name, conf, mix,
                        {"max_logit_gap": {"limit": LIMIT}})


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch, tmp_path):
    # the harness keeps JAX's cache where this says; the test process
    # read no cache directory at import, so nothing is written
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


@pytest.fixture(scope="module")
def sound():
    return R.execute(tiny_cell(CELL, "deepseek-7b.int8", "mixed.open"),
                     SEED, 2.0, True, control="int4")


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True
    checks = sound["checks"]
    assert checks["max_logit_gap"]["value"] <= LIMIT
    assert checks["failed_requests"]["value"] == 0
    assert checks["truncated_requests"]["value"] == 0
    assert sound["attempted"] > 4


def test_the_control_fails_the_limit(sound):
    control = sound["control"]
    assert control["max_logit_gap"] > 3 * sound["checks"][
        "max_logit_gap"]["value"]
    assert control["max_logit_gap"] > LIMIT
    assert control["correct"] is False


def test_result_line_keys(sound):
    keys = list(sound)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "checks"
    assert "control" in keys            # calibration runs only
    dev = sound["device"]
    for k in ("platform", "kind", "count", "memory_peak_bytes", "busy_s",
              "window_s"):
        assert k in dev
    assert set(sound["breakdown"]) == {"device_ops", "idle_gaps"}
    assert json.loads(json.dumps(sound)) == sound
    # the counters that need no chip are read; device metrics are not
    assert {"decode_step_ms", "prefill_stall_share", "engine_host_ms_per_step",
            "prefill_rows_used_share"} <= set(sound["metrics"])
    for m in sound["metrics"].values():
        assert set(m) == {"value", "unit"}


def _alter_tokens(orig):
    import jax.numpy as jnp

    def step(params, token, cache, cfg, overlay=None, variant_idx=None):
        logits, new = orig(params, token, cache, cfg, overlay=overlay,
                           variant_idx=variant_idx)
        return jnp.roll(logits, 1, axis=-1), new
    return step


def _keep_state(orig):
    def step(params, token, cache, cfg, overlay=None, variant_idx=None):
        logits, _ = orig(params, token, cache, cfg, overlay=overlay,
                         variant_idx=variant_idx)
        return logits, cache
    return step


def _broken_run(fault, monkeypatch, cell, traffic):
    from repro.models import transformer
    monkeypatch.setattr(transformer, "decode_step",
                        fault(transformer.decode_step))
    res = R.execute(tiny_cell(cell, "deepseek-7b.int8", traffic),
                    SEED, 2.0, False)
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > LIMIT


@pytest.mark.parametrize("fault", [_alter_tokens, _keep_state],
                         ids=["token_altered", "state_unchanged"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    _broken_run(fault, monkeypatch, CELL, "mixed.open")


@pytest.mark.parametrize("fault", [_alter_tokens, _keep_state],
                         ids=["token_altered", "state_unchanged"])
def test_a_broken_base_only_path_is_not_correct(fault, monkeypatch):
    _broken_run(fault, monkeypatch, BASE_CELL, "base.open")


def test_a_base_only_run_is_correct_and_its_control_is_not():
    res = R.execute(tiny_cell(BASE_CELL, "deepseek-7b.int8", "base.open"),
                    SEED, 2.0, False, control="int4")
    gap = res["checks"]["max_logit_gap"]["value"]
    assert res["correct"] is True and gap <= LIMIT
    assert res["control"]["max_logit_gap"] > max(3 * gap, LIMIT)
    assert res["control"]["correct"] is False


def test_without_a_tpu_the_command_prints_no_result(tmp_path):
    root = spec.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         "ds7b.mixed.open", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr
