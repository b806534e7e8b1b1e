"""End-to-end and per-layer readers on a synthetic token timeline."""
import types

import numpy as np
import pytest

from bench import spec, work
from bench.drive import Stamped

T0 = 100.0


def _request(due, times, prompt_len=4, variant="v0", status="done"):
    out = Stamped()
    out.times = list(times)
    out.extend(range(len(times)))
    handle = types.SimpleNamespace(out_tokens=out, status=status)
    req = types.SimpleNamespace(prompt=np.zeros(prompt_len, np.int32),
                                variant=variant,
                                max_new_tokens=len(times))
    return types.SimpleNamespace(req=req, due=due, handle=handle)


def _run(sent, seconds=10.0, open_loop=True, **kw):
    base = dict(sent=sent, seconds=seconds, open_loop=open_loop, t0=T0,
                t_end=T0 + seconds, steps=[], trace=None, peak=None,
                chips=1, prompt_len=4, setup_s=12.5,
                before={"prefill_seconds": 1.0, "decode_seconds": 2.0,
                        "decode_steps": 10},
                after={"prefill_seconds": 3.0, "decode_seconds": 4.0,
                       "decode_steps": 30})
    base.update(kw)
    return types.SimpleNamespace(**base)


def _read(name, run):
    return spec._module("metrics", name).read(run)


def test_ttft_runs_from_due_time_not_submit():
    # due at T0+1, first token at T0+1.5: 500 ms whenever it was sent
    sent = [_request(T0 + 1.0, [T0 + 1.5, T0 + 1.6])]
    assert _read("ttft_p90_ms", _run(sent)) == pytest.approx(500.0)


def test_ttft_counts_a_request_without_first_token_at_its_time_so_far():
    sent = [_request(T0 + 1.0, [T0 + 1.1]),
            _request(T0 + 6.0, [], status="running")]
    # waits 0.1 s and 4.0 s (window end at T0+10); p90 interpolates
    want = 1e3 * float(np.percentile([0.1, 4.0], 90))
    assert _read("ttft_p90_ms", _run(sent)) == pytest.approx(want)


def test_ttft_leaves_out_requests_due_outside_the_window():
    sent = [_request(T0 - 1.0, [T0 + 3.0]), _request(T0 + 2, [T0 + 2.2])]
    assert _read("ttft_p90_ms", _run(sent)) == pytest.approx(200.0)
    assert _read("ttft_p90_ms", _run(sent, open_loop=False)) is None


def test_a_stall_moves_itl_p95_and_a_median_of_chunks_would_not():
    """Tokens every 100 ms for 10 s, except three seconds of prefill
    stalls (six 500 ms gaps per lane, 8% of all gaps): the whole-window
    p95 lands on a stall; the median over 1 s chunks of each chunk's p95
    does not."""
    lanes, smooth = [], []
    for _ in range(4):
        times = list(T0 + 0.1 * np.arange(1, 41))            # to T0+4
        times += list(times[-1] + 0.5 * np.arange(1, 7))     # to T0+7
        times += list(times[-1] + 0.1 * np.arange(1, 30))    # to T0+9.9
        lanes.append(_request(T0, times))
        smooth.append(_request(T0, list(T0 + 0.1 * np.arange(1, 99))))
    assert _read("itl_p95_ms", _run(smooth)) == pytest.approx(100.0)
    assert _read("itl_p95_ms", _run(lanes)) == pytest.approx(500.0)
    chunks = []
    for c in range(10):
        gaps = [g for s in lanes
                for a, g in zip(s.handle.out_tokens.times[1:],
                                np.diff(s.handle.out_tokens.times))
                if T0 + c < a <= T0 + c + 1]
        chunks.append(np.percentile(gaps, 95))
    assert 1e3 * float(np.median(chunks)) == pytest.approx(100.0)


def test_itl_counts_only_gaps_inside_the_window():
    s = _request(T0 - 5, [T0 - 4.0, T0 + 0.5, T0 + 0.6, T0 + 11.0])
    assert _read("itl_p95_ms", _run([s])) == pytest.approx(100.0)


def test_counter_readers():
    run = _run([])
    assert _read("prefill_stall_share", run) == pytest.approx(20.0)
    assert _read("decode_step_ms", run) == pytest.approx(100.0)
    assert _read("setup_s", run) == 12.5


ENGINE = {"prefill_rows": 3, "prefill_rows_computed": 4,
          "host_seconds": 0.5, "decode_steps": 10}


def test_engine_counter_readers():
    after = {"prefill_rows": 9, "prefill_rows_computed": 12,
             "host_seconds": 0.52, "decode_steps": 30}
    run = _run([], before=ENGINE, after=after)
    assert _read("engine_host_ms_per_step", run) == pytest.approx(1.0)
    assert _read("prefill_rows_used_share", run) == pytest.approx(75.0)
    # a window without a wave or a step has nothing to read
    idle = _run([], before=ENGINE, after=ENGINE)
    assert _read("engine_host_ms_per_step", idle) is None
    assert _read("prefill_rows_used_share", idle) is None


def test_every_engine_counter_is_read():
    from bench import run as R
    eng = types.SimpleNamespace(metrics=dict(
        ENGINE, prefill_seconds=1.5, new_counter=2, label="x",
        ttft_samples=[0.1]))
    compiles = types.SimpleNamespace(n=4)
    assert R.engine_counters(eng, compiles) == dict(
        ENGINE, prefill_seconds=1.5, new_counter=2, compiles=4)


def test_device_readers_say_nothing_without_a_chip_or_trace():
    run = _run([_request(T0, [T0 + 1])])
    for name in ("idle_share", "sign_gemm_ms_per_step",
                 "sign_gemm_roofline.decode", "mfu.decode",
                 "base_gemm_ms_per_step", "attention_ms_per_step"):
        assert _read(name, run) is None


CONF = {"hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "vocab_size": 256}
PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def test_mfu_decode_over_step_time():
    steps = [(T0 + 1, 0.5, {"v0": 2}, [5, 9]),
             (T0 + 2, 0.5, {"__base__": 1}, [7]),
             (T0 + 20, 0.5, {"v0": 1}, [8])]          # after the window
    run = _run([], conf=CONF, peak=PEAK, steps=steps)
    want = sum(work.flops_per_token(CONF, c) for c in (5, 9, 7))
    assert _read("mfu.decode", run) == pytest.approx(100 * want / 1e12)


def _traced_run(custom_call_s, steps, **decode):
    trace = {"host_interval": (T0, T0 + 10), "idle_share": 0.25,
             "modules": {"decode_banked_fn": dict(
                 count=len(steps), device_s=1.0,
                 custom_call_s=custom_call_s, **decode)}}
    return _run([], conf=CONF, peak=PEAK, steps=steps, trace=trace)


def test_scope_readers_from_the_trace():
    steps = [(T0 + 1, 0.1, {"__base__": 3}, [5, 5, 5])] * 4
    run = _traced_run(0.004, steps, scopes={
        "base_gemm": 0.036, "attention": 0.008, "while": 0.1})
    assert _read("base_gemm_ms_per_step", run) == pytest.approx(9.0)
    assert _read("attention_ms_per_step", run) == pytest.approx(2.0)
    # a trace joined to no program text, or with no op in the scope
    for scopes in ({}, {"scopes": {"while": 0.1}}):
        run = _traced_run(0.004, steps, **scopes)
        assert _read("base_gemm_ms_per_step", run) is None
        assert _read("attention_ms_per_step", run) is None


def test_sign_kernel_readers_from_the_trace():
    steps = [(T0 + 1, 0.1, {"v0": 2, "__base__": 1}, [5, 5, 5]),
             (T0 + 2, 0.1, {"__base__": 3}, [6, 6, 6])]
    run = _traced_run(0.004, steps)
    assert _read("sign_gemm_ms_per_step", run) == pytest.approx(2.0)
    assert _read("idle_share", run) == pytest.approx(25.0)
    flops, byts = work.sign_gemm_decode(CONF, {"v0": 2})
    least = work.roofline_seconds(flops, byts, PEAK)[0]
    want = 100 * least / 2 * 2 / 0.004
    assert _read("sign_gemm_roofline.decode", run) == pytest.approx(want)


def test_roofline_has_nothing_to_read_on_base_only_steps():
    steps = [(T0 + 1, 0.1, {"__base__": 3}, [5, 5, 5])]
    assert _read("sign_gemm_roofline.decode",
                 _traced_run(0.004, steps)) is None


@pytest.mark.parametrize("value,ok", [(0.5, True), (1.0, True),
                                      (1.5, False), (None, False)])
def test_verdict_needs_every_reading_within_its_limit(value, ok):
    from bench import check
    checks = {"max_logit_gap": {"value": value, "limit": 1.0},
              "failed_requests": {"value": 0, "limit": 0}}
    assert check.verdict(checks) is ok
