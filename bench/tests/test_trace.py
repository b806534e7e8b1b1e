"""The trace reduction: busy union, idle share, module and kernel time."""
import pytest

from bench import trace as TR

DEV = "/device:TPU:0"


def ev(line, name, start, end, plane=DEV, **stats):
    return {"plane": plane, "line": line, "name": name, "start": start,
            "end": end, "stats": stats}


def synthetic():
    ms = 1e6
    return [
        ev(TR.MODULES_LINE, "jit_decode_banked_fn(7)", 0, 10 * ms),
        ev(TR.OPS_LINE, "fusion.1", 0, 4 * ms),
        ev(TR.OPS_LINE, "custom-call.3", 3 * ms, 8 * ms),   # overlaps
        ev(TR.OPS_LINE, "fusion.2", 9 * ms, 10 * ms),
        ev(TR.MODULES_LINE, "jit_prefill_banked_fn(9)", 20 * ms, 50 * ms),
        ev(TR.OPS_LINE, "custom-call.3", 20 * ms, 30 * ms,
           hlo_module="jit_prefill_banked_fn"),
        ev(TR.OPS_LINE, "fusion.9", 38 * ms, 50 * ms),
        ev("python", "bench.sleep", 10.5 * ms, 19 * ms, plane="/host:CPU"),
        ev("python", TR.WINDOW_SPAN, 0, 100 * ms, plane="/host:CPU"),
    ]


def test_busy_union_and_idle_share():
    red = TR.reduce(synthetic())
    # ops cover [0, 8] + [9, 10] + [20, 30] + [38, 50] ms = 31 ms
    assert red["busy_s"] == pytest.approx(0.031)
    assert red["idle_share"] == pytest.approx(1 - 0.031 / 0.1)
    assert red["devices"] == 1


def test_custom_call_time_split_by_module():
    mods = TR.reduce(synthetic())["modules"]
    dec, pre = mods["decode_banked_fn"], mods["prefill_banked_fn"]
    assert dec["count"] == 1 and pre["count"] == 1
    assert dec["device_s"] == pytest.approx(0.010)
    assert dec["custom_call_s"] == pytest.approx(0.005)
    assert pre["custom_call_s"] == pytest.approx(0.010)


def test_breakdown_top_ops_and_gaps_with_host_cause():
    red = TR.reduce(synthetic())
    names = [n for n, _ in red["device_ops"]]
    # an op is named with the program it ran in
    assert names[:2] == ["prefill_banked_fn/fusion.9",
                         "prefill_banked_fn/custom-call.3"]
    assert red["device_ops"][0][1] == pytest.approx(0.012)
    assert dict(red["device_ops"])["decode_banked_fn/custom-call.3"] == \
        pytest.approx(0.005)
    gaps = red["idle_gaps"]
    assert gaps[0] == ["engine host", pytest.approx(0.050)]     # 50-100 ms
    assert gaps[1] == ["bench.sleep", pytest.approx(0.010)]     # 10-20 ms
    assert gaps[2] == ["engine host", pytest.approx(0.008)]     # 30-38 ms
    assert len(gaps) <= 10


def test_device_time_is_clipped_to_the_traced_window():
    ms = 1e6
    events = synthetic()
    for e in events:
        if e["name"] == TR.WINDOW_SPAN:
            e["start"], e["end"] = 5 * ms, 45 * ms
    red = TR.reduce(events)
    # ops inside [5, 45] ms: [5, 8] + [9, 10] + [20, 30] + [38, 45]
    assert red["window_s"] == pytest.approx(0.040)
    assert red["busy_s"] == pytest.approx(0.021)
    assert red["idle_share"] == pytest.approx(1 - 0.021 / 0.040)
    assert red["modules"]["prefill_banked_fn"]["device_s"] == \
        pytest.approx(0.025)


def test_a_trace_without_its_window_span_is_refused():
    events = [e for e in synthetic() if e["name"] != TR.WINDOW_SPAN]
    with pytest.raises(ValueError, match="bench.traced"):
        TR.reduce(events)


def test_custom_calls_and_op_names_from_hlo_text():
    kernel = ev(TR.OPS_LINE, "%sign_delta_local.12 = f32[6,11008]{1,0} "
                "custom-call(bf16[6,4096]{1,0} %p.1)", 0, 1)
    fusion = ev(TR.OPS_LINE, "%fusion.3 = f32[6,11008]{1,0} "
                "fusion(f32[6,11008]{1,0} %custom-call.3)", 0, 1)
    assert TR.is_custom_call(kernel) and not TR.is_custom_call(fusion)
    assert TR.op_name(kernel["name"]) == "%sign_delta_local.12"
    assert TR.op_name("custom-call.3") == "custom-call.3"
