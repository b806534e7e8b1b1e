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


DECODE_HLO = """HloModule jit_decode_banked_fn, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[6,4096]) -> bf16[6,4096] {
  ROOT %dot.4 = bf16[6,4096]{1,0} dot(%param_0.1), metadata={op_name="jit(decode_banked_fn)/while/body/base_gemm/dot_general" stack_frame_id=6}
}

ENTRY %main.9 (p: bf16[6,4096]) -> bf16[6,4096] {
  %fusion.1 = bf16[6,4096]{1,0} fusion(%p), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(decode_banked_fn)/while/body/closed_call/base_gemm/dot_general" stack_frame_id=6}, backend_config={"flag_configs":[]}
  %custom-call.3 = f32[6,4096]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_banked_fn)/while/body/attention/attention/while/body/dot_general"}
  ROOT %fusion.2 = bf16[6,4096]{1,0} fusion(%custom-call.3), kind=kLoop, calls=%fused_computation.1
}
"""


def _wave_hlo(rows):
    return ("HloModule jit_prefill_banked_fn, is_scheduled=true\n\n"
            f"ENTRY %main (p: bf16[{rows},256]) -> bf16[{rows},256] {{\n"
            f"  ROOT %custom-call.3 = bf16[{rows},256]{{1,0}} custom-call(%p)"
            ', metadata={op_name="jit(prefill_banked_fn)/base_gemm/x"}\n}\n')


HLO = {"decode_banked": DECODE_HLO, "prefill_banked/r1": _wave_hlo(1),
       "prefill_banked": _wave_hlo(6)}


def test_name_stacks_from_hlo_text():
    stacks = TR.name_stacks(DECODE_HLO)
    # an instruction of a fused computation is not an op of the trace,
    # but its name is unique in the module all the same
    assert set(stacks) == {"dot.4", "fusion.1", "custom-call.3"}
    assert "base_gemm" in stacks["fusion.1"]


def test_scopes_join_ops_to_their_instructions():
    mods = TR.reduce(synthetic(), HLO)["modules"]
    dec = mods["decode_banked_fn"]["scopes"]
    assert dec["base_gemm"] == pytest.approx(0.004)       # fusion.1
    assert dec["attention"] == pytest.approx(0.005)       # custom-call.3
    # fusion.2 (9-10 ms) has no op_name: no segment gets its time; the
    # 1 ms in which fusion.1 and custom-call.3 overlap counts once
    assert dec["jit(decode_banked_fn)"] == pytest.approx(0.008)
    # the wave buckets share one module name: nothing tells their ops
    # apart in the trace, so the waves get no scopes
    assert "scopes" not in mods["prefill_banked_fn"]
    assert "scopes" not in TR.reduce(synthetic())["modules"][
        "decode_banked_fn"]


def test_an_op_with_a_repeated_segment_is_credited_once():
    dec = TR.reduce(synthetic(), HLO)["modules"]["decode_banked_fn"]
    # custom-call.3's stack holds "attention", "while" and "body" twice
    assert dec["scopes"]["attention"] == pytest.approx(0.005)
    assert dec["scopes"]["while"] == pytest.approx(0.008)


def test_an_op_nested_in_another_of_its_scope_adds_no_time():
    ms = 1e6
    events = synthetic() + [
        # a loop of the attention scope around its body's op (the
        # custom call at 3-8 ms): only 2 ms of the loop are its own
        ev(TR.OPS_LINE, "%while.7 = (s32[]) while(%p)", 2 * ms, 8.5 * ms)]
    hlo = dict(HLO, decode_banked=DECODE_HLO.replace(
        "  ROOT %fusion.2",
        '  %while.7 = (s32[]) while(%p), metadata={op_name="jit(decode_banke'
        'd_fn)/while/body/attention/attention/while"}\n  ROOT %fusion.2'))
    dec = TR.reduce(events, hlo)["modules"]["decode_banked_fn"]["scopes"]
    assert dec["attention"] == pytest.approx(0.0065)      # 2 - 8.5 ms
    assert dec["base_gemm"] == pytest.approx(0.004)
    assert dec["jit(decode_banked_fn)"] == pytest.approx(0.0085)


def test_the_scopes_of_a_compiled_program():
    import jax
    import jax.numpy as jnp

    def f(x, w):
        with jax.named_scope("base_gemm"):
            y = jax.lax.optimization_barrier(x @ w)
        with jax.named_scope("attention"):
            return jax.nn.softmax(y, axis=-1)
    x = jnp.ones((8, 16), jnp.float32)
    text = jax.jit(f).lower(x, x.T).compile().as_text()
    segments = set().union(*TR.name_stacks(text).values())
    assert {"base_gemm", "attention"} <= segments


def test_a_gap_is_named_by_the_span_with_most_self_time_in_it():
    ms = 1e6
    events = synthetic() + [
        ev("python", "serve.step", 45 * ms, 110 * ms, plane="/host:CPU"),
        ev("python", "serve.emit", 60 * ms, 90 * ms, plane="/host:CPU"),
        ev("python", "serve.admit", 92 * ms, 95 * ms, plane="/host:CPU")]
    gaps = TR.reduce(events)["idle_gaps"]
    # in 50-100 ms serve.step covers all, serve.emit 30 ms of it, and
    # serve.step's own time is 17 ms: the gap is serve.emit's
    assert gaps[0] == ["serve.emit", pytest.approx(0.050)]
    events[-2]["end"] = 70 * ms
    assert TR.reduce(events)["idle_gaps"][0][0] == "serve.step"


def test_load_keeps_engine_spans_with_their_stats(tmp_path):
    import glob

    import jax
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("serve.prefill", rows=1, bucket=1):
        with jax.profiler.TraceAnnotation("other.span"):
            jax.numpy.ones(3).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = TR.load(path[0])
    names = {e["name"] for e in events}
    assert "serve.prefill" in names and "other.span" not in names
    span = next(e for e in events if e["name"] == "serve.prefill")
    assert span["stats"]["rows"] == 1 and span["stats"]["bucket"] == 1
