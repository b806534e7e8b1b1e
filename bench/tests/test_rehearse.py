"""The memory rehearsal models the programs the continuous scheduler runs:
one admission wave per row bucket beside the decode step, sized through
``work.params_bytes_summary``.  On the CPU at a tiny size."""
import jax
from jax.sharding import SingleDeviceSharding

from bench import rehearse, work
from bench.tests.test_harness import CELL, tiny_cell


def test_wave_buckets_are_the_engines():
    assert rehearse.wave_buckets(6) == [1, 2, 4, 6]
    assert rehearse.wave_buckets(4) == [1, 2, 4]
    assert rehearse.wave_buckets(1) == [1]


def test_a_wave_holds_the_old_cache_the_new_cache_and_its_rows():
    conf = tiny_cell(CELL, "deepseek-7b.int8", "mixed.open").config
    d = conf["deployment"]
    out = rehearse.rehearse(conf, SingleDeviceSharding(jax.devices()[0]))
    plan = work.params_bytes_summary(conf, d["bank_size"],
                                     d["lanes"] * d["max_len"])
    assert out["plan"] == plan
    assert set(out["peak_estimate"]) == {
        "decode_banked", "prefill_banked/r1", "prefill_banked/r2",
        "prefill_banked"}
    cache = d["lanes"] * d["max_len"] * plan["kv_per_token"]
    for kind, peak in out["peak_estimate"].items():
        ma = out[kind]
        # base, bank and the old cache come in; the new cache goes out
        assert ma["argument_size_in_bytes"] >= plan["total"]
        assert ma["output_size_in_bytes"] >= cache
        assert peak == (plan["total"] + ma["output_size_in_bytes"]
                        + ma["temp_size_in_bytes"])
    # a wider wave prefills more rows
    assert (out["prefill_banked/r1"]["temp_size_in_bytes"]
            < out["prefill_banked/r2"]["temp_size_in_bytes"])
