"""Sign-delta GEMM of the decode step against its roofline, in percent:
the least time its needed work takes on the chip (``work.py``: planes and
vectors of the variants the active lanes use, their rows of x and of the
output; base rows need none) over the kernel's device time.  Work is
averaged over the steps the trace covers and scaled to the decode
programs the trace holds.  Nothing to read when no traced step served a
variant."""
from bench import work
from bench.readers import traced_steps

DECODE = "decode_banked_fn"


def read(run):
    if run.peak is None:         # no published peak: not a chip
        return None
    mod = (run.trace or {}).get("modules", {}).get(DECODE)
    steps = traced_steps(run)
    if not mod or not mod["custom_call_s"] or not steps:
        return None
    least = 0.0
    for _, _, rows, _ in steps:
        variant_rows = {v: n for v, n in rows.items() if v != "__base__"}
        if variant_rows:
            flops, byts = work.sign_gemm_decode(run.conf, variant_rows)
            least += work.roofline_seconds(flops, byts, run.peak)[0]
    if least == 0.0:
        return None
    return 100.0 * least / len(steps) * mod["count"] / mod["custom_call_s"]
