"""Device time of the sign-delta GEMM (the Pallas custom calls inside the
decode-step program) per traced decode step."""

DECODE = "decode_banked_fn"


def read(run):
    mod = (run.trace or {}).get("modules", {}).get(DECODE)
    if not mod or not mod["count"] or not mod["custom_call_s"]:
        return None
    return 1e3 * mod["custom_call_s"] / mod["count"]
