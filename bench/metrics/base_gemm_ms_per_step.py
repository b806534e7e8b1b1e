"""Device time of the base GEMM (the ops in the ``base_gemm`` name scope
of ``ops.base_matmul``: every linear's int8 matmul and scale) inside the
decode-step program, per decode program traced.  An op's name scope comes
from the program's HLO text (``trace.reduce``)."""
from bench.readers import scope_ms_per_step


def read(run):
    return scope_ms_per_step(run, "decode_banked_fn", "base_gemm")
