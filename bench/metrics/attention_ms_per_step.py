"""Device time of attention (the ops in the ``attention`` name scope of
``decode_attention``) inside the decode-step program, per decode program
traced.  An op's name scope comes from the program's HLO text
(``trace.reduce``)."""
from bench.readers import scope_ms_per_step


def read(run):
    return scope_ms_per_step(run, "decode_banked_fn", "attention")
