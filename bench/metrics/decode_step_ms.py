"""Mean decode step: the engine's ``decode_seconds`` over its
``decode_steps`` in the window (host clock around ``block_until_ready``;
a window of many steps, so the clock's error is small)."""
from bench.readers import delta


def read(run):
    steps = delta(run, "decode_steps")
    return 1e3 * delta(run, "decode_seconds") / steps if steps else None
