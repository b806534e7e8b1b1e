"""The serving engine's own host time per decode step: its
``host_seconds`` counter (each continuous round's wall time less its waits
on the device, the step hook and admission sleeps) over the window's
decode steps."""
from bench.readers import delta


def read(run):
    steps = delta(run, "decode_steps")
    return 1e3 * delta(run, "host_seconds") / steps if steps else None
