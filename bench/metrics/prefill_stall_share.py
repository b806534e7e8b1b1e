"""Share of the window the scheduler spent in prefill-on-admit: the
engine's ``prefill_seconds`` counter over the window, in percent."""
from bench.readers import delta


def read(run):
    return 100.0 * delta(run, "prefill_seconds") / run.seconds
