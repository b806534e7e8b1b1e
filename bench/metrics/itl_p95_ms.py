"""Gap between consecutive tokens of one request, 95th percentile over
every gap of every request inside the window (a gap that spans a
prefill-on-admit stall counts), taken over the whole window at once."""
import numpy as np

from bench.readers import percentile, stamps_in_window


def read(run):
    gaps = [g for times in stamps_in_window(run)
            for g in np.diff(times)]
    return 1e3 * percentile(gaps, 95) if gaps else None
