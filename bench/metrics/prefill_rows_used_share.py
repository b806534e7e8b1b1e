"""Useful work of the admission waves, in percent: the rows they admitted
(``prefill_rows``) over the rows their row buckets computed
(``prefill_rows_computed``) in the window.  Nothing to read in a window
without a wave."""
from bench.readers import delta


def read(run):
    computed = delta(run, "prefill_rows_computed")
    return 100.0 * delta(run, "prefill_rows") / computed if computed else None
