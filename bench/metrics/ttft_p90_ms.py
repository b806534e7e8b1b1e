"""Time to first token, 90th percentile, over every request first due in
the window, timed from its due time on the open-loop schedule.  A request
with no first token by the window's end counts at its time so far."""
from bench.readers import percentile


def read(run):
    if not run.open_loop:
        return None
    waits = []
    for s in run.sent:
        if not run.t0 <= s.due < run.t_end:
            continue
        times = s.handle.out_tokens.times
        first = times[0] if times and times[0] <= run.t_end else run.t_end
        waits.append(first - s.due)
    return 1e3 * percentile(waits, 90) if waits else None
