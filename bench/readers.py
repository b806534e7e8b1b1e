"""Shared arithmetic of the metric readers (``metrics/<name>.py``)."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile with linear interpolation (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def stamps_in_window(run) -> list:
    """Per request sent, its token times inside the window."""
    return [[t for t in s.handle.out_tokens.times
             if run.t0 <= t <= run.t_end] for s in run.sent]


def delta(run, key: str) -> float:
    return run.after[key] - run.before[key]


def traced_steps(run) -> list:
    """The benchmark's per-step records whose step ended inside the traced
    interval."""
    if run.trace is None:
        return []
    start, stop = run.trace["host_interval"]
    return [s for s in run.steps if start <= s[0] <= stop]


def scope_ms_per_step(run, module: str, scope: str):
    """Device milliseconds of the ops in name scope ``scope`` of program
    ``module``, per run of that program in the trace; None where the
    trace holds no such op."""
    mod = (run.trace or {}).get("modules", {}).get(module)
    seconds = (mod or {}).get("scopes", {}).get(scope)
    if not seconds or not mod["count"]:
        return None
    return 1e3 * seconds / mod["count"]
