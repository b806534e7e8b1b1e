"""Share of the traced window in which no operation ran on the device,
in percent (1 minus the union of op intervals over the window)."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace["idle_share"]
