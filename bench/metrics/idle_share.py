"""Share of the traced window in which no operation ran on the device
(1 minus the union of busy intervals over the window, averaged over the
chips).  Moves ``tpot_mean_ms``."""


def read(run):
    tr = run.trace
    lo, hi = run.window.trace_span
    if tr is None or lo is None or hi is None or not tr.devices:
        return None
    return 100.0 * (1.0 - tr.busy_s() / (hi - lo))
