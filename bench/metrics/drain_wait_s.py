"""Median host time from a forget request's due time to the start of its
sweep on the engine's worker thread (queueing in the client, the
scheduler and the worker's queue), over the window's forget requests.
Moves ``forget_p90_s``."""
import stats


def read(run):
    w = run.window
    spans = run.drain_spans[run.n_warm_drains:]
    waits = [s[0] - due for due, s in zip(w.forget_due, spans)]
    if not waits:
        return None
    return stats.median(waits)
