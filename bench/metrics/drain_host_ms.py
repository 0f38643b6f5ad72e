"""Drain worker time per drain group outside the wait for the sweep's
outputs, from the forget service's counters over the window
(``(drain_s - sweep_wait_s) / groups``; ``spans.drain_host_ms``).  Moves
``forget_p90_s``."""
import spans


def read(run):
    return spans.drain_host_ms(run.counters)
