"""Time the engine thread blocks joining an unfinished drain at its
publication deadline, per publication, from the engine's counters over the
window (``publish_wait_s`` / ``publications``; ``spans.publish_wait_ms``).
Moves ``itl_p999_ms``."""
import spans


def read(run):
    return spans.publish_wait_ms(run.counters)
