"""Share of the sweep program's device time spent in its halt checkpoints:
the leaf ops in the ``checkpoint`` named scope inside ``jit_sweep``
launches over those launches (``spans.sweep_checkpoint_pct``).  Moves
``forget_p90_s``."""
import spans


def read(run):
    pt = run.program_trace
    return None if pt is None else spans.sweep_checkpoint_pct(pt)
