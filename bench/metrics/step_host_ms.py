"""Host time of one decode step on the engine thread: the median self time
of the program's ``ficabu.engine.step`` spans less their ``engine.admit``
and ``engine.publish`` children, over the traced steps
(``spans.step_host_ms``).  Moves ``tpot_mean_ms``."""
import spans


def read(run):
    pt = run.program_trace
    return None if pt is None else spans.step_host_ms(pt)
