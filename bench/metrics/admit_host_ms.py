"""Host time of one admission on the engine thread: the mean duration of
the program's ``ficabu.engine.admit`` spans in the trace
(``spans.admit_host_ms``).  Moves ``ttft_p95_ms``."""
import spans


def read(run):
    pt = run.program_trace
    return None if pt is None else spans.admit_host_ms(pt)
