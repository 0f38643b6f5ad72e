"""Device time of one decode step: the ``jit__step`` program (the engine's
one decode+argmax+scatter program), summed over its launches in the trace,
per launch.  Moves ``tpot_mean_ms``."""
PROGRAM = "jit__step"


def read(run):
    got = run.program(PROGRAM)
    if got is None:
        return None
    seconds, launches = got
    return seconds / launches * 1e3
