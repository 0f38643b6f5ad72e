"""Device time of one drain's sweep program (``jit_sweep``: forward collect,
the back-to-front vjp/Fisher/dampen scan and its halt checkpoints), per
launch in the trace.  Moves ``forget_p90_s``."""
PROGRAM = "jit_sweep"


def read(run):
    got = run.program(PROGRAM)
    if got is None:
        return None
    return got[0] / got[1] * 1e3
