"""Share of the chip's bf16 peak that decode steps reach: the operations of
the rows occupied in each traced step (``flops.decode_step_flops``, each
row's newest token against its context), averaged over the traced steps
that decoded, over the mean device time of a ``jit__step`` launch times the
peak.  Moves ``tpot_mean_ms``."""
PROGRAM = "jit__step"


def read(run):
    got = run.program(PROGRAM)
    lo, hi = run.window.trace_steps
    if got is None or lo is None or hi is None:
        return None
    P, G = run.cell["prompt_len"], run.cell["output_len"]
    per_step = []
    admits = run.window.admit_step.values()
    for s in range(lo, hi):
        ctx = [P + (s - a) + 1 for a in admits if a <= s <= a + G - 2]
        if ctx:
            per_step.append(run.flops.decode_step_flops(run.cfg, ctx))
    if not per_step:
        return None
    seconds, launches = got
    mean_flops = sum(per_step) / len(per_step)
    return 100.0 * mean_flops / (seconds / launches
                                 * run.peak["bf16_flops_per_s"])
