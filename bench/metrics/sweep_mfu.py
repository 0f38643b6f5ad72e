"""Share of the chip's bf16 peak that a drain's sweep program reaches: the
operations it needs (``flops.drain_flops``: one forward over the forget set
and the backward of every layer, as a sweep that halts nowhere does) over
its mean device time per launch times the peak.  Checkpoint forwards and
Fisher squares are not counted.  Moves ``forget_p90_s``."""
PROGRAM = "jit_sweep"


def read(run):
    got = run.program(PROGRAM)
    if got is None:
        return None
    c = run.cell
    if float(c["tau"]) >= 0:
        return None      # a halting sweep's depth is not known here
    need = run.flops.drain_flops(run.cfg, c["forget_set"], c["forget_len"],
                                 run.cfg["num_hidden_layers"])
    return 100.0 * need / (got[0] / got[1] * run.peak["bf16_flops_per_s"])
