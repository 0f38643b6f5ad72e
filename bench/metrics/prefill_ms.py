"""Device time of one admission: its prefill blocks (``jit_prefill_block``,
prompt_len / block launches) and its scatter into the slot pool
(``jit__admit``), summed over the trace, per admission.  Moves
``ttft_p95_ms``."""


def read(run):
    admit = run.program("jit__admit")
    pre = run.program("jit_prefill_block")
    if admit is None or pre is None:
        return None
    return (admit[0] + pre[0]) / admit[1] * 1e3
