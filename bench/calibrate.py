"""Find a cell's rates on the chip: the drain time and the knee.

    python3 bench/calibrate.py --workload <cell> --seed <n> \\
        --phase <generate_rate>:<forget_rate>:<seconds> [--phase ...] \\
        [--set pool_width=16]

One process, one set-up (the cell's own, ramp included); then one
open-loop window per ``--phase`` at the given rates, back to back on the
same warm deployment.  Each phase prints one JSON line: requests due and
finished, how long after the window the last one finished (a backlog that
grows shows as a long drain-out), the occupied pool slots (median over the
steps), the cell's end-to-end metrics as the harness computes them
(``harness.end_to_end``; a tail with too few samples beyond it is left
out), and the median host time of a sweep on the engine's worker.  Used
when a cell is defined, to fix the rates written into its workload file;
never part of a measured run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--phase", action="append", required=True)
    ap.add_argument("--grace", type=float, default=20.0)
    ap.add_argument("--set", action="append", default=[],
                    help="KEY=JSON: replace a key of the cell (pool_width)")
    args = ap.parse_args(argv)
    import harness
    import loop
    import stats
    from traffic import generator

    phases = [tuple(float(x) for x in p.split(":")) for p in args.phase]
    over = {k: json.loads(v) for k, v in (a.split("=", 1) for a in args.set)}
    st = harness.setup(args.workload, args.seed, 1.0, t_start=T_START,
                       overrides=over)
    srv, cm = st.srv, st.cm
    G = cm["output_len"]
    sid0 = 0
    for i, (g_rate, f_rate, secs) in enumerate(phases):
        cell = dict(cm, generate_rate=g_rate, forget_rate=f_rate)
        sched = generator.schedule(cell, args.seed + 1 + i, secs)
        prompts = st.prompts
        n_spans = len(srv.drain_spans)
        n_aborts = len(srv.aborts())
        win = loop.serve(srv, sched, prompts, seconds=secs, gen_len=G,
                         clock=st.clock, grace=args.grace, sid0=sid0)
        sid0 += len(sched["generate"]) + 1
        times = loop.request_times(win, G)
        ends = [t[-1] for t in times.values()]
        spans = [b - a for a, b in srv.drain_spans[n_spans:] if b]
        row = {"phase": i, "generate_rate": g_rate, "forget_rate": f_rate,
               "seconds": secs, "generate_due": len(win.gen_due),
               "generate_done": len(times), "forget_due": len(win.forget_due),
               "forget_done": sum(1 for v in win.forget_version
                                  if v in win.publish_time),
               "drain_out_s": (max(ends) - secs) if ends else None,
               "steps": win.last_step - win.first_step,
               "occupied_median": stats.median(win.occupied or [0]),
               "aborts": len(srv.aborts()) - n_aborts}
        for half, keep in (("first", lambda d: d < secs / 2),
                           ("second", lambda d: d >= secs / 2)):
            tt = [times[s][0] - win.gen_due[s] for s in times
                  if keep(win.gen_due[s])]
            if tt:     # a backlog that grows shows as a later half slower
                row[f"ttft_p50_{half}_half_ms"] = stats.median(tt) * 1e3
        row.update(harness.end_to_end(win, cell, 0.0))
        del row["setup_s"]
        if spans:
            row["sweep_host_median_s"] = stats.median(spans)
        print(json.dumps(row), flush=True)
        for a in srv.aborts()[n_aborts:][:3]:
            print(f"abort: {a.get('guard')} {str(a.get('detail'))[:300]}",
                  file=sys.stderr, flush=True)
    srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
