"""Summarise a ``prove_cell.sh`` output directory.

    python3 bench/tools/spread.py <out dir>

Per end-to-end metric and set (A, B): the median and the spread (the
distance between the first and third quartiles of
``statistics.quantiles(values, n=4)``, over the median), also with the run
farthest from the median left out; then every run's ``correct``, its
compared numbers, its peak memory and, for traced runs, busy and window
seconds and the per-layer metrics.
"""
import glob
import json
import os
import statistics
import sys


def spread(vals):
    q = statistics.quantiles(vals, n=4)
    return (q[2] - q[0]) / statistics.median(vals)


def trimmed(vals):
    m = statistics.median(vals)
    far = max(range(len(vals)), key=lambda i: abs(vals[i] - m))
    return spread([x for i, x in enumerate(vals) if i != far])


def main(d):
    runs = {}
    for f in sorted(glob.glob(os.path.join(d, "*.out"))):
        name = os.path.basename(f)[:-4]
        lines = [ln for ln in open(f).read().splitlines()
                 if ln.startswith("{") and '"correct"' in ln]
        if lines:
            runs[name] = json.loads(lines[-1])
        elif name[0] in "ABTCX":
            print("NO RESULT", name)
    for group in "AB":
        g = [r for k, r in runs.items() if k.startswith(group + "_")]
        for m in sorted({m for r in g for m in r["metrics"]}):
            vals = [r["metrics"][m]["value"] for r in g if m in r["metrics"]]
            if len(vals) >= 3:
                print(f"set {group} {m}: n={len(vals)} median="
                      f"{statistics.median(vals)!r} spread={spread(vals):.4f}"
                      f" trimmed={trimmed(vals):.4f} values={vals}")
    for k, r in runs.items():
        checks = {n: v["value"] for n, v in r["checks"].items()}
        dev = r["device"]
        extra = ({m: v["value"] for m, v in r["metrics"].items()}
                 if "busy_s" in dev else "")
        print(k, "correct", r["correct"], "attempted", r["attempted"],
              "failed", r["failed"], checks, "memory",
              dev["memory_peak_bytes"], dev.get("busy_s"),
              dev.get("window_s"), extra)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
