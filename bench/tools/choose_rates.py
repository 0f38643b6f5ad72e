"""The rules that turn calibration phases (``calibrate.py`` output) into a
cell's rates.

    choose_rates.py drain <cal.out>             -> forget rate
    choose_rates.py knee <cal.out> <forget> <cell> -> generate pool seconds
    choose_rates.py write <cell> <generate> <forget> <pool> <out dir>

Forget: 0.6 of the drain capacity (one over the median host time of a
sweep), to one decimal; the mix spaces forget requests evenly, so a drain
seldom waits for the one before it.  Knee: the
highest generate rate at which every request finished and the second
half's median time to first token is within twice the first half's plus
0.1 s (a backlog that grows fails this); the cell runs at 4/5 of it.  Pool:
the cell's own width, at which the knee was found (a narrower pool queues
requests for slots at the same rate).  Seconds: 51, the longest a check
allows (five beyond a p90 needs 50 forget requests, beyond a p95 100
generate requests).
"""
import json
import sys


def rows(path):
    return [json.loads(line) for line in open(path) if line.startswith("{")]


def sustained(r):
    if (r["generate_done"] < r["generate_due"]
            or r["forget_done"] < r["forget_due"]):
        return False
    a = r.get("ttft_p50_first_half_ms")
    b = r.get("ttft_p50_second_half_ms")
    return a is not None and b is not None and b <= 2 * a + 100


def main(argv):
    mode = argv[0]
    if mode == "drain":
        sp = sorted(r["sweep_host_median_s"] for r in rows(argv[1])
                    if "sweep_host_median_s" in r)
        d = sp[len(sp) // 2]
        f = round(0.6 / d, 1)
        print(f)
        print(f"drain median {d:.4f} s -> forget {f:.3f}/s", file=sys.stderr)
    elif mode == "knee":
        rs, f = rows(argv[1]), float(argv[2])
        for r in rs:
            print(("OK " if sustained(r) else "NO ") + json.dumps(r),
                  file=sys.stderr)
        ok = [r["generate_rate"] for r in rs if sustained(r)]
        knee = max(ok) if ok else min(r["generate_rate"] for r in rs) / 2
        g = round(0.8 * knee, 2)
        with open(f"bench/workloads/{argv[3]}.json") as fh:
            pool = json.load(fh)["pool_width"]
        secs = 51
        print(g, pool, secs)
        print(f"knee {knee}/s -> generate {g}/s, pool {pool}, seconds "
              f"{secs}", file=sys.stderr)
    elif mode == "write":
        w, g, f, pool, out = argv[1:6]
        p = f"bench/workloads/{w}.json"
        with open(p) as fh:
            d = json.load(fh)
        d.update(generate_rate=float(g), forget_rate=float(f),
                 pool_width=int(pool))
        text = json.dumps(d, indent=2) + "\n"
        for path in (p, f"{out}/{w}.json"):
            with open(path, "w") as fh:
                fh.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
