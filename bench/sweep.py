"""Find a cell's knee: the highest offered rate the server sustains.

    python3 -m bench.sweep --workload <cell> --seed <n> --seconds <s> \\
        [--rates 10,20,40 | --start 10]

One set-up, then one open-loop window per rate (each with its own
seed), on the cell's configuration and mix with only the rate changed.
A rate is sustained when the backlog does not grow over the window:
at the close no more than two batches of requests are still unanswered,
and the last quarter's median latency is under twice the first
quarter's.  Without ``--rates`` the sweep starts at ``--start``, grows
the rate by half until a rate is not sustained, then bisects twice.
One JSON line per rate; the last line names the knee.  Refuses to run
without a TPU, like ``bench.run``.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from bench import run


def _window(dep, mix: dict, seconds: float, seed: int) -> dict:
    from bench import window

    due, _, rows = dep.traffic(mix, seconds, seed)
    dep.server.start()
    try:
        rec = window.drive(dep.server, rows, due, seconds)
    finally:
        dep.server.stop()
    e2e = window.end_to_end(rec)
    close = rec.start + rec.seconds
    behind = int(np.sum(~(rec.done_at <= close)))
    q = max(len(due) // 4, 1)
    first = float(np.median(rec.latency[:q]))
    last = float(np.median(rec.latency[-q:]))
    batch = int(dep.config["batch_size"])
    return {"rate_qps": mix["rate_qps"], "requests": len(due),
            "unanswered_at_close": behind, **e2e,
            "first_quarter_p50_ms": first * 1e3,
            "last_quarter_p50_ms": last * 1e3,
            "sustained": bool(behind <= 2 * batch and last < 2 * first)}


def sweep(dep, mix: dict, seconds: float, seed: int, rates=None,
          start: float = 10.0) -> dict:
    rows = []

    def at(rate):
        r = _window(dep, dict(mix, rate_qps=float(rate)), seconds,
                    seed + len(rows))
        rows.append(r)
        run.say(phase="sweep", **r)
        return r["sustained"]

    if rates:
        for r in rates:
            at(r)
    else:
        lo, hi = None, float(start)
        while at(hi):
            lo, hi = hi, hi * 1.5
        for _ in range(2):
            if lo is None:
                lo, hi = 0.0, hi
            mid = (lo + hi) / 2
            lo, hi = (mid, hi) if at(mid) else (lo, mid)
    ok = [r["rate_qps"] for r in rows if r["sustained"]]
    return {"knee_qps": max(ok) if ok else None,
            "rate_at_0.8_knee": 0.8 * max(ok) if ok else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--start", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    try:
        bench = run.load_benchmark()
        cell, _, config, mix = run.resolve_cell(bench, args.workload)
        peaks = json.loads((run.BENCH / "peaks.json").read_text())
        device = run.device_info(int(cell["chips"]), peaks)
    except run.Refused as e:
        print(f"sweep: refused: {e}", file=sys.stderr)
        return 2
    dep = run.Deployment(config, mix, args.seed, traced=False)
    rates = [float(r) for r in args.rates.split(",") if r]
    out = sweep(dep, mix, args.seconds, args.seed, rates, args.start)
    print(json.dumps({"workload": args.workload, "device": device, **out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
