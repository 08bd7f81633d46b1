#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: the highest offered rate that the
deployment sustains without a growing backlog.

    python3 bench/sweep.py --workload <open cell> --seed <n> \\
        --seconds 10 --rates 40 80 160 320

One process builds the cell's deployment once, then offers each rate in
turn (a Poisson schedule drawn as the cell's own generator draws it) for
``--seconds``, waits for every answer, and reports per rate: requests
answered per second inside the window, latency percentiles from due time,
the backlog (due but unanswered) over the window, and whether it was
sustained. A rate is sustained when nothing was shed and the backlog of the
window's last third exceeds that of its middle third by no more than
``GROWTH_S`` seconds of arrivals. After the given rates it bisects between
the highest sustained and the lowest unsustained one ``--refine`` times.
The last line is a JSON object with the table and ``knee_qps``.
"""
import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import corpus as corpus_mod  # noqa: E402
from harness import drive  # noqa: E402
from harness.runner import NoChip, set_up  # noqa: E402
from harness.spec import Spec, find_root  # noqa: E402

GROWTH_S = 0.25  # allowed backlog growth, in seconds of arrivals


def backlog(win: drive.Window, t: float) -> int:
    due = sum(1 for r in win.records if r.due <= t)
    done = sum(1 for r in win.records if r.done is not None and r.done <= t)
    return due - done


def step(dep, corpus, mix, gen, seed: int, rate: float, seconds: float):
    sched = gen.make_schedule(mix, corpus_mod.rng(seed, 1), seconds,
                              rate_qps=rate)
    win = drive.run_window(dep, corpus, sched, seconds)
    answered = [r for r in win.records
                if r.response is not None and r.response.ok]
    lat = np.sort([(r.done - r.due) * 1e3 for r in answered]) \
        if answered else np.zeros(1)
    marks = np.linspace(win.t0, win.t1, 13)[1:]
    bl = [backlog(win, t) for t in marks]
    middle, last = np.mean(bl[4:8]), np.mean(bl[8:])
    shed = len(win.records) - len(answered)
    ok = shed == 0 and last - middle <= GROWTH_S * rate
    return {
        "rate_qps": rate, "offered": len(win.records),
        "answered_in_window_qps":
            sum(1 for r in answered if r.done <= win.t1) / win.seconds,
        "p50_ms": float(np.percentile(lat, 50)),
        "p99_ms": float(lat[int(np.ceil(0.99 * len(lat))) - 1]),
        "backlog_middle": float(middle), "backlog_last": float(last),
        "shed_or_failed": shed, "batches": win.counters["batches"],
        "fill": win.counters["real_rows"] / max(win.counters["bucket_rows"], 1),
        "sustained": bool(ok),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--refine", type=int, default=3)
    args = ap.parse_args(argv)
    spec = Spec(find_root())
    cell = spec.cell(args.workload)
    mix = cell.traffic
    if mix["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    try:
        _, _, corpus, dep = set_up(spec, cell, args.seed,
                                   lambda s: print(s, flush=True))
    except NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 2
    gen = spec.generator(mix)
    rows = []

    def run(rate):
        row = step(dep, corpus, mix, gen, args.seed, rate, args.seconds)
        rows.append(row)
        print("sweep " + json.dumps(row), flush=True)
        return row["sustained"]

    for rate in sorted(args.rates):
        if not run(rate):
            break
    for _ in range(args.refine):
        good = [r["rate_qps"] for r in rows if r["sustained"]]
        bad = [r["rate_qps"] for r in rows if not r["sustained"]]
        above = [r for r in bad if good and r > max(good)]
        if not above:
            break
        run(round((max(good) + min(above)) / 2, 1))
    good = [r["rate_qps"] for r in rows if r["sustained"]]
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "seconds": args.seconds, "table": rows,
                      "knee_qps": max(good) if good else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
