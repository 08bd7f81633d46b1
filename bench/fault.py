#!/usr/bin/env python3
"""Planted faults of a cell's timed path, at the cell's own size.

    python3 bench/fault.py --workload <cell> --seeds 1 2 3 --seconds 30 \\
        --set search.pool_size=256

Each seed runs as ``bench/run.py --trace 0`` would, on the chip, with the
configuration's settings replaced as ``--set`` says (a traversal cut short,
a smaller rerank pool), and prints the compared numbers beside their
limits. A fault that the comparison must catch has to come out as not
correct; its readings are the upper readings from which ``checks`` limits
are set (``PERF.md``). The benchmark's own runs never plant one.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness.runner import NoChip, run_cell  # noqa: E402
from harness.spec import Spec, find_root  # noqa: E402


def setting(text: str) -> tuple:
    key, _, value = text.partition("=")
    return key, json.loads(value)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--set", type=setting, action="append", required=True,
                    metavar="GROUP.KEY=JSON", dest="settings")
    args = ap.parse_args(argv)
    spec = Spec(find_root())
    for seed in args.seeds:
        try:
            out = run_cell(spec, args.workload, seed, args.seconds, False,
                           t_start=time.perf_counter(),
                           settings=dict(args.settings),
                           say=lambda s: print(s, flush=True))
        except NoChip as e:
            print(f"no chip: {e}", file=sys.stderr)
            return 2
        print(f"fault {dict(args.settings)} {args.workload} seed {seed}: "
              + json.dumps({"correct": out["correct"],
                            "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
