#!/usr/bin/env python3
"""Benchmark command: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the TPU chips the cell
asks for. The run makes its corpus and traffic from ``--seed``, builds the
deployment through ``repro.api.Engine`` and warms every bucket (set-up),
drives ``repro.serve.ThreadedServer`` for ``--seconds``, compares every
answer due in the window with a float64 reference, and prints one JSON
object as the last line of standard output. ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` traces the window with the JAX
profiler and reports its per-layer metrics. Each compared number is printed
beside its limit as the last lines of standard error and under ``checks``,
the result's last key.

With no TPU, or fewer chips than the cell asks for, it prints no result
and exits 2. Compiled programs are kept where the program's
``repro.launch.compile_cache.enable_compile_cache`` keeps them:
``JAX_COMPILATION_CACHE_DIR`` when it is set, else ``<checkout>/.jax_cache``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness.runner import NoChip, run_cell  # noqa: E402
from harness.spec import Spec, find_root  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="a cell's name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = Spec(find_root())
    try:
        out = run_cell(spec, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START,
                       say=lambda s: print(s, flush=True))
    except NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
