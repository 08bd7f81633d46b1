#!/usr/bin/env python3
"""The control of a cell's comparison: the reference in the program's place,
in the precision below the one the configuration states.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 --requests 3000

For each seed it makes the cell's corpus and schedule as a run would, takes
the first ``--requests`` requests (as many as a run compares), answers every
one with the bfloat16 reference (features and queries rounded to bfloat16,
scored in float32) and puts those answers through the run's own comparison.
The control has to come out as not correct: the readings it prints are the
upper readings from which ``checks`` limits are set (``PERF.md``). It needs
no accelerator and builds no index.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import corpus as corpus_mod  # noqa: E402
from harness import reference  # noqa: E402
from harness.spec import Spec, find_root  # noqa: E402


def control_readings(spec: Spec, name: str, seed: int, requests: int,
                     seconds: float = 30.0) -> tuple:
    """(numbers, correct) of the control on one seed."""
    cell = spec.cell(name)
    cfg, mix = cell.config, cell.traffic
    corpus = corpus_mod.make_corpus(cfg["corpus"], mix["query_pool"], seed)
    sched = spec.generator(mix).make_schedule(
        mix, corpus_mod.rng(seed, 1), seconds)
    idx = sched.queries[:requests]
    k = cfg["search"]["k"]
    predicate = spec.predicate(mix)
    ans = reference.control_answers(idx, corpus.query_features,
                                    corpus.query_attrs, corpus.features,
                                    corpus.attrs, predicate, k)
    ref = reference.Reference(corpus.features, corpus.attrs, predicate)
    numbers = reference.compare(ans, ref, corpus.query_features,
                                corpus.query_attrs, k)
    correct, _ = reference.verdict(numbers, cfg["checks"])
    return numbers, correct


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, required=True)
    args = ap.parse_args(argv)
    spec = Spec(find_root())
    for seed in args.seeds:
        numbers, correct = control_readings(spec, args.workload, seed,
                                            args.requests)
        print(f"control {args.workload} seed {seed}: {numbers} "
              f"correct={correct}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
