"""Host cost of the serve path's spans (``repro.obs.trace``), with no
profiler session: one span, the spans of one request, and the spans of one
batch of the graph and of the brute pq4 path, each against the same loop
with no span.

    PYTHONPATH=src python -m benchmarks.span_cost [--reps N]

A request opens ``serve.idle`` and ``serve.enqueue`` and writes one
``serve.inbox`` record; a batch opens the nine spans of the flush (the
graph path) or those and the four ``brute.*`` stages (the brute path),
nested as the serve path nests them. Prints one JSON line of
microseconds, each the median of 7 repetitions: once as the process runs
(garbage collections included, the ones the spans' allocations bring on and
the ones they do not) and once with collection off (``*_nogc``), the cost
of the spans alone.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import time

from repro.obs import trace as obs_trace

span = obs_trace.span


def _none() -> None:
    pass


def _one_span() -> None:
    with span("engine.lookup"):
        pass


def _request() -> None:
    with span("serve.idle"):
        pass
    t = time.perf_counter_ns()
    obs_trace.recorder().record("serve.inbox", t, t)
    with span("serve.enqueue"):
        pass


def _batch(brute: bool):
    def run() -> None:
        with span("serve.flush"):
            with span("serve.assemble"):
                pass
            with span("engine.search"):
                with span("engine.plan"):
                    pass
                with span("engine.lookup"):
                    pass
                with span("engine.dispatch"):
                    if brute:
                        for stage in ("brute.lut", "brute.scan",
                                      "brute.select", "brute.rerank"):
                            with span(stage):
                                pass
            with span("engine.wait"):
                pass
            with span("serve.fetch"):
                pass
        with span("serve.resolve"):
            pass
    return run


def _median_us(fn, reps: int) -> float:
    runs = []
    for _ in range(7):
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        runs.append((time.perf_counter() - t0) / reps)
    return statistics.median(runs) * 1e6


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20000)
    args = ap.parse_args()
    obs_trace.install()
    out = {}
    for suffix in ("", "_nogc"):
        if suffix:
            gc.disable()
        base = _median_us(_none, args.reps)
        out["empty_call_us" + suffix] = base
        for name, fn in (("span", _one_span), ("request", _request),
                         ("graph_batch", _batch(False)),
                         ("brute_batch", _batch(True))):
            out[name + "_us" + suffix] = _median_us(fn, args.reps) - base
    gc.enable()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
