"""The traced run's instrumentation, from the benchmark's side of the calls.

``EngineProbe`` wraps one engine's ``search`` and ``plan`` on the instance,
so the serving stack calls the wrappers without a change to the program:

  * each call runs inside a ``jax.profiler.TraceAnnotation`` (``SEARCH`` or
    ``PLAN``), so the trace's host spans say what the host did while the
    device sat idle;
  * each ``search`` waits for its result and records its batch rows and
    the full-precision evaluations the program counts per row
    (``SearchResult.n_dist_evals``).

The batcher waits for every result right after ``search`` anyway, so the
wait moves no work; reading the counters costs one small device-to-host
copy per batch. Only ``--trace 1`` runs install it.
"""
from __future__ import annotations

import numpy as np

SEARCH = "bench.engine.search"
PLAN = "bench.engine.plan"


class EngineProbe:
    def __init__(self, engine):
        import jax

        self.engine = engine
        self.batches: list[dict] = []
        search, plan = engine.search, engine.plan

        def traced_search(queries, params=None, *a, **kw):
            rows = queries.batch_size
            with jax.profiler.TraceAnnotation(SEARCH, rows=rows):
                res = search(queries, params, *a, **kw)
                jax.block_until_ready(res.ids)
            self.batches.append({
                "rows": rows,
                "dist_evals": int(np.sum(np.asarray(res.n_dist_evals))),
            })
            return res

        def traced_plan(*a, **kw):
            with jax.profiler.TraceAnnotation(PLAN):
                return plan(*a, **kw)

        engine.search = traced_search
        engine.plan = traced_plan

    def uninstall(self) -> None:
        for name in ("search", "plan"):
            self.engine.__dict__.pop(name, None)
