"""The program's spans in a traced run (harness.spans): the clock join to
the profiler's trace on the CPU, and the five readers built on it, each on
a synthetic run; none of them raises where the program keeps no ring."""
import shutil
import time
import types

import pytest

from conftest import REPO
from harness import instrument, spans, trace
from harness.spec import Spec

MS = 1_000_000  # ns
OFF = 5_000_000_000  # the synthetic trace's clock minus perf_counter_ns
T0 = 1_000_000_000  # the window opens at 1 s on perf_counter_ns
NEW = ("inbox_ms_p99.open", "host_bound_idle_share.open",
       "dispatch_idle_ms_per_batch.open", "gc_pause_ms.open",
       "gc_pause_ms.closed")


def _ring(records, capacity=1 << 10):
    from repro.obs.trace import Recorder

    ring = Recorder(capacity=capacity)
    for name, tid, a, b in records:
        ring.record(name, T0 + a * MS, T0 + b * MS, tid=tid)
    return ring


# two batches on the worker (thread 1), a client thread (2) collecting
WORKER, CLIENT = 1, 2
RECORDS = [
    ("serve.idle", WORKER, 0, 10),
    ("serve.inbox", WORKER, 5, 10),
    ("serve.flush", WORKER, 10, 40),
    ("engine.search", WORKER, 12, 20),
    ("engine.dispatch", WORKER, 14, 20),
    ("engine.wait", WORKER, 20, 38),
    ("serve.idle", WORKER, 40, 50),
    ("serve.flush", WORKER, 50, 80),
    ("engine.search", WORKER, 52, 60),
    ("engine.dispatch", WORKER, 54, 60),
    ("engine.wait", WORKER, 60, 78),
    ("serve.idle", WORKER, 80, 100),
    ("gc", CLIENT, -1, 1),  # half of it before the window opens
    ("gc", CLIENT, 85, 87),
]


def _summary():
    at = lambda ms: T0 + OFF + ms * MS  # noqa: E731
    host = [trace.Span(instrument.SEARCH, at(12), at(38)),
            trace.Span(instrument.SEARCH, at(52), at(78))]
    busy = [[(at(16), at(38)), (at(56), at(78))]]
    return trace.TraceSummary(window_s=0.1, chips=1, modules=[], ops=[],
                              host=host, busy=busy, lo=at(-5), hi=at(105))


def _view(summary, inbox=range(1, 101)):
    window = types.SimpleNamespace(t0=T0 / 1e9, drained_at=(T0 + 100 * MS)
                                   / 1e9, records=[])
    completed = [(None, types.SimpleNamespace(inbox_ms=float(v)))
                 for v in inbox]
    return types.SimpleNamespace(window=window, trace=summary,
                                 completed=completed)


@pytest.fixture
def with_ring(monkeypatch):
    def use(ring):
        monkeypatch.setattr(spans, "_ring",
                            lambda: None if ring is None else (lambda: ring))
    return use


def _read(name, view):
    return Spec(REPO).metric_reader(name).read(view)


def test_readers_on_a_synthetic_run(with_ring, capsys):
    with_ring(_ring(RECORDS))
    view = _view(_summary())
    # idle 16 + 18 + 22 ms; the worker waited for requests in 10 + 10 + 20
    assert _read("host_bound_idle_share.open", view) == pytest.approx(16.0)
    # chip idle under engine.dispatch: 2 ms in each of the two batches
    assert _read("dispatch_idle_ms_per_batch.open", view) == pytest.approx(2.0)
    assert _read("gc_pause_ms.open", view) == pytest.approx(3.0)
    assert _read("gc_pause_ms.closed", view) == pytest.approx(3.0)
    assert _read("inbox_ms_p99.open", view) == 99.0
    j = spans.joined(view)
    assert (j.offset, j.pairs, j.worker, j.worst_us) == (OFF, 2, WORKER, 0.0)
    assert j.label(j.lo + 38 * MS, j.lo + 56 * MS) == "serve.idle"
    assert j.label(j.lo + 14 * MS, j.lo + 16 * MS) == "engine.dispatch"
    said = capsys.readouterr().out
    assert "clock join: 2 pairs" in said and "longest idle gaps" in said


def test_readers_find_nothing_without_the_program_ring(with_ring):
    with_ring(None)  # a program that predates the ring
    view = _view(_summary())
    for name in NEW:
        assert _read(name, view) is None, name


def test_readers_refuse_a_window_the_ring_dropped(with_ring):
    with_ring(_ring(RECORDS, capacity=8))
    view = _view(_summary())
    for name in NEW:
        assert _read(name, view) is None, name


def test_readers_without_a_trace_or_inbox_field(with_ring):
    with_ring(_ring(RECORDS))
    view = _view(None)
    assert _read("host_bound_idle_share.open", view) is None
    assert _read("dispatch_idle_ms_per_batch.open", view) is None
    assert _read("gc_pause_ms.open", view) == pytest.approx(3.0)
    view = _view(_summary())
    view.completed = [(None, types.SimpleNamespace(queue_ms=1.0))]
    assert _read("inbox_ms_p99.open", view) is None


def test_clock_join_on_the_cpu_profiler(capsys):
    """A ThreadedServer run under the JAX profiler with the benchmark's
    engine probe: every program engine.search, shifted by the joined
    offset, lies inside its bench.engine.search annotation."""
    from repro.api import MATCH, Engine, Query, SearchParams
    from repro.data.synthetic import make_hybrid_dataset
    from repro.quant import QuantConfig
    from repro.serve import (
        Request, TenantPolicy, TenantRegistry, ThreadedServer,
    )

    ds = make_hybrid_dataset(n=1500, n_queries=40, profile="sift",
                             attr_dim=4, labels_per_dim=3, n_clusters=8,
                             attr_cluster_corr=0.6, seed=5)
    eng = Engine.build(ds.features, ds.attrs, build_graph=False,
                       quant_cfg=QuantConfig(mode="pq4", pq_subspaces=8,
                                             pq_train_iters=4))
    reg = TenantRegistry(default_policy=TenantPolicy(
        params=SearchParams(k=10, pool_size=64, backend="brute")))
    reqs = [Request("t", Query(ds.query_features[i],
                               [MATCH(int(x)) for x in ds.query_attrs[i]]))
            for i in range(40)]
    probe = instrument.EngineProbe(eng)
    prof = trace.Profiler()
    with ThreadedServer(eng, reg, window_ms=2.0, buckets=(1, 8)) as srv:
        srv.submit(reqs[0]).result()  # compile off the window
        prof.start()
        t0 = time.perf_counter()
        for f in [srv.submit(r) for r in reqs[1:]]:
            f.result()
        drained = time.perf_counter()
        prof.stop()
    probe.uninstall()
    view = types.SimpleNamespace(
        window=types.SimpleNamespace(t0=t0, drained_at=drained),
        trace=trace.reduce(prof.path, prof.window_s))
    shutil.rmtree(prof.dir, ignore_errors=True)
    j = spans.joined(view)
    assert j is not None and j.pairs >= 5
    assert j.worst_us <= 50.0 and j.spread_us <= 50.0
    anns = sorted((s.start, s.end) for s in view.trace.host
                  if s.name == instrument.SEARCH)
    ring = [r for r in j.records
            if r.name == spans.SEARCH and r.tid == j.worker]
    assert len(anns) == len(ring) == j.pairs
    for (a, b), r in zip(anns, ring):
        assert a - 50_000 <= r.t0_ns + j.offset
        assert r.t1_ns + j.offset <= b + 50_000
    assert "[spans]" in capsys.readouterr().out
