"""The serve path's own spans (repro.obs.trace): every phase recorded in the
process-wide ring on the worker's thread, the inbox wait carried out as
``Completed.inbox_ms``, garbage collections and XLA compiles recorded, the
ring bounded, the sampled tree still exact, and the counters exported."""
import gc
import threading
import time

import jax
import numpy as np
import pytest

from repro.api import MATCH, Engine, Query, SearchParams
from repro.core.help_graph import HelpConfig
from repro.data.synthetic import make_hybrid_dataset
from repro.obs import Tracer, chrome_trace, prometheus_text
from repro.obs import trace as obs_trace
from repro.quant import QuantConfig
from repro.serve import Request, TenantPolicy, TenantRegistry, ThreadedServer

PER_REQUEST = ("serve.inbox", "serve.enqueue")
PER_BATCH = ("serve.flush", "serve.assemble", "engine.search", "engine.plan",
             "engine.lookup", "engine.dispatch", "engine.wait", "serve.fetch",
             "serve.resolve")
BRUTE = ("brute.lut", "brute.scan", "brute.select", "brute.rerank")


@pytest.fixture(scope="module")
def ds():
    return make_hybrid_dataset(
        n=1500, n_queries=64, profile="sift", attr_dim=4, labels_per_dim=3,
        n_clusters=8, attr_cluster_corr=0.6, seed=3,
    )


@pytest.fixture(scope="module")
def engines(ds):
    return {
        "graph": (Engine.build(ds.features, ds.attrs, HelpConfig(
            gamma=12, gamma_new=4, max_rounds=3, quality_sample=64,
            node_block=512)), SearchParams(k=10, pool_size=32,
                                           pioneer_size=8, backend="graph")),
        "brute_pq4": (Engine.build(
            ds.features, ds.attrs, build_graph=False,
            quant_cfg=QuantConfig(mode="pq4", pq_subspaces=8,
                                  pq_train_iters=4)),
            SearchParams(k=10, pool_size=64, backend="brute")),
    }


def _requests(ds, n, offset=0):
    return [Request("t", Query(ds.query_features[i],
                               [MATCH(int(x)) for x in ds.query_attrs[i]]))
            for i in range(offset, offset + n)]


def _registry(params):
    return TenantRegistry(default_policy=TenantPolicy(params=params))


def _serve(engine, params, reqs, **kw):
    with ThreadedServer(engine, _registry(params), window_ms=2.0,
                        buckets=(1, 8), **kw) as srv:
        srv.submit(reqs[0]).result()  # compiles every shape used below
        t0 = time.perf_counter_ns()
        out = [f.result() for f in [srv.submit(r) for r in reqs[1:]]]
        worker = srv._thread.ident
        time.sleep(0.01)  # the worker's last serve.idle closes
    return out, worker, obs_trace.recorder().between(
        t0, time.perf_counter_ns())


@pytest.mark.parametrize("backend", ["graph", "brute_pq4"])
def test_every_phase_recorded_on_the_worker(ds, engines, backend):
    engine, params = engines[backend]
    out, worker, win = _serve(engine, params, _requests(ds, 25))
    assert all(c.ok for c in out) and win.complete
    recs = [r for r in win.records if r.name != "gc"]
    names = [r.name for r in recs]
    # the records of the served requests, every one on the worker's thread
    assert {r.tid for r in recs if r.name != "xla.compile"} == {worker}
    assert names.count("serve.inbox") == names.count("serve.enqueue") == 24
    assert names.count("serve.idle") >= 1
    batches = names.count("serve.flush")
    assert batches >= 3
    per_batch = PER_BATCH + (BRUTE if backend == "brute_pq4" else ())
    for name in per_batch:
        assert names.count(name) == batches, name
    for name in BRUTE if backend == "graph" else ():
        assert name not in names
    # phases nest: each engine span lies inside a flush
    flushes = [(r.t0_ns, r.t1_ns) for r in recs if r.name == "serve.flush"]
    for r in recs:
        if r.name.startswith(("engine.", "brute.")):
            assert any(a <= r.t0_ns and r.t1_ns <= b for a, b in flushes)


def test_profiler_annotations_carry_the_span_names(ds, engines, tmp_path):
    engine, params = engines["brute_pq4"]
    reqs = _requests(ds, 9)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, _, win = _serve(engine, params, reqs)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    (path,) = tmp_path.glob("**/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    seen = {e.name for p in pd.planes for line in p.lines
            for e in line.events if e.name.startswith("stable.")}
    ring = {r.name for r in win.records} - {"serve.inbox", "xla.compile"}
    assert {"stable." + n for n in ring} <= seen
    assert {"stable." + n for n in PER_BATCH + BRUTE} <= seen


def test_stalled_worker_raises_inbox_wait_not_queue_wait(ds, engines):
    """A request that arrives while a slow batch runs waits in the inbox:
    ``inbox_ms`` carries the stall, ``queue_ms`` does not move."""
    engine, params = engines["graph"]
    started = threading.Event()
    search = engine.search

    def slow(queries, p=SearchParams()):
        if not started.is_set():
            started.set()
            time.sleep(0.3)
        return search(queries, p)

    reqs = _requests(ds, 2)
    engine.search = slow
    try:
        with ThreadedServer(engine, _registry(params), window_ms=2.0,
                            buckets=(1, 8)) as srv:
            first = srv.submit(reqs[0])
            assert started.wait(30)
            second = srv.submit(reqs[1])
            a, b = first.result(), second.result()
    finally:
        del engine.search
    assert a.inbox_ms < 100.0
    assert b.inbox_ms >= 200.0  # waited out most of the 300 ms stall
    assert a.queue_ms < 100.0 and b.queue_ms < 100.0
    assert srv.stats.registry.histogram("serve_inbox_ms").count == 2


def test_sampled_tree_is_inbox_plus_queue_plus_flush(ds, engines):
    engine, params = engines["graph"]
    tracer = Tracer(sample_every=1)
    out, _, _ = _serve(engine, params, _requests(ds, 12), tracer=tracer)
    assert all(c.ok for c in out)
    traces = tracer.traces()
    assert traces
    for tr in traces:
        root = tr.root
        assert [c.name for c in root.children] == [
            "serve.inbox", "serve.queue", "serve.flush"]
        inbox, queue, flush = root.children
        assert root.duration == pytest.approx(
            inbox.duration + queue.duration + flush.duration, abs=1e-9)
        assert inbox.duration == pytest.approx(
            root.attrs["inbox_ms"] * 1e-3, abs=1e-9)
        # the counters are read after the wait, not inside the dispatch
        assert "n_hops" in flush.find("engine.dispatch").attrs
        assert flush.find("engine.wait") is not None


def test_forced_collection_leaves_one_gc_record():
    tracer = Tracer(sample_every=1)
    tr = tracer.start()
    n0 = obs_trace.GC_PAUSE_MS.count
    t0 = time.perf_counter_ns()
    with tr.root:
        gc.collect()
    recs = obs_trace.recorder().between(t0, time.perf_counter_ns()).records
    assert [r.name for r in recs] == ["gc"]
    assert obs_trace.GC_PAUSE_MS.count == n0 + 1
    (node,) = tr.root.children
    assert node.name == "gc" and node.attrs["gen"] == 2


def test_xla_compile_is_recorded_and_counted():
    obs_trace.install()
    n0 = obs_trace.XLA_COMPILES.value
    t0 = time.perf_counter_ns()
    x = np.ones((3, 5), np.float32)  # made on the host: no compile
    jax.jit(lambda x: x * 7 + 0.25)(x).block_until_ready()
    t1 = time.perf_counter_ns()
    recs = [r for r in obs_trace.recorder().between(t0, t1).records
            if r.name == "xla.compile"]
    assert len(recs) == 1 and obs_trace.XLA_COMPILES.value == n0 + 1
    assert t0 <= recs[0].t0_ns <= recs[0].t1_ns <= t1


def test_ring_is_bounded_and_counts_what_it_drops():
    with pytest.raises(ValueError):
        obs_trace.Recorder(capacity=12)
    ring = obs_trace.Recorder(capacity=8)
    for i in range(20):
        ring.record("x", 100 * i, 100 * i + 50, tid=1)
    assert ring.written == 20 and ring.dropped == 12
    win = ring.between(0, 10_000)
    assert [r.t0_ns for r in win.records] == [100 * i for i in range(12, 20)]
    assert not win.complete  # records closing in the window were lost
    # the oldest record held closed at 1250: nothing after it was lost
    assert ring.between(1250, 10_000).complete
    assert [r.t0_ns for r in ring.between(1400, 1500).records] == [1400, 1500]


def test_ring_loses_no_record_under_threads():
    """Eight threads write at once, with the interpreter switching threads
    as often as it can: every record lands whole in a slot of its own."""
    import sys

    ring = obs_trace.Recorder(capacity=1 << 15)
    per_thread, n_threads = 4000, 8
    start = threading.Barrier(n_threads)

    def work(k):
        start.wait(timeout=60)
        for i in range(per_thread):
            ring.record("x", k * 10**6 + i, k * 10**6 + i + k, tid=k)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(1, n_threads + 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    recs = ring.between(0, 10**9).records
    assert ring.written == len(recs) == per_thread * n_threads
    assert len({(r.tid, r.t0_ns) for r in recs}) == len(recs)
    assert all(r.t1_ns - r.t0_ns == r.tid and r.t0_ns // 10**6 == r.tid
               for r in recs)


def test_untraced_spans_retain_no_memory():
    """A span always annotates and writes the ring, but keeps nothing: the
    ring is preallocated and the annotation is gone at exit."""
    import tracemalloc

    def hot():
        with obs_trace.span("engine.lookup") as sp:
            assert sp is obs_trace.NOOP_SPAN

    for _ in range(100):
        hot()
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(5000):
        hot()
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(s.size_diff for s in after.compare_to(before, "filename")
                if s.size_diff > 0)
    assert grown < 16_000


def test_counters_are_exported(ds, engines):
    engine, params = engines["graph"]
    reqs = _requests(ds, 4, offset=30)
    with ThreadedServer(engine, _registry(params), window_ms=2.0,
                        buckets=(1, 8)) as srv:
        for f in [srv.submit(r) for r in reqs]:
            f.result()
    gc.collect()
    text = prometheus_text(srv.stats.registry)
    for line in ("# TYPE serve_inbox_ms histogram",
                 "# TYPE process_gc_pause_ms histogram",
                 "# TYPE xla_compiles_total counter",
                 "# TYPE obs_spans_dropped_total counter"):
        assert line in text
    assert "serve_inbox_ms_count 4" in text
    gc_count = next(l for l in text.splitlines()
                    if l.startswith("process_gc_pause_ms_count"))
    assert int(gc_count.split()[1]) >= 1


def test_chrome_trace_carries_flat_spans():
    t0 = time.perf_counter_ns()
    with obs_trace.span("serve.flush"):
        with obs_trace.span("serve.assemble"):
            pass
    win = obs_trace.recorder().between(t0, time.perf_counter_ns())
    doc = chrome_trace([], win.records)
    flat = [e for e in doc["traceEvents"] if e["pid"] == 2]
    assert {e["name"] for e in flat} >= {"serve.flush", "serve.assemble"}
    outer = next(e for e in flat if e["name"] == "serve.flush")
    inner = next(e for e in flat if e["name"] == "serve.assemble")
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert np.isclose(outer["ts"], win.records[0].t0_ns / 1e3)
