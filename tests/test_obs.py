"""Observability subsystem (repro.obs): registry thread-safety and bounds,
streaming-histogram percentile accuracy vs numpy, zero-allocation no-op
tracing, deterministic serve-loop trace decomposition, exporter formats,
the metrics HTTP endpoint, ServerStats snapshot compatibility, and
negative-result caching."""
import json
import re
import threading
import tracemalloc
import urllib.request

import numpy as np
import pytest

from repro.api import MATCH, Engine, Query, SearchParams
from repro.core.help_graph import HelpConfig
from repro.data.synthetic import make_hybrid_dataset
from repro.obs import (
    LATENCY_MS_BOUNDS, MetricsRegistry, MetricsServer, NOOP_SPAN, Tracer,
    chrome_trace, current, json_snapshot, log_bounds, prometheus_text,
)
from repro.obs import trace as obs_trace
from repro.serve import (
    Request, ServerStats, TenantPolicy, TenantRegistry, serve_loop,
)

HELP_CFG = HelpConfig(gamma=12, gamma_new=4, max_rounds=3,
                      quality_sample=64, node_block=512)
PARAMS = SearchParams(k=10, pool_size=32, pioneer_size=8)


@pytest.fixture(scope="module")
def ds():
    return make_hybrid_dataset(
        n=2000, n_queries=48, profile="sift", attr_dim=5, labels_per_dim=3,
        n_clusters=8, attr_cluster_corr=0.6, seed=0,
    )


@pytest.fixture(scope="module")
def engine(ds):
    return Engine.build(ds.features, ds.attrs, HELP_CFG)


def _trace(ds, n=48, spacing=2e-4):
    tenants = ("acme", "beta")
    return [
        (i * spacing,
         Request(tenants[i % 2],
                 Query(ds.query_features[i],
                       [MATCH(int(x)) for x in ds.query_attrs[i]])))
        for i in range(n)
    ]


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_conservation_under_threads(self):
        """8 threads hammering one counter + one histogram lose nothing."""
        reg = MetricsRegistry()
        c = reg.counter("ops")
        h = reg.histogram("lat_ms")
        per_thread, n_threads = 2000, 8

        def work():
            for i in range(per_thread):
                c.inc()
                h.observe(float(i % 50) + 0.1)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == per_thread * n_threads
        assert h.count == per_thread * n_threads

    def test_get_or_create_is_idempotent_and_kind_checked(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(ValueError):
            reg.gauge("x")

    def test_histogram_percentiles_match_numpy(self):
        """Streaming log-bucket percentiles land within one bucket width
        (≤ ~26% relative at 10 buckets/decade) of numpy's exact answer."""
        rng = np.random.default_rng(0)
        samples = np.exp(rng.normal(np.log(5.0), 1.0, size=20_000))
        h = MetricsRegistry().histogram("lat", bounds=LATENCY_MS_BOUNDS)
        for s in samples:
            h.observe(float(s))
        for q in (50, 90, 95, 99):
            exact = float(np.percentile(samples, q))
            est = h.percentile(q)
            assert abs(est - exact) / exact < 0.26, (q, est, exact)
        assert h.count == samples.size
        assert h.min == pytest.approx(samples.min())
        assert h.max == pytest.approx(samples.max())

    def test_histogram_state_is_bounded(self):
        """A million observations keep a fixed-size footprint: bucket
        counts + scalars, no per-sample storage (the old list bug)."""
        h = MetricsRegistry().histogram("lat")
        for i in range(100_000):
            h.observe(float(i % 977) + 0.5)
        snap = h.snapshot()
        assert snap["count"] == 100_000
        assert len(h.cumulative_buckets()) == len(LATENCY_MS_BOUNDS) + 1
        assert len(h._counts) == len(LATENCY_MS_BOUNDS) + 1  # fixed buckets

    def test_log_bounds_cover_range(self):
        b = log_bounds(1e-3, 6e4, per_decade=10)
        assert b[0] <= 1e-3 and b[-1] >= 6e4
        assert all(x < y for x, y in zip(b, b[1:]))

    def test_providers_flatten_and_survive_errors(self):
        reg = MetricsRegistry()
        reg.register_provider(
            "exec", lambda: {"hits": 3, "nested": {"a": 1.5, "flag": True}}
        )
        reg.register_provider("boom", lambda: 1 / 0)
        vals = reg.provider_values()
        assert vals["exec_hits"] == 3
        assert vals["exec_nested_a"] == 1.5
        assert vals["exec_nested_flag"] == 1
        assert not any(k.startswith("boom") for k in vals)
        reg.unregister_provider("exec")
        assert "exec_hits" not in reg.provider_values()


# ---------------------------------------------------------------------------
# Tracing
# ---------------------------------------------------------------------------


class TestTracer:
    def test_noop_path_allocates_nothing(self):
        """With no sampled trace, a span's tree target is the falsy
        singleton, and the instrumentation pattern retains no memory: the
        span's own record goes to the preallocated ring."""
        assert current() is NOOP_SPAN
        with obs_trace.span("anything") as sp:
            assert sp is NOOP_SPAN
        assert not NOOP_SPAN

        def hot():
            with obs_trace.span("engine.plan") as sp:
                if sp:  # pragma: no cover - never taken untraced
                    sp.set("k", 1)

        hot()  # warm any lazy interning
        tracemalloc.start()
        before = tracemalloc.take_snapshot()
        for _ in range(1000):
            hot()
        after = tracemalloc.take_snapshot()
        tracemalloc.stop()
        leaked = sum(
            s.size_diff for s in after.compare_to(before, "filename")
            if s.size_diff > 0
        )
        # tracemalloc's own bookkeeping can show small noise; anything per-
        # iteration would be >= 1000 * minimal object size (~32kB)
        assert leaked < 16_000

    def test_disabled_tracer_never_samples(self):
        t = Tracer(sample_every=0)
        assert not t.enabled
        assert not any(t.should_sample() for _ in range(100))

    def test_sampling_is_deterministic(self):
        t = Tracer(sample_every=3)
        picks = [t.should_sample() for _ in range(9)]
        assert picks == [False, False, True] * 3  # fires on every Nth tick

    def test_span_stack_nesting_and_find(self):
        t = Tracer(sample_every=1)
        tr = t.start("request")
        with tr.root.span("serve.flush") as b:
            assert current() is b
            with obs_trace.span("engine.plan") as p:
                p.set("backend", "graph")
            assert current() is b
        assert current() is NOOP_SPAN
        t.finish(tr)
        plan = tr.root.find("engine.plan")
        assert plan is not None and plan.attrs["backend"] == "graph"
        assert tr.root.duration >= plan.duration >= 0.0

    def test_trace_store_is_bounded(self):
        t = Tracer(sample_every=1, max_traces=4)
        for i in range(10):
            tr = t.start(f"r{i}")
            t.finish(tr)
        kept = t.traces()
        assert len(kept) == 4
        assert kept[-1].root.name == "r9"  # oldest dropped first


# ---------------------------------------------------------------------------
# Serve-loop trace decomposition (deterministic driver)
# ---------------------------------------------------------------------------


class TestServeTrace:
    def test_trace_tree_sums_to_end_to_end_latency(self, ds, engine):
        reg = TenantRegistry(default_policy=TenantPolicy(params=PARAMS))
        tracer = Tracer(sample_every=1)
        resp, stats = serve_loop(
            engine, _trace(ds), reg, window_ms=2.0, buckets=(1, 8, 32),
            tracer=tracer,
        )
        assert all(r.ok for r in resp)
        traces = tracer.traces()
        assert traces, "sample_every=1 must record every flushed batch"
        for tr in traces:
            root = tr.root
            inbox, queue, batch = (root.find("serve.inbox"),
                                   root.find("serve.queue"),
                                   root.find("serve.flush"))
            assert None not in (inbox, queue, batch)
            assert [c.name for c in root.children] == [
                "serve.inbox", "serve.queue", "serve.flush"]
            assert inbox.duration == 0.0  # serve_loop has no inbox
            # exact by construction: root pinned to inbox + queue + flush
            assert root.duration == pytest.approx(
                inbox.duration + queue.duration + batch.duration, abs=1e-9
            )
            # engine spans attached under the flush via the thread-local
            # stack
            for name in ("serve.assemble", "engine.search", "engine.plan",
                         "engine.lookup", "engine.dispatch", "engine.wait",
                         "serve.fetch"):
                assert batch.find(name) is not None, name
            assert "n_hops" in batch.find("engine.dispatch").attrs
            child_s = sum(c.duration for c in batch.children)
            assert child_s <= batch.duration + 1e-9
            assert child_s >= 0.5 * batch.duration
            # recorded latency attrs re-derive the root within tolerance
            attr_ms = root.attrs["queue_ms"] + root.attrs["service_ms"]
            total_ms = root.duration * 1e3
            assert abs(total_ms - attr_ms) <= max(1.0, 0.25 * total_ms)

    def test_untraced_run_records_nothing(self, ds, engine):
        reg = TenantRegistry(default_policy=TenantPolicy(params=PARAMS))
        tracer = Tracer(sample_every=0)
        resp, _ = serve_loop(
            engine, _trace(ds, n=16), reg, window_ms=2.0, buckets=(1, 8),
            tracer=tracer,
        )
        assert all(r.ok for r in resp)
        assert tracer.traces() == []


# ---------------------------------------------------------------------------
# Exporters + HTTP endpoint
# ---------------------------------------------------------------------------


def _filled_registry():
    reg = MetricsRegistry()
    reg.counter("reqs").inc(7)
    reg.gauge("depth").set(3.5)
    h = reg.histogram("lat_ms")
    for v in (0.5, 1.5, 12.0, 80.0):
        h.observe(v)
    reg.register_provider("exec", lambda: {"hits": 2, "rate": 0.5})
    return reg


class TestExport:
    def test_prometheus_text_parses(self):
        text = prometheus_text(_filled_registry())
        sample = re.compile(
            r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9+\-.eEinfa]+$"
        )
        lines = [l for l in text.splitlines() if l]
        assert any(l.startswith("# TYPE reqs counter") for l in lines)
        assert any(l.startswith("# TYPE lat_ms histogram") for l in lines)
        for l in lines:
            if not l.startswith("#"):
                assert sample.match(l), l
        # histogram buckets are cumulative and end at +Inf == count
        buckets = [l for l in lines if l.startswith("lat_ms_bucket")]
        counts = [float(l.split()[-1]) for l in buckets]
        assert counts == sorted(counts)
        assert 'le="+Inf"' in buckets[-1] and counts[-1] == 4
        assert any(l.startswith("lat_ms_count 4") for l in lines)
        assert any(l.startswith("exec_hits 2") for l in lines)

    def test_json_snapshot_round_trips(self):
        snap = json.loads(json_snapshot(_filled_registry()))
        assert snap["counters"]["reqs"] == 7
        assert snap["histograms"]["lat_ms"]["count"] == 4
        assert snap["providers"]["exec_rate"] == 0.5

    def test_chrome_trace_structure(self):
        t = Tracer(sample_every=1)
        tr = t.start("request")
        with tr.root.span("serve.flush"):
            with obs_trace.span("engine.plan") as p:
                p.set("backend", "graph")
        t.finish(tr)
        doc = chrome_trace(t.traces())
        events = doc["traceEvents"]
        assert {e["name"] for e in events} >= {
            "request", "serve.flush", "engine.plan"}
        for e in events:
            assert e["ph"] == "X" and e["dur"] >= 0
        plan = next(e for e in events if e["name"] == "engine.plan")
        assert plan["args"]["backend"] == "graph"

    def test_metrics_server_scrape(self):
        reg = _filled_registry()
        with MetricsServer(reg, port=0) as srv:
            text = urllib.request.urlopen(
                srv.url + "/metrics", timeout=5
            ).read().decode()
            assert "reqs 7" in text
            snap = json.loads(urllib.request.urlopen(
                srv.url + "/metrics.json", timeout=5
            ).read().decode())
            assert snap["counters"]["reqs"] == 7
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(srv.url + "/nope", timeout=5)


# ---------------------------------------------------------------------------
# ServerStats as a registry view
# ---------------------------------------------------------------------------


class TestServerStatsRegistry:
    def test_snapshot_keys_backward_compatible(self, ds, engine):
        reg = TenantRegistry(default_policy=TenantPolicy(params=PARAMS))
        _, stats = serve_loop(engine, _trace(ds), reg, window_ms=2.0,
                              buckets=(1, 8, 32))
        snap = stats.snapshot()
        for key in ("submitted", "completed", "rejected", "latency_ms",
                    "queue_ms_p99", "service_ms_p99", "batches",
                    "batch_fill_ratio", "qps", "service_qps", "per_tenant",
                    "retraces", "jit_hit_rate", "plan_cache"):
            assert key in snap, key
        for p in ("p50", "p95", "p99", "mean"):
            assert snap["latency_ms"][p] >= 0.0
        assert snap["latency_ms"]["p50"] <= snap["latency_ms"]["p99"]

    def test_no_unbounded_latency_lists(self, ds, engine):
        """The old queue_ms/service_ms/... per-request lists are gone;
        latency state is the registry's fixed-bucket histograms."""
        stats = ServerStats(engine)
        for attr in ("queue_ms", "service_ms", "total_ms", "merge_ms"):
            assert not hasattr(stats, attr)
        for _ in range(1000):
            stats.record_completion("t", 1.0, 2.0)
        assert stats.registry.histogram("serve_total_ms").count == 1000

    def test_registry_sees_all_owners(self, ds, engine):
        reg = TenantRegistry(default_policy=TenantPolicy(params=PARAMS))
        _, stats = serve_loop(engine, _trace(ds), reg, window_ms=2.0,
                              buckets=(1, 8, 32))
        vals = stats.registry.provider_values()
        assert vals["serve_completed"] == stats.completed
        assert "executor_hits" in vals
        assert "routing_jit_traces" in vals
        text = prometheus_text(stats.registry)
        assert "serve_total_ms_bucket" in text
        assert "serve_completed" in text


# ---------------------------------------------------------------------------
# Negative-result caching
# ---------------------------------------------------------------------------


class TestNegativeCache:
    def test_empty_hits_counted(self):
        from repro.cache.results import ResultCache

        rc = ResultCache(max_entries=8)
        k_neg, k_pos = b"neg", b"pos"
        rc.insert(k_neg, np.full(10, -1, np.int32),
                  np.full(10, np.inf, np.float32), now=0.0, epoch=0)
        rc.insert(k_pos, np.arange(10, dtype=np.int32),
                  np.zeros(10, np.float32), now=0.0, epoch=0)
        assert rc.lookup(k_neg, now=0.1, epoch=0) is not None
        assert rc.lookup(k_neg, now=0.2, epoch=0) is not None
        assert rc.lookup(k_pos, now=0.3, epoch=0) is not None
        st = rc.stats()
        assert st["empty_hits"] == 2
        assert st["empty_entries"] == 1
        assert st["hits"] == 3
        rc.reset_counters()
        assert rc.stats()["empty_hits"] == 0

    def test_partial_invalid_row_is_not_empty(self):
        from repro.cache.results import ResultCache

        rc = ResultCache(max_entries=8)
        ids = np.array([3, 1, -1, -1], np.int32)
        rc.insert(b"k", ids, np.zeros(4, np.float32), now=0.0, epoch=0)
        rc.lookup(b"k", now=0.1, epoch=0)
        assert rc.stats()["empty_hits"] == 0
