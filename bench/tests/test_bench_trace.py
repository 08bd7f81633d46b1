"""The trace reducer on a trace recorded on a TPU v5e, the roofline count,
and the per-layer readers that read the trace.

``bench/testdata/v5e_search_n20k_b8.xplane.pb.gz``: two HELP traversals
(``jit__search_jit``, pool 512) and two pq4 brute searches (the Pallas
kernel ``adc_scan4_scores``) of 8 queries over 20,000 rows, each inside a
host ``TraceAnnotation``, profiled on one v5e chip.
"""
import os
import types

import pytest

from conftest import BENCH, REPO
from harness import instrument, roofline, trace
from harness.spec import Spec

TRACE = os.path.join(BENCH, "testdata", "v5e_search_n20k_b8.xplane.pb.gz")
V5E = {"bf16_flop_per_s": 197e12, "hbm_byte_per_s": 819e9}


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(TRACE)


def test_device_busy_and_window(summary):
    assert summary.chips == 1
    assert summary.busy_s == pytest.approx(0.089918648, abs=1e-9)
    assert summary.window_s == pytest.approx(0.156192631, abs=1e-9)
    idle = sum(b - a for a, b in summary.gaps()) / 1e9
    assert idle == pytest.approx(summary.window_s - summary.busy_s, abs=1e-9)


def test_programs_by_name(summary):
    runs = summary.program("jit__search_jit")
    assert [m.end - m.start for m in runs] == [44418167, 44419516]
    assert len(summary.program("jit_adc_scan4_scores")) == 2
    assert summary.program("no_such_program") == []
    top = summary.top_ops(3)
    assert all(name.startswith("jit__search_jit/") for name, _ in top)
    assert top[0][1] >= top[1][1] >= top[2][1] > 0


def test_pallas_kernel_by_name(summary):
    calls = summary.kernel("adc_scan4_scores")
    assert len(calls) == 2
    for c in calls:
        assert c.dims == (8, 20224) and c.end - c.start == 199407
        assert c.program == "jit_adc_scan4_scores"
    assert summary.kernel("fusion") == []


def test_op_self_time_excludes_nested_body(summary):
    whiles = [o for o in summary.ops if o.name.startswith("while")]
    assert whiles and all(0 <= o.self_ns < o.end - o.start for o in whiles)
    total_self = sum(o.self_ns for o in summary.ops)
    assert total_self / 1e9 <= summary.busy_s * (1 + 1e-9)


def test_idle_gaps_are_named_by_host_spans(summary):
    a, b = max(summary.gaps(), key=lambda g: g[1] - g[0])
    assert summary.label(a, b).startswith("outside the engine")
    summary.host.append(trace.Span(instrument.SEARCH, a - 10, b + 10))
    summary._by_name.clear()
    assert summary.label(a, b) == "engine.search dispatch"
    summary.host.append(trace.Span(instrument.PLAN, a, a + (b - a) * 3 // 4))
    summary._by_name.clear()
    assert summary.label(a, b) == "engine.plan"
    gaps = summary.idle_gaps(3)
    assert len(gaps) == 3 and gaps[0][1] >= gaps[1][1] >= gaps[2][1]
    del summary.host[-2:]
    summary._by_name.clear()


def test_adc_scan_work_count():
    ops, nbytes = roofline.adc_scan_work(128, 1_000_000, 32, 5, 2048)
    assert ops == 128 * 1_000_000 * (32 + 5)
    assert nbytes == (16_000_000 + 20_000_000 + 128 * 32 * 16 * 4
                      + 128 * 5 * 4 + 128 * 2048 * 8)
    t, bound = roofline.least_seconds(ops, nbytes, V5E)
    assert bound == "bytes" and t == pytest.approx(nbytes / 819e9)
    t, bound = roofline.least_seconds(10**12, 1, V5E)
    assert bound == "ops" and t == pytest.approx(10**12 / 197e12)


def _view(summary, rows):
    cfg = {"corpus": {"rows": rows, "attr_dims": 5},
           "index": {"pq_subspaces": 32}, "search": {"pool_size": 2048}}
    return types.SimpleNamespace(trace=summary, peaks=V5E,
                                 cell=types.SimpleNamespace(config=cfg))


def test_trace_readers(summary):
    spec = Spec(REPO)
    view = _view(summary, 20000)
    read = lambda name: spec.metric_reader(name).read(view)  # noqa: E731
    assert read("traversal_ms_per_batch") == pytest.approx(44.4188415)
    assert read("adc_scan_ms_per_batch") == pytest.approx(0.199407)
    ops, nbytes = roofline.adc_scan_work(8, 20000, 32, 5, 2048)
    want = 100 * 2 * (nbytes / 819e9) / (2 * 199407e-9)
    assert read("adc_scan_roofline") == pytest.approx(want)
    assert 0 < want < 100
    idle = 100 * (1 - summary.busy_s / summary.window_s)
    assert read("idle_share.closed") == pytest.approx(idle)
    assert read("idle_share.open") == pytest.approx(idle)


def test_trace_readers_find_nothing_without_a_trace():
    spec = Spec(REPO)
    view = _view(None, 20000)
    for name in ("traversal_ms_per_batch", "adc_scan_ms_per_batch",
                 "adc_scan_roofline", "idle_share.closed", "idle_share.open"):
        assert spec.metric_reader(name).read(view) is None
