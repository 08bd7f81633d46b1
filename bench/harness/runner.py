"""One run of one cell: set up, measure a window, compare, report.

    corpus and query pool from the seed → the deployment built and every
    bucket warmed (set-up, timed) → the traffic mix's schedule driven through
    ``ThreadedServer`` for ``seconds`` → every answer due compared with the
    plain reference → the cell's metrics, each read by its own reader.

With ``trace`` the window runs under the JAX profiler with the engine
instrumented (``instrument.EngineProbe``), and the per-layer metrics are
reported instead of the end-to-end ones.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import os
import shutil
import sys
import time
from typing import Callable, Optional

import numpy as np

from harness import corpus as corpus_mod
from harness import deploy, drive, instrument, reference
from harness import trace as trace_mod
from harness.spec import Cell, Spec


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class RunView:
    """What a metric reader may read (see ``bench/metrics/``)."""

    cell: Cell
    setup_s: float
    window: drive.Window
    numbers: dict  # the compared numbers (reference.compare)
    batches: list  # traced runs: one dict per engine call (instrument)
    trace: Optional[trace_mod.TraceSummary]
    peaks: dict  # the device's published peaks (peaks.json)

    @property
    def completed(self) -> list:
        """(record, Completed) of every request answered."""
        return [(r, r.response) for r in self.window.records
                if r.response is not None and r.response.ok]


def check_chips(jax, chips: int) -> list:
    platform = jax.default_backend()
    devs = jax.devices()
    if platform != "tpu" or len(devs) < chips:
        raise NoChip(f"JAX default backend {platform!r} with {len(devs)} "
                     f"device(s); this cell needs {chips} TPU chip(s)")
    return devs[:chips]


class _CompileCounter:
    """Backend compiles seen while ``on`` (JAX's monitoring events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **_):
        if self.on and event == self.EVENT:
            self.count += 1


def set_up(spec: Spec, cell: Cell, seed: int, say: Callable[[str], None],
           require_chips: bool = True):
    """Chips, compile cache, corpus, deployment and warm-up: everything
    before the window. Returns (devices, peaks, corpus, deployment)."""
    deploy.check_config(cell.config)
    predicate = spec.predicate(cell.traffic)
    import jax

    devs = check_chips(jax, cell.chips) if require_chips else jax.devices()[:1]
    sys.path.insert(0, os.path.join(spec.root, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # cache every program, however quick to compile, so a second run's
    # set-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = devs[0]
    say(f"device: {dev.platform} / {dev.device_kind} x {len(devs)}; "
        f"compile cache {cache}")
    peaks = spec.peaks(dev.device_kind) if require_chips else {}

    cfg, mix = cell.config, cell.traffic
    clock = deploy.SetupClock(say)
    t0 = time.perf_counter()
    corpus = corpus_mod.make_corpus(cfg["corpus"], mix["query_pool"], seed)
    clock.step(f"corpus N={len(corpus.features)} and {mix['query_pool']} "
               f"queries (seed {seed})", t0)
    dep = deploy.build(cfg, corpus, predicate, clock)
    say(f"planned backend: {dep.planned_backend}")
    deploy.warm(dep, corpus, clock)
    return devs, peaks, corpus, dep


def with_settings(cell: Cell, settings: dict) -> Cell:
    """The cell with configuration settings replaced: ``{"search.pool_size":
    256}`` sets ``pool_size`` in the ``search`` group (planted faults)."""
    cfg = {g: dict(v) if isinstance(v, dict) else v
           for g, v in cell.config.items()}
    for key, value in settings.items():
        group, _, name = key.partition(".")
        if name not in cfg.get(group, {}):
            raise KeyError(f"{cell.config['name']} has no setting {key}")
        cfg[group][name] = value
    return dataclasses.replace(cell, config=cfg)


def run_cell(spec: Spec, name: str, seed: int, seconds: float, trace: bool,
             *, t_start: float, require_chips: bool = True,
             say: Callable[[str], None] = print,
             settings: Optional[dict] = None) -> dict:
    """One run; ``settings`` (see ``with_settings``) plants a fault."""
    cell = spec.cell(name)
    if settings:
        cell = with_settings(cell, settings)
    import jax

    compiles = _CompileCounter(jax)
    devs, peaks, corpus, dep = set_up(spec, cell, seed, say, require_chips)
    predicate = dep.predicate
    dev = devs[0]
    cfg, mix = cell.config, cell.traffic
    sched = spec.generator(mix).make_schedule(
        mix, corpus_mod.rng(seed, 1), seconds)

    probe = instrument.EngineProbe(dep.engine) if trace else None
    tracer = trace_mod.Profiler() if trace else None
    setup_s = time.perf_counter() - t_start
    say(f"[set-up] total (process start to window): {setup_s:.3f} s")

    compiles.on = True
    win = drive.run_window(
        dep, corpus, sched, seconds,
        before=tracer.start if tracer else (lambda: None),
        after=tracer.stop if tracer else (lambda: None),
    )
    compiles.on = False
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0) for d in devs) \
        if require_chips else 0
    in_use = [d.memory_stats().get("bytes_in_use", 0) for d in devs] \
        if require_chips else []
    say(f"window: {win.seconds:.3f} s, {len(win.records)} requests sent, "
        f"drained {win.drained_at - win.t1:.3f} s after the close; "
        f"server counters {win.counters}; compiles in the window "
        f"{compiles.count}")
    if len(win.lateness_s):
        say(f"generator lateness: p50 {np.median(win.lateness_s) * 1e3:.3f} "
            f"ms, p99 {np.percentile(win.lateness_s, 99) * 1e3:.3f} ms, "
            f"max {win.lateness_s.max() * 1e3:.3f} ms")
    by_bucket = collections.Counter(
        r.response.bucket for r in win.records
        if r.response is not None and r.response.ok)
    say(f"answers by bucket: {dict(sorted(by_bucket.items()))}")
    say(f"bytes_in_use after the window: {in_use}; peak {peak}")

    summary = None
    if tracer is not None:
        t0 = time.perf_counter()
        summary = trace_mod.reduce(tracer.path, tracer.window_s)
        shutil.rmtree(tracer.dir, ignore_errors=True)
        say(f"[trace] reduced in {time.perf_counter() - t0:.3f} s: "
            f"{summary.describe()}")
    batches = probe.batches if probe else []
    if probe:
        probe.uninstall()
    del dep, probe
    gc.collect()

    t0 = time.perf_counter()
    k = cfg["search"]["k"]
    ans = reference.Answers.collect(
        [r.pool_idx for r in win.records],
        [(r.response.ids, r.response.dists)
         if r.response is not None and r.response.ok else None
         for r in win.records], k)
    ref = reference.Reference(corpus.features, corpus.attrs, predicate)
    numbers = reference.compare(ans, ref, corpus.query_features,
                                corpus.query_attrs, k)
    correct, rows = reference.verdict(numbers, cfg["checks"])
    say(f"[reference] float64 exact {mix['predicate']} top-{k} of "
        f"{len(np.unique(ans.pool_idx))} distinct queries and the "
        f"comparison: {time.perf_counter() - t0:.3f} s (not set-up)")

    view = RunView(cell, setup_s, win, numbers, batches, summary, peaks)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.metric_reader(m["name"]).read(view)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": len(ans.pool_idx),
           "failed": int((~ans.answered).sum()), "metrics": metrics,
           "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops(10),
                            "idle_gaps": summary.idle_gaps(10)}
    out["checks"] = {n: {"value": v, "limit": lim, "pass": ok}
                     for n, v, _, lim, ok in rows}
    for n, v, rel, lim, ok in rows:
        print(f"check {n} = {v!r} {rel} {lim!r}: "
              f"{'pass' if ok else 'FAIL'}", file=sys.stderr, flush=True)
    return out

