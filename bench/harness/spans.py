"""The program's own spans in a traced run, joined to the device trace.

The program (``repro.obs.trace``) records every serve-path phase in a
process-wide ring, ``(name, thread id, t0_ns, t1_ns)`` on
``time.perf_counter_ns``, the clock of the run's window. The profiler's
trace (``harness.trace``) has a time base of its own. Each of the
program's ``engine.search`` spans lies inside the benchmark's
``bench.engine.search`` annotation, because ``instrument.EngineProbe``
wraps the same call; so the offset between the two clocks is the median
of (annotation start - span start) over the pairs, matched in order,
inside the traced window.

``records(run)`` gives the ring's records of the traced window
``[window.t0, window.drained_at]`` and ``joined(run)`` adds the offset and
the serving worker's thread. Both return None where the program keeps no
ring, where the ring dropped records inside the window, and (``joined``)
where the trace has no annotations to pair with the spans. The first
``joined`` of a run prints the join's own numbers and the longest idle
gaps of chip 0, each named by the program span that covers it.
"""
from __future__ import annotations

import dataclasses
import statistics
import sys
from typing import Optional

from harness import instrument

SEARCH = "engine.search"
IDLE = "serve.idle"
DISPATCH = "engine.dispatch"
INBOX = "serve.inbox"  # on the worker's thread, but timed from submit
GC = "gc"
ANYWHERE = (GC, "xla.compile")  # records that name a gap on any thread


def _ring():
    trace = sys.modules.get("repro.obs.trace")
    return getattr(trace, "recorder", None) if trace else None


def records(run) -> Optional[list]:
    """The ring's records overlapping the traced window, or None."""
    if "_span_records" not in vars(run):
        recorder = _ring()
        out = None
        if recorder is not None:
            w = run.window
            got = recorder().between(int(w.t0 * 1e9),
                                     int(w.drained_at * 1e9))
            out = got.records if got.complete else None
        run._span_records = out
    return run._span_records


def gc_pause_ms(run) -> Optional[float]:
    """Garbage collection inside the traced window, on any thread, in ms.
    Prints the run's span diagnostics when the trace can be joined."""
    recs = records(run)
    if recs is None:
        return None
    joined(run)
    w = run.window
    lo, hi = int(w.t0 * 1e9), int(w.drained_at * 1e9)
    return sum(max(0, min(hi, r.t1_ns) - max(lo, r.t0_ns))
               for r in recs if r.name == GC) / 1e6


def union(intervals) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return out


def overlap(gaps, intervals) -> int:
    """ns of the (disjoint, sorted) ``gaps`` under any of ``intervals``."""
    cover, total, j = union(intervals), 0, 0
    for a, b in gaps:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            total += max(0, min(b, cover[k][1]) - max(a, cover[k][0]))
            k += 1
    return total


@dataclasses.dataclass
class Joined:
    records: list  # the window's ring records (perf_counter_ns)
    worker: int  # thread id of the serving worker
    offset: int  # trace clock - perf_counter_ns
    lo: int  # the traced window on the trace's clock
    hi: int
    pairs: int  # engine.search spans joined to annotations
    worst_us: float  # largest reach of a shifted span past its annotation
    spread_us: float  # IQR of the pairs' offsets

    def on_trace(self, name: Optional[str] = None, worker: bool = True):
        """(start, end) on the trace's clock of the records called
        ``name`` (any name but ``serve.inbox`` when None), on the worker's
        thread unless ``worker`` is False."""
        return [(r.t0_ns + self.offset, r.t1_ns + self.offset)
                for r in self.records
                if (not worker or r.tid == self.worker)
                and (r.name == name if name else r.name != INBOX)]

    def gaps(self, trace) -> list:
        """Chip 0's idle intervals, clipped to the traced window."""
        return [(max(a, self.lo), min(b, self.hi)) for a, b in trace.gaps()
                if min(b, self.hi) > max(a, self.lo)]

    def label(self, a: int, b: int) -> str:
        """The innermost worker span (or a garbage collection or compile
        on any thread) that covers at least half of [a, b), else the one
        that covers most of it."""
        best, best_key = "no program span", None
        for r in self.records:
            if not (r.tid == self.worker and r.name != INBOX
                    or r.name in ANYWHERE):
                continue
            s, e = r.t0_ns + self.offset, r.t1_ns + self.offset
            cover = max(0, min(b, e) - max(a, s))
            if not cover:
                continue
            key = (cover * 2 >= b - a, -(e - s) if cover * 2 >= b - a
                   else cover)
            if best_key is None or key > best_key:
                best, best_key = r.name, key
        return best


def _worker(recs) -> Optional[int]:
    counts: dict = {}
    for r in recs:
        if r.name == SEARCH:
            counts[r.tid] = counts.get(r.tid, 0) + 1
    return max(counts, key=counts.get) if counts else None


def joined(run) -> Optional[Joined]:
    if "_span_joined" in vars(run):
        return run._span_joined
    run._span_joined = None
    recs = records(run)
    if recs is None or run.trace is None:
        return None
    worker = _worker(recs)
    spans = [r for r in recs if r.name == SEARCH and r.tid == worker]
    anns = sorted((s.start, s.end) for s in run.trace.host
                  if s.name == instrument.SEARCH)
    if not spans or len(anns) != len(spans):
        print(f"[spans] no clock join: {len(spans)} {SEARCH} spans, "
              f"{len(anns)} {instrument.SEARCH} annotations")
        return None
    offsets = [a - r.t0_ns for (a, _), r in zip(anns, spans)]
    offset = int(statistics.median(offsets))
    worst = max(max(a - (r.t0_ns + offset), (r.t1_ns + offset) - b, 0)
                for (a, b), r in zip(anns, spans))
    q = statistics.quantiles(offsets, n=4) if len(offsets) > 1 \
        else [offset] * 3
    w = run.window
    j = Joined(recs, worker, offset, int(w.t0 * 1e9) + offset,
               int(w.drained_at * 1e9) + offset, len(spans), worst / 1e3,
               (q[2] - q[0]) / 1e3)
    run._span_joined = j
    _describe(j, run.trace)
    return j


def _describe(j: Joined, trace) -> None:
    gaps = j.gaps(trace)
    idle = sum(b - a for a, b in gaps)
    under = overlap(gaps, j.on_trace())
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:5]
    named = ", ".join(f"{j.label(a, b)} {(b - a) / 1e9:.6f} s"
                      for a, b in longest)
    print(f"[spans] {len(j.records)} records in the window; clock join: "
          f"{j.pairs} pairs, offset {j.offset} ns, spread {j.spread_us:.3f} "
          f"us, worst reach past an annotation {j.worst_us:.3f} us; chip-0 "
          f"idle {idle / 1e9:.6f} s, {100.0 * under / max(idle, 1):.3f}% "
          f"under worker spans; longest idle gaps: {named}")
