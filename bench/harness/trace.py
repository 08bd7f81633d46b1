"""Profile a window and reduce the ``.xplane.pb`` to device busy time,
program and kernel time, and the host's doing in each idle gap.

Read with ``jax.profiler.ProfileData`` alone. What a TPU trace holds, as
read by hand from one recorded on a v5e (``bench/testdata``):

  * one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Modules``
    has one event per program run, named ``<jit name>(<fingerprint>)``,
    and whose line ``XLA Ops`` has one event per HLO instruction run,
    named by the instruction's text (``%fusion.9 = f32[8,512]{...} ...``).
    A ``while`` event encloses the events of its body;
  * a Pallas kernel is an op whose text has ``custom_call_target=
    "tpu_custom_call"``; the instruction's name, less its ``.<n>``
    suffix, is the kernel's (``adc_scan4_scores``), and its output shape
    leads the text;
  * host threads are lines of ``/host:CPU``; the benchmark's own
    ``TraceAnnotation`` spans (``instrument``) appear there by name;
  * device and host events share one clock (the program's run on the
    device lies inside the host span that launched it).

So the naming rule is: a program is the module name before ``(``, a kernel
is a ``tpu_custom_call`` op named as above.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import os
import re
import tempfile
import time
from typing import Optional

from harness import instrument

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULES, OPS = "XLA Modules", "XLA Ops"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
_OP = re.compile(r"%([\w.\-]+) = (\w+)\[([\d,]*)\]")


def program_of(module_event_name: str) -> str:
    return module_event_name.split("(", 1)[0]


def parse_op(text: str) -> tuple[str, tuple]:
    """(instruction name, output dims) of an ``XLA Ops`` event's text."""
    m = _OP.match(text)
    if not m:
        return text.split(" ", 1)[0].lstrip("%"), ()
    dims = tuple(int(d) for d in m.group(3).split(",") if d)
    return m.group(1), dims


def base_name(instr: str) -> str:
    return re.sub(r"\.\d+$", "", instr)


@dataclasses.dataclass
class Span:
    name: str
    start: int  # ns on the trace's clock
    end: int
    program: str = ""
    dims: tuple = ()
    self_ns: int = 0
    kernel: bool = False  # a Pallas kernel (tpu_custom_call)


def _union(spans) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted((s.start, s.end) for s in spans):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _self_times(ops: list[Span]) -> None:
    """Self time of nested op events (a ``while`` less its body)."""
    stack: list[Span] = []
    for op in sorted(ops, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= op.start:
            stack.pop()
        op.self_ns = op.end - op.start
        if stack and op.end <= stack[-1].end:
            stack[-1].self_ns -= op.end - op.start
        stack.append(op)


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    chips: int
    modules: list  # Span per program run
    ops: list  # Span per op run (program and self time filled in)
    host: list  # Span per benchmark annotation
    busy: list  # per chip: union of op intervals
    lo: int  # trace-clock bounds of the traced window
    hi: int
    _by_name: dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips used."""
        per = [sum(b - a for a, b in u) for u in self.busy]
        return sum(per) / max(len(per), 1) / 1e9

    def program(self, name: str) -> list:
        return [m for m in self.modules if m.program == name]

    def kernel(self, name: str) -> list:
        return [o for o in self.ops if o.kernel and o.name == name]

    def top_ops(self, n: int) -> list:
        tot: dict = collections.Counter()
        for o in self.ops:
            tot[f"{o.program}/{o.name}"] += o.self_ns
        return [[k, v / 1e9] for k, v in tot.most_common(n)]

    def gaps(self) -> list[tuple[int, int]]:
        """Idle intervals of chip 0 inside the traced window."""
        out, t = [], self.lo
        for a, b in (self.busy[0] if self.busy else []):
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if self.hi > t:
            out.append((t, self.hi))
        return out

    def _covered(self, name: str, a: int, b: int) -> int:
        """ns of [a, b) inside the benchmark's host spans called ``name``."""
        if name not in self._by_name:
            spans = sorted((s.start, s.end) for s in self.host
                           if s.name == name)
            longest = max((e - s for s, e in spans), default=0)
            self._by_name[name] = ([s for s, _ in spans], spans, longest)
        starts, spans, longest = self._by_name[name]
        i = bisect.bisect_left(starts, a - longest)
        j = bisect.bisect_left(starts, b)
        return sum(max(0, min(b, e) - max(a, s)) for s, e in spans[i:j])

    def label(self, a: int, b: int) -> str:
        """What the host did in [a, b): the benchmark's innermost engine
        span that covers at least half of it."""
        for name, what in ((instrument.PLAN, "engine.plan"),
                           (instrument.SEARCH, "engine.search dispatch")):
            if self._covered(name, a, b) * 2 >= b - a:
                return what
        return "outside the engine (batcher, futures, client)"

    def idle_gaps(self, n: int) -> list:
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:n]
        return [[self.label(a, b), (b - a) / 1e9] for a, b in gaps]

    def idle_by_label(self) -> dict:
        tot: dict = collections.Counter()
        for a, b in self.gaps():
            tot[self.label(a, b)] += (b - a) / 1e9
        return dict(tot)

    def describe(self) -> str:
        progs = collections.Counter()
        for m in self.modules:
            progs[m.program] += m.end - m.start
        top = ", ".join(f"{k} {v / 1e9:.3f} s" for k, v in progs.most_common(5))
        idle = ", ".join(f"{k} {v:.3f} s" for k, v in self.idle_by_label().items())
        return (f"window {self.window_s:.3f} s, busy {self.busy_s:.3f} s on "
                f"{self.chips} chip(s); programs: {top}; idle: {idle}")


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)


def reduce(path: str, window_s: Optional[float] = None) -> TraceSummary:
    """Summarise one ``.xplane.pb`` (or a gzipped one, ``.xplane.pb.gz``).
    ``window_s`` is the traced window's host-clock length; without it, the
    span of the trace's events."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    modules, ops, host, busy = [], [], [], []
    lo, hi = None, None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {l.name: l for l in plane.lines}
            mods = [Span(name, a, b, program_of(name))
                    for name, a, b in _events(lines[MODULES])] \
                if MODULES in lines else []
            chip_ops = []
            for text, a, b in (_events(lines[OPS]) if OPS in lines else ()):
                instr, dims = parse_op(text)
                chip_ops.append(Span(instr, a, b, dims=dims,
                                     kernel=KERNEL_MARK in text))
            _place(chip_ops, mods)
            _self_times(chip_ops)
            if chip_ops or mods:
                busy.append(_union(chip_ops or mods))
            modules += mods
            ops += chip_ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for name, a, b in _events(line):
                    lo = a if lo is None else min(lo, a)
                    hi = b if hi is None else max(hi, b)
                    if name.startswith("bench."):
                        host.append(Span(name, a, b))
    for m in modules:
        lo = m.start if lo is None else min(lo, m.start)
        hi = m.end if hi is None else max(hi, m.end)
    lo, hi = lo or 0, hi or 0
    return TraceSummary(
        window_s=window_s if window_s is not None else (hi - lo) / 1e9,
        chips=len(busy), modules=modules, ops=ops, host=host, busy=busy,
        lo=lo, hi=hi)


def _place(ops: list[Span], mods: list[Span]) -> None:
    """Name each op's program: the module run that encloses it."""
    mods = sorted(mods, key=lambda m: m.start)
    starts = [m.start for m in mods]
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.end <= mods[i].end:
            op.program = mods[i].program
        if op.kernel:
            op.name = base_name(op.name)


class Profiler:
    """The JAX profiler around one window, writing under ``TMPDIR``; the
    Python tracer stays off (it would slow the host it measures)."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.path = ""
        self.window_s = 0.0

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        self.path = sorted(found)[-1]
