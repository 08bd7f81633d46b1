"""Spans of the serve path: one name per phase, written to three sinks.

``span(name)`` is the one entry point. Every span, sampled or not, writes:

(a) **a profiler annotation** ``stable.<name>``
    (``jax.profiler.TraceAnnotation``; jax is imported on the first span,
    so this module imports without it). With a profiler session on, the
    phase lies on the device trace's clock, and a Perfetto or TensorBoard
    view puts each idle gap of the chip under a program phase;
(b) **one record in a bounded process-wide ring**, ``recorder()``:
    ``(name, thread id, t0_ns, t1_ns)`` on ``time.perf_counter_ns``,
    preallocated arrays, oldest overwritten and counted as ``dropped``;
(c) **a child in the sampled request's attribute tree**, when a sampled
    ``Span`` is current on this thread. ``Tracer`` sampling decides only
    which requests carry attributes; the ``with span(...) as sp`` target is
    that child, or the falsy ``NOOP_SPAN`` when nothing is sampled.

Design constraints, in priority order:

1. **Bounded cost.** Sinks (a) and (b) are always on, so a span is not
   free: one small object, an annotation (≈ 0.6–0.8 µs with no profiler
   session) and a ring write. The serve path keeps the count bounded: at
   most one span per phase per batch, and at most three records per
   request (``serve.inbox``, ``serve.idle``, ``serve.enqueue``). The ring
   is preallocated, so spans retain no memory. Cost per span, per batch
   and per request: ``benchmarks/span_cost.py``.
2. **Context flows implicitly.**  A real ``Span`` pushes itself onto a
   thread-local stack in ``__enter__`` and pops in ``__exit__``; nested
   instrumentation (engine → executor → partitioned searcher) finds its
   parent via ``current()`` without any plumbing through call signatures.
3. **Sampling is deterministic.**  ``Tracer(sample_every=N)`` samples every
   N-th ``should_sample()`` call via a counter, so tests and the bench can
   force exactly which request is traced (N=1 → all, N=0 → none).

Process-wide events are recorded here as well, once per process:
``gc.callbacks`` time every garbage collection as a ``gc`` span (attribute
``gen``) and feed the ``process_gc_pause_ms`` histogram; a
``jax.monitoring`` listener records each XLA backend compile as an
``xla.compile`` record ``[now - duration, now]`` and counts it in
``xla_compiles_total``. ``ServerStats`` adopts these instruments (and
``obs_spans_dropped_total``) into its registry.

Tree timestamps are ``time.perf_counter()`` seconds; exporters convert.
Spans support *synthetic* children with explicit timing (``add``) for
phases measured in a different clock domain (e.g. the serve loop's
virtual-clock queue wait), which keeps the decomposition invariant — root
duration = sum of direct children — exact by construction.
"""
from __future__ import annotations

import gc
import itertools
import threading
import time
from array import array
from typing import Dict, List, NamedTuple, Optional

from .registry import LATENCY_MS_BOUNDS, Counter, Histogram

__all__ = [
    "NOOP_SPAN", "Record", "Recorder", "Span", "Trace", "Tracer", "Window",
    "current", "install", "process_instruments", "recorder", "span",
]

_tls = threading.local()
# real Spans entered and not yet exited, on every thread: while it is 0 a
# span skips the thread-local lookup for a sampled parent
_sampled_open = 0
_sampled_lock = threading.Lock()
_now_ns = time.perf_counter_ns
_ident = threading.get_ident

#: Ring capacity: ≈ 131 s of the serve path at 1,000 records/s.
RING_CAPACITY = 1 << 17
ANNOTATION_PREFIX = "stable."


class _NoopSpan:
    """Falsy do-nothing stand-in for a Span; a single shared instance is
    returned from every trace entry point when tracing is off or the
    request was not sampled.  Every method returns ``self`` so chained
    instrumentation (``span("x").set("k", v)``) stays allocation-free."""

    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def span(self, name: str) -> "_NoopSpan":
        return self

    def set(self, key: str, value) -> "_NoopSpan":
        return self

    def add(self, name: str, t0: float, duration: float) -> "_NoopSpan":
        return self


NOOP_SPAN = _NoopSpan()


def current():
    """The innermost active span on this thread, or ``NOOP_SPAN``."""
    stack = getattr(_tls, "stack", None)
    if stack:
        return stack[-1]
    return NOOP_SPAN


# ---------------------------------------------------------------------------
# The flat ring
# ---------------------------------------------------------------------------


class Record(NamedTuple):
    name: str
    tid: int  # threading.get_ident() of the recording thread
    t0_ns: int  # time.perf_counter_ns
    t1_ns: int


class Window(NamedTuple):
    """Records overlapping ``[t0_ns, t1_ns]``, sorted by start.
    ``complete`` is False when the ring overwrote a record that closed at
    or after ``t0_ns``: the window then lacks records."""

    records: List[Record]
    complete: bool


class Recorder:
    """Bounded ring of finished spans in four preallocated arrays.

    A write claims its slot from an ``itertools.count`` (atomic under the
    GIL, no lock) and makes four array stores; when the ring is full it
    overwrites the oldest record and counts it in ``dropped``. Records are
    written as spans close, so the ring is ordered by close time. Read
    while spans are closing on other threads, a record being written at
    that instant may come out torn; readers read a window after it ends."""

    def __init__(self, capacity: int = RING_CAPACITY):
        if capacity <= 0 or capacity & (capacity - 1):
            raise ValueError("capacity must be a power of two")
        self.capacity = capacity
        self._mask = capacity - 1
        self._seq = itertools.count()
        self._lock = threading.Lock()  # name interning only
        self._name = array("H", bytes(2 * capacity))
        self._tid = array("Q", bytes(8 * capacity))
        self._t0 = array("q", bytes(8 * capacity))
        self._t1 = array("q", bytes(8 * capacity))
        self._names: List[str] = []
        self._ids: Dict[str, int] = {}

    def name_id(self, name: str) -> int:
        i = self._ids.get(name)
        if i is None:
            with self._lock:
                i = self._ids.get(name)
                if i is None:
                    i = self._ids[name] = len(self._names)
                    self._names.append(name)
        return i

    def record(
        self, name: str, t0_ns: int, t1_ns: int, tid: Optional[int] = None
    ) -> None:
        """Write an interval measured elsewhere (a wait that began on
        another thread, a compile reported after the fact)."""
        i = next(self._seq) & self._mask
        self._name[i] = self.name_id(name)
        self._tid[i] = _ident() if tid is None else tid
        self._t0[i] = t0_ns
        self._t1[i] = t1_ns

    @property
    def written(self) -> int:
        # repr is "count(<next value>)": reads the counter without a claim
        return int(repr(self._seq)[6:-1])

    @property
    def dropped(self) -> int:
        return max(0, self.written - self.capacity)

    def between(self, t0_ns: int, t1_ns: int) -> Window:
        n, cap, mask = self.written, self.capacity, self._mask
        cols = (self._name[:], self._tid[:], self._t0[:], self._t1[:])
        names = list(self._names)
        first = max(0, n - cap)  # the oldest record still held
        order = [(first + j) & mask for j in range(min(n, cap))]
        nm, tid, a, b = cols
        out = [Record(names[nm[i]], tid[i], a[i], b[i]) for i in order
               if b[i] >= t0_ns and a[i] <= t1_ns]
        out.sort(key=lambda r: r.t0_ns)
        complete = n <= cap or b[order[0]] <= t0_ns
        return Window(out, complete)


_RING = Recorder()


def recorder() -> Recorder:
    """The process-wide span ring."""
    return _RING


# ---------------------------------------------------------------------------
# Process-wide instruments and hooks
# ---------------------------------------------------------------------------

GC_PAUSE_MS = Histogram(
    "process_gc_pause_ms", LATENCY_MS_BOUNDS,
    help="garbage-collection pauses of this process",
)
XLA_COMPILES = Counter(
    "xla_compiles_total", help="XLA backend compiles in this process"
)
SPANS_DROPPED = Counter(
    "obs_spans_dropped_total", help="span records the ring overwrote",
    source=lambda: _RING.dropped,
)


def process_instruments() -> tuple:
    return (GC_PAUSE_MS, XLA_COMPILES, SPANS_DROPPED)


#: jax.profiler.TraceAnnotation once loaded; False where jax is missing
_annotation = None
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_compile(event: str, duration_secs: float, **_) -> None:
    if event == _COMPILE_EVENT:
        t1 = _now_ns()
        _RING.record("xla.compile", t1 - int(duration_secs * 1e9), t1)
        XLA_COMPILES.inc()


def _load_jax():
    """Resolve the annotation class and hook XLA compiles, once."""
    global _annotation
    if _annotation is None:
        try:
            import jax.monitoring
            import jax.profiler
        except ImportError:
            _annotation = False
        else:
            jax.monitoring.register_event_duration_secs_listener(_on_compile)
            _annotation = jax.profiler.TraceAnnotation
    return _annotation


_gc_open: list = []


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        p = _Phase("gc", {"gen": info.get("generation")})
        p.__enter__()
        _gc_open.append(p)
    elif _gc_open:
        p = _gc_open.pop()
        p.__exit__(None, None, None)
        GC_PAUSE_MS.observe((p.t1 - p.t0) / 1e6)


def install() -> None:
    """Hook garbage collection and XLA compiles (idempotent). Runs at
    import for ``gc``; the compile listener needs jax and is added here or
    on the first span, whichever comes first."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    _load_jax()


# ---------------------------------------------------------------------------
# The entry point
# ---------------------------------------------------------------------------

_META: Dict[str, tuple] = {}  # name → (ring id, annotation label, name)


class _Phase:
    """One open span: annotation, ring record and (if sampled) tree node.
    Constructed by ``span(name)``; ``attrs`` (gc's ``gen``) go to the
    annotation and the tree node."""

    __slots__ = ("meta", "attrs", "ann", "node", "t0", "t1")

    def __init__(self, name: str, attrs: Optional[dict] = None):
        meta = _META.get(name)
        self.meta = meta if meta is not None else _meta(name)
        self.attrs = attrs

    def __enter__(self):
        ann = _annotation
        if ann is None and self.attrs is None:
            # the first span loads jax; a gc span (it carries attrs) never
            # imports, since a collection may start inside an import
            ann = _load_jax()
        # an annotation exists only while a profiler session records one
        if ann and ann.is_enabled():
            ann = ann(self.meta[1], **self.attrs) if self.attrs \
                else ann(self.meta[1])
            ann.__enter__()
        else:
            ann = None
        self.ann = ann
        if _sampled_open:
            node = current().span(self.meta[2])
            if node:
                node.__enter__()
                for k, v in (self.attrs or {}).items():
                    node.set(k, v)
        else:
            node = NOOP_SPAN
        self.node = node
        self.t0 = _now_ns()
        return node

    def __exit__(self, *exc) -> None:
        self.t1 = t1 = _now_ns()
        if self.node is not NOOP_SPAN:
            self.node.__exit__()
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        ring = _RING  # Recorder.record, inlined: one call less a span
        i = next(ring._seq) & ring._mask
        ring._name[i] = self.meta[0]
        ring._tid[i] = _ident()
        ring._t0[i] = self.t0
        ring._t1[i] = t1


#: ``span(name)`` opens a span of phase ``name`` (use as a context
#: manager). It is always annotated and recorded in the ring; the ``as``
#: target is the sampled tree's child, or ``NOOP_SPAN`` when no sampled
#: span is current::
#:
#:     with obs_trace.span("engine.plan") as sp:
#:         ...
#:         if sp:
#:             sp.set("backend", plan.backend)
span = _Phase


def _meta(name: str) -> tuple:
    meta = _META[name] = (
        _RING.name_id(name), ANNOTATION_PREFIX + name, name
    )
    return meta


class Span:
    """A named timed interval with attributes and children.  Real spans
    only exist on the sampled path, so clarity wins over nanosecond
    shaving here; the hot path never constructs one."""

    __slots__ = ("name", "t0", "t1", "attrs", "children")

    def __init__(self, name: str, t0: Optional[float] = None):
        self.name = name
        self.t0 = time.perf_counter() if t0 is None else t0
        self.t1: Optional[float] = None
        self.attrs: Dict[str, object] = {}
        self.children: List["Span"] = []

    # -- context / structure ----------------------------------------------

    def __bool__(self) -> bool:
        return True

    def __enter__(self) -> "Span":
        global _sampled_open
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self)
        with _sampled_lock:
            _sampled_open += 1
        return self

    def __exit__(self, *exc) -> None:
        global _sampled_open
        if self.t1 is None:
            self.t1 = time.perf_counter()
        stack = getattr(_tls, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        with _sampled_lock:
            _sampled_open = max(0, _sampled_open - 1)

    def span(self, name: str) -> "Span":
        child = Span(name)
        self.children.append(child)
        return child

    def add(self, name: str, t0: float, duration: float) -> "Span":
        """Attach an already-measured child (synthetic span) — used for
        phases timed in another clock domain, e.g. queue wait."""
        child = Span(name, t0=t0)
        child.t1 = t0 + duration
        self.children.append(child)
        return child

    def set(self, key: str, value) -> "Span":
        self.attrs[key] = value
        return self

    # -- reporting ---------------------------------------------------------

    @property
    def duration(self) -> float:
        if self.t1 is None:
            return 0.0
        return self.t1 - self.t0

    def find(self, name: str) -> Optional["Span"]:
        """Depth-first lookup by name (self included)."""
        if self.name == name:
            return self
        for c in self.children:
            hit = c.find(name)
            if hit is not None:
                return hit
        return None

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "t0": self.t0,
            "duration_ms": self.duration * 1e3,
            "attrs": dict(self.attrs),
            "children": [c.to_dict() for c in self.children],
        }


class Trace:
    """One sampled request: a root span plus an id for correlation."""

    __slots__ = ("trace_id", "root")

    def __init__(self, trace_id: int, root: Span):
        self.trace_id = trace_id
        self.root = root

    def to_dict(self) -> dict:
        return {"trace_id": self.trace_id, "root": self.root.to_dict()}


class Tracer:
    """Deterministic counter-based sampler + bounded store of finished
    traces.  ``sample_every=0`` disables sampling entirely (every entry
    point degrades to the no-op path); ``sample_every=1`` traces every
    request.  At most ``max_traces`` finished traces are retained
    (oldest dropped) — the store must not become the new unbounded list.
    """

    def __init__(self, sample_every: int = 0, max_traces: int = 256):
        self.sample_every = int(sample_every)
        self.max_traces = int(max_traces)
        self._lock = threading.Lock()
        self._tick = 0
        self._next_id = 0
        self._traces: List[Trace] = []

    @property
    def enabled(self) -> bool:
        return self.sample_every > 0

    def should_sample(self) -> bool:
        if self.sample_every <= 0:
            return False
        with self._lock:
            self._tick += 1
            return self._tick % self.sample_every == 0

    def start(self, name: str = "request") -> Trace:
        with self._lock:
            tid = self._next_id
            self._next_id += 1
        return Trace(tid, Span(name))

    def finish(self, trace: Trace) -> None:
        if trace.root.t1 is None:
            trace.root.t1 = time.perf_counter()
        with self._lock:
            self._traces.append(trace)
            if len(self._traces) > self.max_traces:
                del self._traces[: len(self._traces) - self.max_traces]

    def traces(self) -> List[Trace]:
        with self._lock:
            return list(self._traces)

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()


if _on_gc not in gc.callbacks:  # once per process; install() adds jax's hook
    gc.callbacks.append(_on_gc)
