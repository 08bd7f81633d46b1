"""Drive a deployment's ``ThreadedServer`` through one measured window.

The server's own worker thread serves. A closed loop keeps ``clients``
requests in flight, sending the next as each returns. An open loop sends
each request from the calling thread when it is due, whatever the server
does. Every request due in the window is waited for, up to
``DRAIN_S`` past the close, so a late answer is timed and compared rather
than lost; one that never comes counts as missing.

Times are ``time.perf_counter`` seconds. Latency runs from when a request
was due (open loop) or sent (closed loop) to when its future resolved.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Optional

import numpy as np

from harness import deploy

DRAIN_S = 60.0  # wait past the window's close for answers still due
PREBUILT = 1 << 13  # closed-loop requests built before the window


@dataclasses.dataclass
class Record:
    pool_idx: int
    due: float  # when the request was due (open) or sent (closed)
    sent: float = 0.0
    done: Optional[float] = None
    response: object = None  # Completed | Rejected
    error: Optional[BaseException] = None


@dataclasses.dataclass
class Window:
    records: list
    t0: float  # window opens
    t1: float  # window closes
    counters: dict  # ServerStats counters read at t0 and t1, differenced
    lateness_s: np.ndarray  # open loop: sent - due per request
    drained_at: float = 0.0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


_COUNTERS = ("batches", "real_rows", "bucket_rows", "service_wall_s",
             "completed", "submitted", "rejected")


def _counters(stats) -> dict:
    snap = {k: getattr(stats, k) for k in _COUNTERS}
    snap["retraces"] = stats.snapshot()["retraces"]
    return snap


def run_window(dep: deploy.Deployment, corpus, sched, seconds: float,
               before: Callable[[], None] = lambda: None,
               after: Callable[[], None] = lambda: None) -> Window:
    """``before`` runs just before the window opens and ``after`` once every
    answer is in (the traced run starts and stops the profiler there).

    Requests are built before the window. A closed-loop caller sends its
    next request from the answer's callback, on the thread that resolved
    it: every caller answered by one batch is back in the server's inbox
    before the server looks for its next batch, as callers that reply at
    once would be (a separate sender thread would wait for the interpreter
    lock longer than the batcher's 2 ms window)."""
    from repro.serve import ThreadedServer

    srv = ThreadedServer(dep.engine, deploy.registry(dep),
                         window_ms=dep.window_ms, buckets=dep.buckets,
                         result_cache=deploy.new_result_cache(dep))
    closed = sched.loop == "closed"
    n = min(len(sched.queries), PREBUILT) if closed else len(sched.due)
    built = [deploy.make_request(dep, corpus, int(sched.queries[i]), i)
             for i in range(n)]
    records: list[Record] = []
    lock = threading.Lock()
    settled, closing = [0], [False]
    all_in = threading.Event()
    t0 = t1 = float("inf")

    def send(due: float) -> None:
        with lock:
            i = len(records)
            rec = Record(pool_idx=int(sched.queries[i]), due=due)
            records.append(rec)
        req = built[i] if i < len(built) else deploy.make_request(
            dep, corpus, rec.pool_idx, i)
        rec.sent = time.perf_counter()
        srv.submit(req).add_done_callback(lambda f: answered(rec, f))

    def answered(rec: Record, fut) -> None:
        rec.done = time.perf_counter()
        if fut.exception() is not None:
            rec.error = fut.exception()
        else:
            rec.response = fut.result()
        if closed and rec.done < t1 and rec.error is None \
                and rec.response.ok:
            send(time.perf_counter())
        with lock:
            settled[0] += 1
            if closing[0] and settled[0] == len(records):
                all_in.set()

    with srv:
        before()
        c0 = _counters(srv.stats)
        t0 = time.perf_counter()
        t1 = t0 + seconds
        lateness = []
        if closed:
            for _ in range(sched.clients):
                send(time.perf_counter())
        else:
            for off in sched.due:
                due = t0 + off
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                send(due)
                lateness.append(records[-1].sent - due)
        time.sleep(max(0.0, t1 - time.perf_counter()))
        c1 = _counters(srv.stats)
        with lock:
            closing[0] = True
            if settled[0] == len(records):
                all_in.set()
        all_in.wait(DRAIN_S)
        drained = time.perf_counter()
        after()
    counters = {k: c1[k] - c0[k] for k in c0}
    return Window(list(records), t0, t1, counters, np.asarray(lateness),
                  drained)
