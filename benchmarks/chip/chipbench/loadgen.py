"""The load generator: one thread that submits on schedule and records each
completion in the future's callback.  No thread per request.

Open loop: request ``i`` is due at ``t0 + due[i]`` whatever happened to the
ones before it, and its answer time runs from when it was due, so a stall of
the server is charged to every request that had to wait behind it.  Closed
loop: ``in_flight`` requests are kept outstanding and each completion releases
the next; answer time runs from the submit.  Either way the generator records
how late it sent each request against its own plan (``sent - due``), so a
starved generator is not read as a fast server.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np


class LoadGenerator:
    """``submit(i)`` returns a ``concurrent.futures.Future``; ``rejected`` is
    the exception type that means the server refused the request.  Times are
    ``time.perf_counter()`` seconds."""

    def __init__(self, schedule, submit, *, rejected: type[BaseException], span=None):
        self.schedule = schedule
        self._submit = submit
        self._rejected = rejected
        self._span = span  # context-manager factory: span(name)
        n = schedule.n
        self.due = np.full(n, np.nan)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.status = np.zeros(n, np.int8)  # 0 not sent, 1 in flight, 2 answered, 3 rejected, 4 failed
        self.results: dict[int, object] = {}
        self.issued = 0
        self._stop = threading.Event()
        self._released: queue.SimpleQueue = queue.SimpleQueue()
        self._thread = threading.Thread(target=self._run, name="bench-loadgen", daemon=True)
        self.t0: float | None = None

    # ------------------------------------------------------------- lifecycle
    def start(self) -> float:
        self.t0 = time.perf_counter()
        self._thread.start()
        return self.t0

    def stop(self) -> None:
        """Stop issuing; requests in flight still complete."""
        self._stop.set()
        self._released.put(None)
        self._thread.join()

    def wait_for(self, indices, *, until: float) -> None:
        """Block until every request in ``indices`` has left the in-flight
        state or ``until`` (perf_counter) passes."""
        while time.perf_counter() < until:
            if not np.any(self.status[indices] == 1):
                return
            time.sleep(0.005)

    # --------------------------------------------------------------- sending
    def _on_done(self, i: int, fut) -> None:
        now = time.perf_counter()
        err = fut.exception()
        if err is None:
            self.results[i] = fut.result()
            self.done[i] = now
            self.status[i] = 2
        else:
            self.done[i] = now
            self.status[i] = 4
        if self.schedule.loop == "closed":
            self._released.put(i)

    def _send(self, i: int, due: float) -> None:
        self.due[i] = due
        self.sent[i] = time.perf_counter()
        self.status[i] = 1
        self.issued = i + 1
        try:
            if self._span is not None:
                with self._span("bench.submit"):
                    fut = self._submit(i)
            else:
                fut = self._submit(i)
        except self._rejected:
            self.done[i] = time.perf_counter()
            self.status[i] = 3
            if self.schedule.loop == "closed":
                self._released.put(i)
            return
        fut.add_done_callback(lambda f, i=i: self._on_done(i, f))

    def _run(self) -> None:
        if self.schedule.loop == "open":
            self._run_open()
        else:
            self._run_closed()

    def _run_open(self) -> None:
        for i, offset in enumerate(self.schedule.due):
            due = self.t0 + float(offset)
            while True:
                wait = due - time.perf_counter()
                if wait <= 0 or self._stop.is_set():
                    break
                # sleep most of the way, then yield in short steps
                time.sleep(wait - 0.0005 if wait > 0.001 else 0)
            if self._stop.is_set():
                return
            self._send(i, due)

    def _run_closed(self) -> None:
        i = 0
        for _ in range(min(self.schedule.in_flight, self.schedule.n)):
            self._send(i, time.perf_counter())
            i += 1
        while i < self.schedule.n:
            released = self._released.get()
            if released is None or self._stop.is_set():
                return
            self._send(i, time.perf_counter())
            i += 1
