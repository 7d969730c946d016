"""Load generators: one thread, and `submit` (and, for a closed loop, `poll`)
is all it calls on the pool. An open loop sleeps to each request's due time
and never looks at the server; a closed loop keeps a fixed number of clients,
each sending its next request when its last completes. The generator notes
how late each submit ran, so a starved generator is not read as a fast
server."""
from __future__ import annotations

import threading

from benchmark.timing import clock


class Generator:
    """Drives ``traffic`` into ``loop`` from its own thread. After `join`,
    ``submitted`` maps a public request id to ``(request, due, sent)`` on
    the benchmark's clock, ``errors`` holds submits that raised."""

    def __init__(self, loop, traffic, *, t_start: float, t_end: float,
                 trace_stamp=None):
        self.loop = loop
        self.traffic = traffic
        self.t_start, self.t_end = t_start, t_end
        self.trace_stamp = trace_stamp      # callable -> (trace_id, parent)
        self.submitted: dict[int, tuple] = {}
        self.errors: list[tuple] = []
        self.completions: list = []
        self.done_event = threading.Event()
        self._stop = threading.Event()
        self.failure: BaseException | None = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-generator")

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self.done_event.set()

    def join(self, timeout: float | None = None) -> None:
        self._thread.join(timeout)

    def _run(self) -> None:
        try:
            if self.traffic.mix["loop"] == "open":
                self._run_open()
            else:
                self._run_closed()
        except BaseException as e:  # noqa: BLE001 - re-raised by the harness
            self.failure = e

    def _submit(self, req, due: float) -> None:
        kw = {}
        if req.temperature > 0.0:
            kw = {"temperature": req.temperature, "seed": req.index}
        if self.trace_stamp is not None:
            kw["trace"] = self.trace_stamp()
        sent = clock()
        try:
            rid = self.loop.submit(req.tokens, req.max_new, **kw)
        except ValueError as e:      # refused: counts as failed
            self.errors.append((req.index, due, repr(e)))
            return
        self.submitted[rid] = (req, due, sent)

    def _run_open(self) -> None:
        for req in self.traffic.requests:
            due = self.t_start + req.due_s
            if due >= self.t_end:
                break
            while True:
                wait = due - clock()
                if wait <= 0 or self._stop.is_set():
                    break
                # sleep to within a millisecond, then yield in short naps:
                # a coarse sleep can overshoot by the scheduler's quantum
                self._stop.wait(wait - 0.001 if wait > 0.002 else 0.0002)
            if self._stop.is_set():
                break
            self._submit(req, due)

    def _run_closed(self) -> None:
        pending = iter(self.traffic.requests)
        for _ in range(self.traffic.clients):
            self._submit(next(pending), clock())
        while not self._stop.is_set():
            self.done_event.wait(0.05)
            self.done_event.clear()
            got = self.loop.poll()
            self.completions.extend(got)
            now = clock()
            if now >= self.t_end:
                break
            for _ in got:
                req = next(pending, None)
                if req is None:
                    raise RuntimeError(
                        "closed loop ran out of requests: raise "
                        "max_rps_hint in the traffic file")
                self._submit(req, now)
