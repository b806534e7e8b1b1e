"""Drives one measured window through ``Deployment.submit`` / ``drain``.

The program keeps no per-token times and no lock, so the benchmark stamps
tokens itself: every request it submits gets an ``out_tokens`` list whose
``append`` records ``time.perf_counter()`` (the engine's own clock).  The
first stamp is the request's first token.

Requests are submitted on the serving thread, between engine steps: the
engine calls ``step_times.append`` after every decode step, and the
benchmark's list runs the generator there (and when the engine is idle,
after sleeping until the next due time).  The engine admits new requests
only at the top of its loop, after that call, so a request due during a
step is admitted exactly when a generator thread would have had it
admitted; the generator's lateness (submit time minus due time) is
reported.  Open arrivals are timed from their due time, never from when
they were submitted.

The window closes at its end: the next step raises ``WindowClosed`` out of
``drain``; requests still in flight are not finished at the run's cost.
"""
from __future__ import annotations

import dataclasses
import time

import jax


class WindowClosed(Exception):
    pass


class Stamped(list):
    """A token list that records when each token was appended."""

    def __init__(self):
        super().__init__()
        self.times = []

    def append(self, token):
        self.times.append(time.perf_counter())
        super().append(token)


@dataclasses.dataclass
class Sent:
    req: object                 # traffic.Request
    rid: int
    due: float                  # absolute perf_counter time it was due
    sent: float                 # absolute perf_counter time submitted
    handle: object              # the engine's Request


class StepHook(list):
    """Stands in for the engine's ``step_times``: runs ``fn`` after each
    decode step."""

    def __init__(self, fn):
        super().__init__()
        self.fn = fn

    def append(self, entry):
        super().append(entry)
        self.fn(entry)


class Window:
    def __init__(self, dep, requests: list, *, open_loop: bool,
                 queue_depth: int, seconds: float, trace_dir=None,
                 trace_seconds: float = 0.0):
        self.dep = dep
        self.requests = requests
        self.open_loop = open_loop
        self.queue_depth = queue_depth
        self.seconds = seconds
        self.sent: list = []
        self.steps: list = []       # (t_end, dt, {variant: rows}, [ctx])
        self.live: list = []
        self.trace_dir = trace_dir
        self.trace_seconds = trace_seconds
        self.trace_t = None         # (start, stop) on perf_counter
        self._traced = None         # the bench.traced span, while open
        self._next = 0

    # -- generator ---------------------------------------------------------
    def _submit(self, now: float) -> None:
        r = self.requests[self._next]
        self._next += 1
        with jax.profiler.TraceAnnotation("bench.submit"):
            rid = self.dep.submit(r.prompt, variant=r.variant,
                                  max_new_tokens=r.max_new_tokens)
            handle = self.dep.engine.request(rid)
            handle.out_tokens = Stamped()
        s = Sent(req=r, rid=rid, due=self.t0 + r.due, sent=now,
                 handle=handle)
        self.sent.append(s)
        self.live.append(s)

    def _pump(self) -> None:
        n = len(self.requests)
        now = time.perf_counter()
        if self.open_loop:
            while self._next < n and self.t0 + self.requests[
                    self._next].due <= now:
                self._submit(now)
        else:
            while self._next < n and self.dep.pending() < self.queue_depth:
                self._submit(now)

    # -- per decode step -----------------------------------------------------
    def _on_step(self, entry) -> None:
        with jax.profiler.TraceAnnotation("bench.step_hook"):
            t_end, dt = entry[0], entry[1]
            rows, ctxs = {}, []
            still = []
            for s in self.live:
                st = s.handle.status
                if st == "running":
                    rows[s.req.variant] = rows.get(s.req.variant, 0) + 1
                    ctxs.append(len(s.req.prompt) + len(s.handle.out_tokens))
                if st not in ("done", "failed"):
                    still.append(s)
            self.live = still
            self.steps.append((t_end, dt, rows, ctxs))
            self._pump()
            now = time.perf_counter()
            self._maybe_trace(now)
        if now >= self.t_end:
            raise WindowClosed

    def _maybe_trace(self, now: float) -> None:
        if (self.trace_dir is not None and self.trace_t is None
                and now >= self.t_end - self.trace_seconds):
            jax.profiler.start_trace(self.trace_dir)
            self._traced = jax.profiler.TraceAnnotation("bench.traced")
            self._traced.__enter__()
            self.trace_t = [time.perf_counter(), None]

    # -- the window ----------------------------------------------------------
    def run(self, counters) -> dict:
        eng = self.dep.engine
        eng.record_step_times = True
        eng.step_times = StepHook(self._on_step)
        before = counters()
        self.t0 = time.perf_counter()
        self.t_end = self.t0 + self.seconds
        n = len(self.requests)
        while True:
            self._pump()
            now = time.perf_counter()
            self._maybe_trace(now)
            if now >= self.t_end:
                break
            if self.dep.pending() == 0 and self.dep.active() == 0:
                nxt = (self.t0 + self.requests[self._next].due
                       if self._next < n else self.t_end)
                with jax.profiler.TraceAnnotation("bench.sleep"):
                    time.sleep(max(0.0, min(nxt, self.t_end) - now))
                continue
            try:
                self.dep.drain()
            except WindowClosed:
                break
        closed = time.perf_counter()
        after = counters()
        if self.trace_t is not None:
            self._traced.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.trace_t[1] = closed
        eng.record_step_times = False
        return {"t0": self.t0, "t_end": self.t_end, "closed": closed,
                "before": before, "after": after}
