"""The measured window: an open-loop generator against the live server.

A generator thread submits each request at its due time, whatever the
server is doing, so a stall makes later requests wait.  Each request is
timed from its due time: the generator's lateness (due time to
``submit``) plus the server's own ``Response.latency_us``.  After the
window closes no more requests are sent; those still in flight are
waited for (at most ``wait_s`` past the close) and keep their full
latency.

When the generator is held more than ``stall_s`` past a due time, a
watchdog (``faulthandler``, which runs without the interpreter lock)
writes every thread's stack to ``stall_file``, a few times a window at
most: the stacks name what held the generator up.
"""
from __future__ import annotations

import dataclasses
import faulthandler
import sys
import threading
import time

import numpy as np


@dataclasses.dataclass
class Record:
    due: np.ndarray        # f64[N] due time, perf_counter seconds
    lateness: np.ndarray   # f64[N] submit - due
    latency: np.ndarray    # f64[N] answer - due
    done_at: np.ndarray    # f64[N] perf_counter at the answer (nan: none)
    cached: np.ndarray     # bool[N]
    ok: np.ndarray         # bool[N]
    responses: list        # Response or None
    start: float           # the window's start, perf_counter seconds
    seconds: float
    stalls: int = 0        # stack dumps the generator's watchdog wrote
    stalls: int = 0        # stack dumps the generator's watchdog wrote


class CompileCounter:
    """Counts JAX tracings and backend compilations while armed, and
    tallies, while not armed (the set-up), the seconds spent compiling
    and the persistent cache's hits and misses."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        self.setup = {"compile_s": 0.0, "cache_hits": 0,
                      "cache_misses": 0, "cache_read_s": 0.0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if self.armed:
            self.count += event in self.EVENTS
        elif event == self.EVENTS[1]:
            self.setup["compile_s"] += duration
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.setup["cache_read_s"] += duration

    def _on_event(self, event: str, **_kw) -> None:
        if not self.armed and event.startswith("/jax/compilation_cache/"):
            key = event.rsplit("/", 1)[1]
            if key in self.setup:
                self.setup[key] += 1


class GcPauses:
    """Counts the interpreter's garbage collections and their longest
    pause while open."""

    def __init__(self):
        import gc

        self.count, self.longest, self._t0 = 0, 0.0, 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.count += 1
            self.longest = max(self.longest, time.perf_counter() - self._t0)

    def close(self) -> None:
        import gc

        gc.callbacks.remove(self._on)


def drive(server, rows: list, due: np.ndarray, seconds: float,
          wait_s: float = 60.0, lead_s: float = 0.05, stall_s: float = 0.3,
          stall_file=None, max_dumps: int = 3) -> Record:
    """Serve ``rows`` at ``due`` (seconds from the window's start)."""
    n = len(rows)
    tickets: list = [None] * n
    start = time.perf_counter() + lead_s
    due_abs = start + np.asarray(due, np.float64)
    out = sys.stderr if stall_file is None else stall_file
    dumps = [0]

    def send():
        for i in range(n):
            wait = due_abs[i] - time.perf_counter()
            watch = dumps[0] < max_dumps
            if watch:
                armed = time.perf_counter()
                limit = max(wait, 0.0) + stall_s
                faulthandler.dump_traceback_later(limit, file=out)
            if wait > 0:
                time.sleep(wait)
            tickets[i] = server.submit(rows[i])
            if watch:
                faulthandler.cancel_dump_traceback_later()
                if time.perf_counter() - armed > limit:
                    dumps[0] += 1

    gen = threading.Thread(target=send, name="open-loop", daemon=True)
    gen.start()
    gen.join(timeout=seconds + wait_s + lead_s)
    if gen.is_alive():
        raise RuntimeError("the generator did not finish its schedule")
    deadline = start + seconds + wait_s
    responses = []
    for t in tickets:
        try:
            responses.append(t.result(
                timeout=max(deadline - time.perf_counter(), 0.0)))
        except TimeoutError:
            responses.append(None)
    lateness = np.array([t.t_submit for t in tickets]) - due_abs
    # a request never answered counts as answered when the wait ended
    latency = deadline - due_abs
    done_at = np.full(n, np.nan)
    cached = np.zeros(n, bool)
    ok = np.zeros(n, bool)
    for i, (t, r) in enumerate(zip(tickets, responses)):
        if r is None or not r.ok:
            continue
        ok[i] = True
        cached[i] = bool(r.cached)
        done_at[i] = t.t_submit + r.latency_us * 1e-6
        latency[i] = done_at[i] - due_abs[i]
    return Record(due=due_abs, lateness=lateness, latency=latency,
                  done_at=done_at, cached=cached, ok=ok,
                  responses=responses, start=start, seconds=seconds,
                  stalls=dumps[0])


def end_to_end(rec: Record) -> dict:
    """qps over the window; latency percentiles over every request due
    in it."""
    from bench.stats import percentile

    lat_ms = rec.latency * 1e3
    answered = np.sum(rec.ok & (rec.done_at <= rec.start + rec.seconds))
    return {"qps": float(answered / rec.seconds),
            "latency_p50_ms": percentile(lat_ms, 50),
            "latency_p95_ms": percentile(lat_ms, 95)}
