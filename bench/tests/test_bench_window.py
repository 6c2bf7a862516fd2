"""The open-loop schedule: requests are timed from their due time."""
import threading
import time

import numpy as np

from bench import window


class _Ticket:
    def __init__(self, served_after: float):
        self.t_submit = time.perf_counter()
        self._resp = None
        self._done = threading.Event()
        threading.Timer(served_after, self._serve).start()

    def _serve(self):
        class R:
            ok = True
            cached = False
        R.latency_us = (time.perf_counter() - self.t_submit) * 1e6
        self._resp = R
        self._done.set()

    def result(self, timeout=None):
        if not self._done.wait(timeout):
            raise TimeoutError
        return self._resp


class _SlowSubmit:
    """Admission that blocks for ``stall`` on request ``stall_at``."""

    def __init__(self, stall_at: int, stall: float, service: float):
        self.stall_at, self.stall, self.service = stall_at, stall, service
        self.n = 0

    def submit(self, row):
        if self.n == self.stall_at:
            time.sleep(self.stall)
        self.n += 1
        return _Ticket(self.service)


def test_lateness_counts_in_latency():
    due = np.arange(10) * 0.02
    srv = _SlowSubmit(stall_at=3, stall=0.15, service=0.01)
    rec = window.drive(srv, [None] * 10, due, seconds=0.2, wait_s=5)
    assert rec.ok.all()
    # the stall makes request 3 and the ones due during it late ...
    assert rec.lateness[3] > 0.14 and rec.lateness[4] > 0.1
    assert rec.lateness[:3].max() < 0.05
    # ... and every latency runs from the due time
    assert np.all(rec.latency >= rec.lateness + 0.009)
    e2e = window.end_to_end(rec)
    assert e2e["latency_p95_ms"] > 100
    assert 0 < e2e["qps"] <= 10 / 0.2


def test_unanswered_requests_count_as_late_and_missing():
    class Never:
        def submit(self, row):
            class T:
                t_submit = time.perf_counter()

                def result(self, timeout=None):
                    time.sleep(min(timeout or 0, 0.05))
                    raise TimeoutError
            return T()

    rec = window.drive(Never(), [None] * 4, np.zeros(4) + 0.01,
                       seconds=0.05, wait_s=0.1)
    assert not rec.ok.any()
    e2e = window.end_to_end(rec)
    assert e2e["qps"] == 0 and e2e["latency_p50_ms"] >= 90


def test_a_held_generator_dumps_every_stack(tmp_path):
    due = np.arange(6) * 0.02
    srv = _SlowSubmit(stall_at=2, stall=0.5, service=0.01)
    with open(tmp_path / "stacks.txt", "w+") as out:
        rec = window.drive(srv, [None] * 6, due, seconds=0.12, wait_s=5,
                           stall_s=0.2, stall_file=out)
        out.seek(0)
        text = out.read()
    assert rec.stalls == 1 and rec.ok.all()
    # the dump names the call that held the generator
    assert "in submit" in text and "Thread" in text
