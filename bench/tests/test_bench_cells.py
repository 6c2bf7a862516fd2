"""Each cell's path through the harness, rehearsed on the CPU at a tiny
size, and the faults the comparison has to catch."""
import json
import subprocess
import sys

import numpy as np
import pytest

from bench import run
from bench.tests import tiny

CELLS = ["paper-1m.table7"]


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_reaches_a_passing_comparison(name):
    res = tiny.execute(name)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] == 12 and res["failed"] == 0
    m = res["metrics"]
    assert {"qps", "latency_p50_ms", "latency_p95_ms", "setup_s",
            "index_bytes_per_posting"} == set(m)
    assert all(v["value"] > 0 for v in m.values())
    assert 0 < res["checks"]["score_gap"]["value"] < 1e-5


def test_index_bytes_leave_out_garbage_from_before():
    import jax.numpy as jnp

    def per_posting():
        res = tiny.execute("paper-1m.table7", seconds=0.5)
        return res["metrics"]["index_bytes_per_posting"]["value"]

    class Cycle:
        pass

    clean = per_posting()
    # an array only the collector frees, as earlier work in a process
    # can leave; freed during the build it would be counted off
    c = Cycle()
    c.me, c.arr = c, jnp.ones((1 << 20,), jnp.float32)
    del c
    assert per_posting() == clean > 0


def test_traced_rehearsal_reads_the_program_spans():
    res = tiny.execute("paper-1m.table7", traced=True)
    assert res["correct"]
    m = res["metrics"]
    # spans exist on the CPU; device readings need a device trace
    assert {"queue_wait_p95_ms", "score_p50_ms", "merge_p50_ms"} <= set(m)
    assert "walk_roofline" not in m and "device_idle_share" not in m
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_the_measured_entry_refuses_a_cpu():
    out = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=run.ROOT, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0
    assert "refused" in out.stderr and not out.stdout.strip()


def _alter_scores(monkeypatch):
    from repro.core import live_index
    orig = live_index.LiveView.topk

    def topk(self, *a, **kw):
        r = orig(self, *a, **kw)
        s = np.asarray(r.scores).copy()
        s[:, 0] *= np.float32(1.001)
        return r._replace(scores=s)
    monkeypatch.setattr(live_index.LiveView, "topk", topk)


def _drop_half_batch(monkeypatch):
    from repro.core import live_index
    orig = live_index.LiveView.topk

    def topk(self, qh, *a, **kw):
        r = orig(self, qh, *a, **kw)
        ids = np.asarray(r.doc_ids).copy()
        s = np.asarray(r.scores).copy()
        filled = int(np.any(np.asarray(qh) != 0, axis=1).sum())
        ids[filled // 2:filled], s[filled // 2:filled] = -1, 0.0
        return r._replace(doc_ids=ids, scores=s)
    monkeypatch.setattr(live_index.LiveView, "topk", topk)


def _drop_a_segment(monkeypatch):
    from repro.kernels import ops
    orig = ops.fused_segment_topk
    calls = []

    def seg(*a, **kw):
        v, g, o = orig(*a, **kw)
        calls.append(1)
        if len(calls) % 2:   # every other segment loses its candidates
            return v * 0 - np.inf, g, o
        return v, g, o
    monkeypatch.setattr(ops, "fused_segment_topk", seg)


@pytest.mark.parametrize("fault,rate", [(_alter_scores, 12.0),
                                        (_drop_half_batch, 150.0),
                                        (_drop_a_segment, 12.0)])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault, rate):
    fault(monkeypatch)
    res = tiny.execute("paper-1m.table7", rate=rate)
    assert res["correct"] is False, res["checks"]
    json.dumps(res)
