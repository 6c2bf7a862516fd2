"""The reduction from profiler events to numbers."""
import json
import pathlib

import pytest

from bench import trace_reduce as tr
from bench import walk

DEV, HOST = "/device:TPU:0", "/host:CPU"
DATA = pathlib.Path(__file__).resolve().parent / "data"


def ev(plane, name, start, dur, line=tr.OPS_LINE, op=""):
    return tr.Event(plane, line, name, float(start), float(dur), op)


SYNTH = [
    ev(DEV, "fusion.1", 0, 100),
    ev(DEV, '%walk.1 = f32[8] custom-call(s32[1] %fusion.1), '
       'custom_call_target="tpu_custom_call"', 50, 250),  # overlaps
    ev(DEV, "fusion.1", 1000, 100),
    ev(DEV, "copy.2", 1500, 500),
    ev(DEV, "fusion.1", 5000, 1000),
    ev(DEV, "step", 0, 9000, line="XLA Modules"),  # not an op line
    ev(HOST, "PjitFunction(fused_segment_topk)", 320, 600, line="t1"),
    ev(HOST, "merge", 2100, 2800, line="t2"),
    ev(HOST, "whole run", 0, 5e9, line="t3"),
]


def test_busy_is_the_union_of_op_intervals():
    busy, merged = tr.busy(SYNTH)
    assert merged == [[0, 300], [1000, 1100], [1500, 2000], [5000, 6000]]
    assert busy == pytest.approx((300 + 100 + 500 + 1000) * 1e-9)


def test_kernel_time_matches_name_or_op():
    assert tr.kernel_seconds(SYNTH, walk.PATTERN) == pytest.approx(250e-9)
    assert tr.kernel_seconds(SYNTH, r"^copy") == pytest.approx(500e-9)


def test_breakdown_lists_ops_and_named_gaps():
    top = tr.top_ops(SYNTH)
    assert top[0] == ["fusion.1", pytest.approx(1200e-9)]
    assert ["%walk.1 [pallas]", pytest.approx(250e-9)] in top
    gaps = tr.idle_gaps(SYNTH)
    assert gaps[0] == ["host: merge", pytest.approx(3000e-9)]
    assert gaps[1] == ["host: PjitFunction(fused_segment_topk)",
                       pytest.approx(700e-9)]
    assert len(gaps) == 3


def test_a_trace_without_a_device_reads_nothing():
    host_only = [e for e in SYNTH if e.plane == HOST]
    assert tr.busy(host_only) == (0.0, [])
    assert tr.kernel_seconds(host_only, walk.PATTERN) == 0.0
    assert tr.idle_gaps(host_only) == []


def test_events_parse_a_recorded_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench-span"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    evs = tr.events(tr.find_xplane(tmp_path))
    names = {e.name for e in evs}
    assert "bench-span" in names
    assert all(e.dur_ns >= 0 for e in evs)


def _recorded():
    raw = json.loads((DATA / "tpu_trace_slice.json").read_text())
    return [tr.Event(p, line, name, float(s), float(d), "")
            for p, line, name, s, d in raw["events"]]


def test_reduction_of_a_recorded_tpu_trace():
    evs = _recorded()
    ops = tr.device_ops(evs)
    busy, merged = tr.busy(evs)
    # the union never exceeds the span it covers, nor the summed op time
    span = (max(e.start_ns + e.dur_ns for e in ops)
            - min(e.start_ns for e in ops)) * 1e-9
    assert 0 < busy <= span
    assert busy <= sum(e.dur_ns for e in ops) * 1e-9
    assert all(a[1] < b[0] for a, b in zip(merged, merged[1:]))
    walk_ops = [e for e in ops if "tpu_custom_call" in e.name]
    assert len(walk_ops) == 1
    assert tr.kernel_seconds(evs, walk.PATTERN) == pytest.approx(
        walk_ops[0].dur_ns * 1e-9)
    top = dict(tr.top_ops(evs))
    assert walk_ops[0].name.split(" = ")[0] + " [pallas]" in top
    gaps = tr.idle_gaps(evs)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)
    assert any(g[0].startswith("host: ") for g in gaps)
