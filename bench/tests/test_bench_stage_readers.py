"""The per-layer readers of the program's stage spans, its compile
counter and its stage annotations, on synthetic inputs; and a traced
rehearsal that reports them."""
import types

import pytest

from bench import run
from bench import trace_reduce as tr
from bench.tests import tiny
from repro.obs.registry import MetricsRegistry
from repro.obs.trace import Span

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, name, start, dur, line=tr.OPS_LINE, op=""):
    return tr.Event(plane, line, name, float(start), float(dur), op)


def ctx_of(**kw):
    base = dict(trace_events=None, window_s=1.0, batches=[], server=None,
                spans=lambda name: [])
    return types.SimpleNamespace(**dict(base, **kw))


def span(name, us):
    return Span(name, t0=0.0).end(us * 1e-6)


@pytest.mark.parametrize("name,stage", [("dispatch_p50_ms", "dispatch"),
                                        ("device_wait_p50_ms",
                                         "device_wait")])
def test_stage_medians_read_their_spans(name, stage):
    read = run.load_reader(name)
    spans = {stage: [span(stage, us) for us in (3000, 1000, 2000)],
             "score": [span("score", 9000)]}
    assert read(ctx_of(spans=lambda n: spans.get(n, []))) == \
        pytest.approx(2.0)
    # a program without the span (the parent of this reader) reads none
    assert read(ctx_of()) is None


def test_compiles_per_batch_reads_the_server_counter():
    read = run.load_reader("compiles_per_batch")
    reg = MetricsRegistry()
    server = types.SimpleNamespace(registry=reg,
                                   metrics=types.SimpleNamespace(batches=4))
    assert read(ctx_of(server=server)) is None      # no counter: none
    reg.counter("serve_compiles").inc(6)
    assert read(ctx_of(server=server)) == 1.5
    server.metrics.batches = 0
    assert read(ctx_of(server=server)) is None


SYNTH = [
    ev(DEV, "fusion.1", 0, 100),
    ev(DEV, "fusion.2", 50, 150),           # overlaps fusion.1
    ev(DEV, "fusion.3", 500, 100),
    ev(DEV, "fusion.4", 2000, 100),
    ev(HOST, "serve.dispatch", 0, 300, line="worker"),
    ev(HOST, "PjitFunction(fused_segment_topk)", 10, 50, line="worker"),
    ev(HOST, "serve.device_wait", 300, 400, line="worker"),
    ev(HOST, "serve.merge", 650, 250, line="worker"),   # overlaps: once
    # idle 900-2000 lies outside every stage: waiting for requests
]


def test_idle_in_batch_counts_only_idle_time_inside_stages():
    read = run.load_reader("device_idle_in_batch_share")
    # stages cover 0-900; the device is busy 0-200 and 500-600 in it
    assert read(ctx_of(trace_events=SYNTH, window_s=1e-5)) == \
        pytest.approx(100.0 * 600e-9 / 1e-5)
    whole = run.load_reader("device_idle_share")(
        ctx_of(trace_events=SYNTH, window_s=1e-5))
    assert whole == pytest.approx(100.0 * (1 - 400e-9 / 1e-5))


def test_idle_in_batch_needs_stages_and_a_device():
    read = run.load_reader("device_idle_in_batch_share")
    no_stages = [e for e in SYNTH if not e.name.startswith("serve.")]
    no_device = [e for e in SYNTH if e.plane == HOST]
    assert read(ctx_of(trace_events=no_stages)) is None
    assert read(ctx_of(trace_events=no_device)) is None
    assert read(ctx_of()) is None


def test_traced_rehearsal_reads_the_stage_metrics():
    res = tiny.execute("paper-1m.table7", traced=True)
    assert res["correct"]
    m = res["metrics"]
    assert {"dispatch_p50_ms", "device_wait_p50_ms",
            "compiles_per_batch"} <= set(m)
    assert m["compiles_per_batch"]["value"] == 0
    assert m["dispatch_p50_ms"]["value"] > 0
    assert m["device_wait_p50_ms"]["value"] > 0
    # a device reading needs a device trace
    assert "device_idle_in_batch_share" not in m
