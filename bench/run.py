"""Run one cell of the benchmark on the chip it is started on.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``configs``: its
``file`` holds the corpus, index plan, server shapes and the limits of
the comparison) and a traffic mix (``bench/traffic/<mix>.json``).  The
run builds the corpus from ``--seed``, stands the live index up through
the program's snapshot restore, warms the server's one batch shape, and
then serves an open-loop stream for ``--seconds`` through
``QueryServer`` with its worker thread running.  After the window a
sample of the answers is checked against the plain reference of the
configuration's ranking (``bench/rankings/<ranking>.py``), compared by
``bench/reference.py``.

With ``--trace 0`` the result's metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler with
every request traced, and the metrics are the cell's per-layer ones,
each read by ``bench/layers/<metric>.py``.  The last line of standard
output is the JSON result; the numbers compared, each with its limit,
end standard error.  Without a TPU, or on one missing from
``bench/peaks.json``, the run fails and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import resource  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class Refused(RuntimeError):
    """The run cannot measure: no result is printed."""


def say(**kw) -> None:
    print(json.dumps(kw, sort_keys=True, default=float), flush=True)


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve_cell(bench: dict, name: str, root: pathlib.Path = ROOT):
    """(cell, configuration entry, configuration file, mix file).
    Refuses a configuration whose ``ranking`` names no ranking file."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    from bench import traffic

    config = json.loads((root / entry["file"]).read_text())
    ranking = config.get("ranking")
    if not isinstance(ranking, str):
        raise Refused(f"{entry['file']} names no ranking: its \"ranking\" "
                      "is the name of a file bench/rankings/<name>.py")
    if not (root / "bench" / "rankings" / f"{ranking}.py").is_file():
        raise Refused(f"no ranking file bench/rankings/{ranking}.py")
    return cell, entry, config, traffic.load(cell["traffic"], root / "bench")


def metrics_for(bench: dict, cell: str, kind: str) -> list:
    """The cell's metric entries of ``end_to_end`` or ``per_layer``."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def _load(kind: str, name: str, root: pathlib.Path):
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: pathlib.Path = ROOT):
    return _load("layers", name, root).read


def load_ranking(name: str, root: pathlib.Path = ROOT):
    """The ranking module ``bench/rankings/<name>.py``: its plain
    ``Reference(corpus, needed_terms, precision)``, the arrays and meta
    keys it adds to the snapshot (``snapshot_part(corpus)``), and the
    index's set-up after restore (``after_restore(index)``)."""
    return _load("rankings", name, root)


def device_info(chips: int, peaks: dict) -> dict:
    """The devices JAX reports; refuses a run without enough TPUs or on
    a TPU the peaks table does not know."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devs)}")
    if devs[0].device_kind not in peaks["devices"]:
        raise Refused(f"device kind {devs[0].device_kind!r} is not in "
                      "bench/peaks.json")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class Context:
    """What a per-layer reader may look at after a traced window."""

    def __init__(self, server, record, trace_events, window_s, batches,
                 walk_bytes, peaks, k):
        self.server = server
        self.trace_events = trace_events
        self.window_s = window_s
        self.batches = batches
        self.walk_bytes = walk_bytes
        self.peaks = peaks
        self.k = k
        self._spans = {}
        for r in record.responses:
            for s in (r.trace.spans if r is not None and r.trace else ()):
                self._spans.setdefault(s.name, {})[id(s)] = s

    def spans(self, name: str) -> list:
        """Every distinct span of that name (a batch's spans are shared
        by its requests and counted once)."""
        return list(self._spans.get(name, {}).values())


def scored_batches(record, queries) -> list:
    """The term-id arrays of each scored batch, grouped by the batch's
    shared ``score`` span."""
    groups: dict = {}
    for i, r in enumerate(record.responses):
        if r is None or r.trace is None or r.cached:
            continue
        for s in r.trace.spans:
            if s.name == "score":
                groups.setdefault(id(s), []).append(queries[i])
                break
    return list(groups.values())


class Deployment:
    """The corpus, the live index and its server, ready to serve."""

    def __init__(self, config: dict, mix: dict, seed: int, traced: bool):
        import gc

        import jax

        from bench import corpus as corpus_mod
        from bench import deploy

        cache = ROOT / ".jax_cache"
        cache.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(cache))
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        # no eviction: the cell's programs are few, and an evicting
        # cache scans and locks the directory on every read
        jax.config.update("jax_compilation_cache_max_size", -1)
        self.config = config
        self.ranking = load_ranking(config["ranking"])
        self.phase = {}
        t = time.perf_counter()
        self.corpus = corpus_mod.generate(config, seed)
        self.phase["generate_s"] = time.perf_counter() - t
        t = time.perf_counter()
        gc.collect()
        before = deploy.device_bytes(), deploy.live_array_bytes()
        self.index = deploy.build_index(config, self.corpus, seed,
                                        self.ranking)
        for leaf in jax.live_arrays():
            leaf.block_until_ready()
        gc.collect()
        self.phase["build_s"] = time.perf_counter() - t
        # the allocator's count; the arrays' own sizes are printed beside
        self.index_bytes = deploy.device_bytes() - before[0]
        self.index_array_bytes = deploy.live_array_bytes() - before[1]
        view = self.index.view()
        self.segments = [(int(s.doc_base), int(s.doc_span), s.layout)
                         for s in view.segments]
        self.delta_docs = int(view.delta_n_docs)
        t = time.perf_counter()
        self.server = deploy.server(self.index, config, mix, traced)
        self.server.warmup()
        self.phase["warm_s"] = time.perf_counter() - t

    def traffic(self, mix: dict, seconds: float, seed: int):
        """(due times, query term ids, query hash rows)."""
        from bench import traffic

        c = self.corpus
        due = traffic.arrivals(mix, seconds)
        queries = traffic.queries(mix, c.df(), c.token_counts(),
                                  c.num_docs, len(due), seed)
        return due, queries, [c.hashes[q] for q in queries]

    def release(self) -> None:
        """Drop the program's state (the corpus stays)."""
        import gc

        self.server = self.index = None
        gc.collect()


def execute(cell: dict, config: dict, mix: dict, seed: int, seconds: float,
            traced: bool, bench: dict, peaks: dict,
            t_start: float) -> dict:
    """Set up, serve the window, check the answers.  Returns the result
    (without the device's name)."""
    import jax

    from bench import reference, trace_reduce, traffic, window
    from bench.walk import WalkBytes

    compiles = window.CompileCounter()
    dep = Deployment(config, mix, seed, traced)
    due, queries, rows = dep.traffic(mix, seconds, seed)
    corpus, server = dep.corpus, dep.server
    setup_s = time.perf_counter() - t_start
    say(phase="setup", setup_s=setup_s, **dep.phase, docs=corpus.num_docs,
        postings=corpus.num_postings, index_device_bytes=dep.index_bytes,
        index_array_bytes=dep.index_array_bytes, **compiles.setup,
        host_peak_gib=resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        / 2**20,
        segments=dep.segments, delta_docs=dep.delta_docs,
        requests=len(due), rate_qps=mix["rate_qps"])

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    t_trace = time.perf_counter()
    compiles.armed = True
    pauses = window.GcPauses()
    server.start()
    try:
        rec = window.drive(server, rows, due, seconds)
    finally:
        server.stop()
        compiles.armed = False
        pauses.close()
    window_s = time.perf_counter() - t_trace
    trace_events = None
    if traced:
        jax.profiler.stop_trace()
        trace_events = trace_reduce.events(trace_reduce.find_xplane(log_dir))
        import shutil
        shutil.rmtree(log_dir, ignore_errors=True)
    late = rec.lateness
    worst = np.argsort(-late)[:3]
    say(phase="window", requests=len(due), answered=int(rec.ok.sum()),
        cached=int(rec.cached.sum()), compiles_in_window=compiles.count,
        generator_late_p50_ms=float(np.percentile(late, 50) * 1e3),
        generator_late_p99_ms=float(np.percentile(late, 99) * 1e3),
        generator_late_worst=[[float(due[i]), float(late[i] * 1e3)]
                              for i in worst],
        latency_p90_ms=float(np.percentile(rec.latency, 90) * 1e3),
        latency_p99_ms=float(np.percentile(rec.latency, 99) * 1e3),
        gc_collections=pauses.count, gc_pause_max_ms=pauses.longest * 1e3,
        stall_dumps=rec.stalls)

    peak_bytes = memory_peak(int(cell["chips"]))
    metrics = {}
    busy_s = ctx = None
    if traced:
        batches = scored_batches(rec, queries)
        terms = {int(t) for b in batches for q in b for t in q}
        ctx = Context(server, rec, trace_events, window_s, batches,
                      WalkBytes(corpus, dep.segments, terms), peaks,
                      int(config["k"]))
        for m in metrics_for(bench, cell["name"], "per_layer"):
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        busy_s, _ = trace_reduce.busy(trace_events)
        breakdown = {"device_ops": trace_reduce.top_ops(trace_events),
                     "idle_gaps": trace_reduce.idle_gaps(trace_events)}
    else:
        e2e = window.end_to_end(rec)
        e2e["setup_s"] = setup_s
        e2e["index_bytes_per_posting"] = (dep.index_bytes /
                                          corpus.num_postings)
        for m in metrics_for(bench, cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}

    # the program's state goes before the reference runs
    del server, ctx
    dep.release()
    k = int(config["k"])
    pick = traffic.check_sample(mix, len(due), rec.cached, seed)
    ok_pick = [i for i in pick if rec.ok[i]]
    t = time.perf_counter()
    ref = dep.ranking.Reference(corpus, {int(x) for i in ok_pick
                                         for x in queries[i]})
    found = reference.compare(
        [(queries[i], np.asarray(rec.responses[i].doc_ids),
          np.asarray(rec.responses[i].scores)) for i in ok_pick], ref, k)
    say(phase="check", sampled=len(pick),
        cached_sampled=int(rec.cached[pick].sum()),
        reference_s=time.perf_counter() - t)
    checks, correct = reference.verdict(
        found, int(len(due) - rec.ok.sum()), config["limits"])
    result = {"correct": bool(correct), "attempted": len(due),
              "failed": int(len(due) - rec.ok.sum()), "metrics": metrics,
              "device": {"memory_peak_bytes": peak_bytes}}
    if traced:
        result["device"]["busy_s"] = busy_s
        result["device"]["window_s"] = window_s
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        bench = load_benchmark()
        cell, _entry, config, mix = resolve_cell(bench, args.workload)
        peaks = json.loads((BENCH / "peaks.json").read_text())
        device = device_info(int(cell["chips"]), peaks)
        result = execute(cell, config, mix, args.seed, args.seconds,
                         bool(args.trace), bench,
                         peaks["devices"][device["kind"]], T_START)
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 2
    device.update(result["device"])
    result["device"] = device
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
