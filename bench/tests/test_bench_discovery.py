"""A configuration, a mix and a per-layer metric are found by name: a
later change adds files and entries, and edits none."""
import json
import shutil

from bench import run


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(run.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = run.load_benchmark()
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}

    (root / "bench" / "configs" / "wiki-2m.json").write_text(json.dumps(
        dict(json.loads((root / "bench/configs/paper-1m.json").read_text()),
             name="wiki-2m", num_docs=2_000_000)))
    (root / "bench" / "traffic" / "bursty.json").write_text(json.dumps(
        dict(json.loads((root / "bench/traffic/table7.json").read_text()),
             rate_qps=3.5)))
    (root / "bench" / "layers" / "padded_slot_share.py").write_text(
        "def read(ctx):\n    return 12.5\n")
    bench["configs"].append({"name": "wiki-2m", "source": "x",
                             "file": "bench/configs/wiki-2m.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "wiki-2m.bursty",
                               "config": "wiki-2m", "traffic": "bursty",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "padded_slot_share", "unit": "%",
                               "better": "lower", "source": "program_span",
                               "layer": "admission and micro-batch",
                               "moves": "latency_p50_ms",
                               "workloads": ["wiki-2m.bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    found = run.load_benchmark(root)
    cell, entry, config, mix = run.resolve_cell(found, "wiki-2m.bursty",
                                                root)
    assert config["num_docs"] == 2_000_000 and mix["rate_qps"] == 3.5
    names = [m["name"] for m in run.metrics_for(found, cell["name"],
                                                "per_layer")]
    assert names == ["padded_slot_share"]
    assert run.load_reader("padded_slot_share", root)(None) == 12.5
    e2e = [m["name"] for m in run.metrics_for(found, cell["name"],
                                              "end_to_end")]
    assert "setup_s" in e2e and "qps" in e2e
    after = {p.relative_to(root): p.read_bytes()
             for p in (root / "bench").rglob("*") if p.is_file()}
    assert all(after[p] == b for p, b in before.items())
