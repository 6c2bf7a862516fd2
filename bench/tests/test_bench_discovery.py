"""A configuration, its ranking, a mix and a per-layer metric are found
by name: a later change adds files and entries, and edits none."""
import json
import re
import shutil
import subprocess
import sys

import pytest

from bench import run


def checkout(tmp_path):
    """A copy of the benchmark's files alone, and its BENCHMARK.json."""
    root = tmp_path / "checkout"
    shutil.copytree(run.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root, run.load_benchmark()


def test_new_files_are_found_by_name(tmp_path):
    root, bench = checkout(tmp_path)
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}

    (root / "bench" / "configs" / "wiki-2m.json").write_text(json.dumps(
        dict(json.loads((root / "bench/configs/paper-1m.json").read_text()),
             name="wiki-2m", num_docs=2_000_000, ranking="bm25")))
    (root / "bench" / "rankings" / "bm25.py").write_text(
        "def after_restore(index):\n    index.append('bm25')\n")
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
    index = []
    run.load_ranking(config["ranking"], root).after_restore(index)
    assert index == ["bm25"]
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


@pytest.mark.parametrize("ranking,named", [
    ("okapi", "bench/rankings/okapi.py"),       # no such file
    (None, "bench/configs/nameless.json"),      # no ranking at all
])
def test_a_configuration_without_its_ranking_is_refused(tmp_path, ranking,
                                                       named):
    root, bench = checkout(tmp_path)
    config = json.loads((root / "bench/configs/paper-1m.json").read_text())
    config.pop("ranking")
    if ranking is not None:
        config["ranking"] = ranking
    (root / "bench/configs/nameless.json").write_text(json.dumps(config))
    bench["configs"].append({"name": "nameless", "source": "x",
                             "file": "bench/configs/nameless.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "nameless.table7",
                               "config": "nameless", "traffic": "table7",
                               "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(run.Refused, match=re.escape(named)):
        run.resolve_cell(run.load_benchmark(root), "nameless.table7", root)
    # the measured entry stops there: before the device and any set-up
    out = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "nameless.table7",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode == 2 and not out.stdout.strip()
    assert "refused" in out.stderr and named in out.stderr
