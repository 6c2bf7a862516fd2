"""Cells of the benchmark cut to a size the CPU runs in seconds."""
from __future__ import annotations

import json
import time

from bench import run

SHAPES = {
    "paper-1m": dict(num_docs=2400, vocab=700, doc_len_median=64,
                     segments=[1280, 512, 384, 200], delta_docs=24),
}
SEED = 2**31 + 977


def cell(name: str, rate: float = 12.0):
    """(cell, configuration, mix, benchmark) at the tiny size."""
    bench = run.load_benchmark()
    c, _, config, mix = run.resolve_cell(bench, name)
    config = dict(config, **SHAPES[c["config"]], delta_doc_capacity=64,
                  delta_posting_capacity=64 * 64)
    return c, config, dict(mix, rate_qps=rate), bench


def execute(name: str, traced: bool = False, seconds: float = 1.0,
            seed: int = SEED, rate: float = 12.0) -> dict:
    c, config, mix, bench = cell(name, rate)
    peaks = json.loads((run.BENCH / "peaks.json").read_text())
    return run.execute(c, config, mix, seed, seconds, traced, bench,
                       peaks["devices"]["TPU v5 lite"], time.perf_counter())
