"""Kernel geometry autotuner for the fused decode-and-score engine.

The fused kernels historically baked in one geometry — ``TILE = 512``
doc-tile width, ``Q_PAD = 8`` query quantum, ``K_PAD = 8`` candidate
quantum, successive-maxima tile reduction.  Those constants are good
defaults for a TPU MXU but have no reason to be optimal for every
(backend, index size, layout) triple — the tile width trades pair count
against per-tile work, and the bitonic tile reducer
beats ``k_tile`` successive-maxima passes once ``k_tile`` outgrows the
fixed ``log2(tile)*(log2(tile)+1)/2`` stage count of a full sort.

This module makes the geometry a measured quantity:

  * ``TuneConfig`` — one frozen geometry choice.  ``DEFAULT_CONFIG`` is
    exactly the historical constants, so an EMPTY tuning table is
    bit-identical to the pre-autotuner engine (the layout-parity fuzz
    suite runs untouched).
  * ``TuningTable`` — winning config per ``(backend, size_class,
    layout)``, JSON-serializable (schema-versioned) for on-disk reuse;
    a module-level ACTIVE table is what ``make_scorer``, the segment
    engines and the sharded scorers consult.  Size classes use
    ``core.size_model.tuning_size_class`` — the same quantization the
    seal path applies to segment doc counts, so seal/compaction emit
    segments that land exactly on a tuned class.
  * ``autotune_index`` — sweeps candidate configs over a real index +
    query batch, stores the min-median winner.

Env override ``REPRO_REDUCER=bitonic`` (or ``successive``) forces the
tile reducer regardless of table state — used by CI to run the whole
layout-parity fuzz suite under the bitonic reducer without editing
tests.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
import warnings
from typing import Iterable

TUNE_SCHEMA = "repro-tune/1"

_TILE_DEFAULT = 512
_Q_PAD_DEFAULT = 8
_K_PAD_DEFAULT = 8


@dataclasses.dataclass(frozen=True)
class TuneConfig:
    """One kernel-geometry choice for the fused candidate engine.

    ``k_tile`` is an optional OVERRIDE of the per-query candidate count;
    ``None`` derives it from (k, tile, k_pad) at call time.  Either way
    ``resolve_k_tile`` clamps to the exactness floor ``min(k, tile)`` so
    a tuned config can widen but never break the merge contract.
    """
    tile: int = _TILE_DEFAULT
    q_pad: int = _Q_PAD_DEFAULT
    k_pad: int = _K_PAD_DEFAULT
    k_tile: int | None = None
    reducer: str = "successive"

    def resolve_k_tile(self, k: int) -> int:
        from repro.kernels.fused_decode_score import default_k_tile
        floor = default_k_tile(k, self.tile, self.k_pad)
        if self.k_tile is None:
            return floor
        return min(max(int(self.k_tile), floor), self.tile)

    def resolved(self) -> "TuneConfig":
        """Apply env overrides (REPRO_REDUCER) on top of this config."""
        forced = os.environ.get("REPRO_REDUCER", "")
        if forced and forced != self.reducer:
            from repro.kernels.fused_decode_score import REDUCERS
            if forced not in REDUCERS:
                raise ValueError(f"REPRO_REDUCER={forced!r} not in "
                                 f"{REDUCERS}")
            return dataclasses.replace(self, reducer=forced)
        return self

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TuneConfig":
        """Unknown keys are dropped, so tables stored with a retired
        field (``pairs_per_step``) still load."""
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


DEFAULT_CONFIG = TuneConfig()


def size_class_of(num_docs: int) -> int:
    from repro.core.size_model import tuning_size_class
    return tuning_size_class(num_docs)


def layout_of(index) -> str:
    """'hor' for BlockedIndex, 'packed' for PackedCsrIndex, 'banded' for
    BandedCsrIndex — the same layout tags the segmented live index
    uses."""
    from repro.core.layouts import BandedCsrIndex, PackedCsrIndex
    if isinstance(index, BandedCsrIndex):
        return "banded"
    return "packed" if isinstance(index, PackedCsrIndex) else "hor"


def _compiled_lowering(backend: str) -> bool:
    """True when ``backend`` lowers through the compiled (non-interpret)
    Pallas path, where the bitonic tile reducer is not implemented."""
    if backend == "pallas-tpu":
        return True
    if backend == "pallas":
        import jax
        return jax.default_backend() == "tpu"
    return False


_BITONIC_WARNED = False


def downgrade_reducer(cfg: TuneConfig, backend: str) -> TuneConfig:
    """Resolve a ``reducer="bitonic"`` table entry to ``successive`` on
    compiled lowerings, where the kernel would otherwise reject it at
    entry (fused_decode_score raises NotImplementedError).  Warns once
    per process and bumps the ``autotune_bitonic_downgrade`` counter so
    poisoned tables are visible, not fatal."""
    global _BITONIC_WARNED
    if cfg.reducer != "bitonic" or not _compiled_lowering(backend):
        return cfg
    from repro.obs.registry import GLOBAL
    GLOBAL.counter("autotune_bitonic_downgrade").inc()
    if not _BITONIC_WARNED:
        _BITONIC_WARNED = True
        warnings.warn(
            "tuning table requested reducer='bitonic' on a compiled "
            f"lowering (backend={backend!r}); downgrading to "
            "'successive' — re-tune the table on this backend",
            RuntimeWarning, stacklevel=3)
    return dataclasses.replace(cfg, reducer="successive")


class TuningTable:
    """Winning ``TuneConfig`` per ``(backend, size_class, layout)``."""

    def __init__(self) -> None:
        self._entries: dict[tuple[str, int, str], TuneConfig] = {}
        self._costs: dict[tuple[str, int, str], float] = {}

    def __len__(self) -> int:
        return len(self._entries)

    def put(self, backend: str, size_class: int, layout: str,
            cfg: TuneConfig, cost_s: float | None = None) -> None:
        key = (str(backend), int(size_class), str(layout))
        self._entries[key] = cfg
        if cost_s is not None:
            self._costs[key] = float(cost_s)

    def get(self, backend: str, size_class: int,
            layout: str) -> TuneConfig | None:
        return self._entries.get((str(backend), int(size_class),
                                  str(layout)))

    def cost(self, backend: str, size_class: int,
             layout: str) -> float | None:
        """Measured median seconds of the winning config at EXACTLY this
        (backend, size_class, layout), or None if the sweep never timed
        it.  No nearest-class fallback: the layout cost model must only
        compare costs measured at the same class."""
        return self._costs.get((str(backend), int(size_class),
                                str(layout)))

    def lookup(self, backend: str, num_docs: int, layout: str) -> TuneConfig:
        """Config for an index of ``num_docs`` docs; falls back to the
        nearest SMALLER tuned class of the same (backend, layout), then
        to ``DEFAULT_CONFIG`` — a partially swept table still covers
        every query."""
        cls_ = size_class_of(num_docs)
        hit = self.get(backend, cls_, layout)
        if hit is not None:
            return downgrade_reducer(hit, backend)
        below = [(c, cfg) for (b, c, l), cfg in self._entries.items()
                 if b == backend and l == layout and c < cls_]
        if below:
            return downgrade_reducer(max(below, key=lambda e: e[0])[1],
                                     backend)
        return DEFAULT_CONFIG

    def to_dict(self) -> dict:
        entries = []
        for (b, c, l), cfg in sorted(self._entries.items()):
            e = {"backend": b, "size_class": c, "layout": l,
                 "config": cfg.to_dict()}
            cost = self._costs.get((b, c, l))
            if cost is not None:
                e["median_s"] = cost
            entries.append(e)
        return {"schema": TUNE_SCHEMA, "entries": entries}

    @classmethod
    def from_dict(cls, d: dict) -> "TuningTable":
        if d.get("schema") != TUNE_SCHEMA:
            raise ValueError(f"unknown tuning-table schema "
                             f"{d.get('schema')!r} (want {TUNE_SCHEMA})")
        t = cls()
        for e in d.get("entries", []):
            t.put(e["backend"], e["size_class"], e["layout"],
                  TuneConfig.from_dict(e["config"]),
                  cost_s=e.get("median_s"))
        return t

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=2, sort_keys=True)
            f.write("\n")

    @classmethod
    def load(cls, path: str) -> "TuningTable":
        with open(path) as f:
            return cls.from_dict(json.load(f))


# The table every wiring point (make_scorer, LiveView.topk, the sharded
# scorers, seal-time route_tile selection) consults.  Starts EMPTY:
# every lookup resolves to DEFAULT_CONFIG and the engine is bit-
# identical to the pre-autotuner code.
_ACTIVE = TuningTable()


def get_active() -> TuningTable:
    return _ACTIVE


def set_active(table: TuningTable | None) -> TuningTable:
    """Install ``table`` (None -> fresh empty table) as the active
    tuning table; returns the previous one so tests can restore it."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = table if table is not None else TuningTable()
    return prev


def lookup(backend: str, num_docs: int, layout: str) -> TuneConfig:
    """Active-table resolution + env overrides — THE query-time entry
    point; every engine call site funnels through here."""
    return _ACTIVE.lookup(str(backend), num_docs, str(layout)).resolved()


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------


def candidate_configs(k: int, tile_default: int = _TILE_DEFAULT,
                      tiles: Iterable[int] = (256, 512, 1024),
                      reducers: Iterable[str] = ("successive", "bitonic"),
                      include_wide_k: bool = True) -> list[TuneConfig]:
    """The pruned sweep grid: geometry axes that can plausibly matter,
    not the full cross product.  The reducer only varies at the default
    tile (it is independent of tile width to first order); tile varies
    with everything else at defaults; ``k_tile`` widening is tried once
    (2x the floor) at the default tile."""
    from repro.kernels.fused_decode_score import default_k_tile
    out: list[TuneConfig] = [TuneConfig()]
    for t in tiles:
        if t != tile_default:
            out.append(TuneConfig(tile=t))
    for r in reducers:
        if r != "successive":
            out.append(TuneConfig(reducer=r))
    if include_wide_k:
        floor = default_k_tile(k, tile_default, _K_PAD_DEFAULT)
        wide = min(2 * floor, tile_default)
        if wide > floor:
            out.append(TuneConfig(k_tile=wide))
            out.append(TuneConfig(k_tile=wide, reducer="bitonic"))
    return out


def _median(xs: list[float]) -> float:
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def time_config(index, query_hashes, idf_w, k: int, cap: int,
                cfg: TuneConfig, backend: str = "pallas", reps: int = 3,
                warmup: int = 1, rank_blend: float = 0.0) -> float:
    """Median wall-clock seconds of one fused candidate-engine call
    under ``cfg`` (jit-compiled; warmup excluded)."""
    import jax

    from repro.core.ranking import query_norms
    from repro.kernels import ops

    k_tile = cfg.resolve_k_tile(k)
    qscale = jax.numpy.asarray(query_norms(idf_w))
    # the query paths' budget: every candidate is timed doing the FULL
    # pair set, not a silently truncated one
    max_pairs = ops.default_max_pairs(index, *query_hashes.shape, cap,
                                      cfg.tile)

    def run():
        vals, ids, _ = ops.fused_segment_topk(
            index, query_hashes, idf_w, qscale, jax.numpy.int32(0),
            k_tile=k_tile, cap=cap, max_pairs=max_pairs,
            rank_blend=rank_blend,
            tile=cfg.tile, backend=backend, q_pad=cfg.q_pad,
            reducer=cfg.reducer)
        jax.block_until_ready((vals, ids))

    for _ in range(max(warmup, 1)):
        run()
    samples = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        run()
        samples.append(time.perf_counter() - t0)
    return _median(samples)


def autotune_index(index, query_hashes, idf_w, k: int, cap: int | None = None,
                   backend: str = "pallas",
                   configs: Iterable[TuneConfig] | None = None,
                   reps: int = 3, warmup: int = 1,
                   table: TuningTable | None = None):
    """Sweep candidate configs on a real (index, query batch) workload.

    Returns ``(best_config, records)`` where records is one dict per
    config (config, median seconds, candidate bytes/query) — the raw
    material of the BENCH_autotune artifact.  If ``table`` is given the
    winner is stored under this index's (backend, size_class, layout)
    key.  Ties inside 2% break toward the smaller candidate output
    (size-model hook), then toward the default config.
    """
    from repro.core.size_model import candidate_bytes_per_query

    if cap is None:
        cap = max(int(index.max_posting_len), 1)
    if configs is None:
        configs = candidate_configs(k)
    num_docs = int(index.docs.num_docs)
    records = []
    for cfg in configs:
        sec = time_config(index, query_hashes, idf_w, k, cap, cfg,
                          backend=backend, reps=reps, warmup=warmup)
        records.append({
            "config": cfg.to_dict(),
            "median_s": sec,
            "candidate_bytes_per_query": candidate_bytes_per_query(
                num_docs, cfg.tile, cfg.resolve_k_tile(k)),
            "is_default": cfg == DEFAULT_CONFIG,
        })
    fastest = min(r["median_s"] for r in records)

    def rank(r):
        return (r["median_s"] > fastest * 1.02,
                r["candidate_bytes_per_query"],
                not r["is_default"], r["median_s"])

    best_rec = min(records, key=rank)
    best = TuneConfig.from_dict(best_rec["config"])
    if table is not None:
        # the winner's measured median feeds the layout cost model's
        # decode-cost term (size_model.LayoutCostModel.measured_cost_s)
        table.put(backend, size_class_of(num_docs), layout_of(index), best,
                  cost_s=best_rec["median_s"])
    return best, records
