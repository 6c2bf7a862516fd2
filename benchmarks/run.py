# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness: ``PYTHONPATH=src python -m benchmarks.run``.

  table5   paper Table 5 (sizes + copy times)
  table6   paper Table 6 (aux-index sizes + creation)
  table7   paper Table 7 (query evaluation, 1-4 terms)
  expansion  paper §4.4 (document-based access)
  roofline   §Roofline terms from the dry-run artifacts (if present)
  churn    live-index ingest/churn: docs/sec, latency vs segment count,
           posting-merge amplification vs full rebuild
  serving  QueryServer offered-QPS sweep: request latency p50/p99,
           achieved QPS, cache hit rate, maintenance-thread lifecycle;
           plus the MeshServer offered-QPS x shard-count sweep (shed
           rate, handoff pause), in this process over ``jax.devices()``

``--smoke`` runs every suite on a CI-sized corpus (plumbing check, not
representative numbers).  ``partitioned`` and the mesh sweep need 8
devices: on CPU launch with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import dataclasses
import sys
import traceback


def main() -> None:
    from benchmarks import churn, common, expansion, partitioned, \
        roofline, serving, table5_size, table6_index, table7_query
    from repro.kernels.runtime import enable_compile_cache
    enable_compile_cache()
    suites = [("table5", table5_size.main), ("table6", table6_index.main),
              ("table7", table7_query.main), ("expansion", expansion.main),
              ("partitioned", partitioned.main),
              ("roofline", roofline.main), ("churn", churn.main),
              ("serving", serving.main)]
    args = [a for a in sys.argv[1:]]
    smoke = "--smoke" in args
    if smoke:
        args.remove("--smoke")
        common.set_smoke()
    only = args[0] if args else None
    print("name,us_per_call,derived")
    common.reset_records()
    failed = 0
    for name, fn in suites:
        if only and name != only:
            continue
        try:
            fn()
        except Exception:                        # noqa: BLE001
            failed += 1
            traceback.print_exc()
            print(f"{name}/FAILED,0.0,")
    if smoke:
        # the artifact CI gates on: suite CSV rows + a dedicated
        # fused-scorer latency measurement (schema-versioned JSON);
        # v3 adds the observability section — a traced serving drive's
        # per-stage breakdown + the unified registry snapshot — and,
        # additively, the mesh section: a deterministic MeshServer
        # drive's shed counts/rate, handoff pauses, and stage
        # breakdown (check_regression.check_mesh_section)
        gate = common.smoke_gate_stats()
        obs = common.smoke_observability()
        common.write_bench(
            "smoke",
            results={"gate": gate, "suites_failed": failed,
                     "layout_mix": common.smoke_layout_mix(),
                     "stages": obs["stages"],
                     "registry": obs["registry"],
                     "mesh": common.smoke_mesh()},
            config={"spec": dataclasses.asdict(common.SMOKE_SPEC),
                    "only": only})
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
