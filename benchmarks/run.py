"""Benchmark harness: one module per paper table/figure.

Emits ``name,us_per_call,derived`` CSV plus a machine-readable JSON
(``suite -> name -> us_per_call``, default ``BENCH_PR2.json``) so the
perf trajectory is tracked across PRs.  See EXPERIMENTS.md for the
mapping to the paper's Figures 8-14 and Tables 2-3.
"""
from __future__ import annotations

import argparse
import os
import time

from . import (bench_batch_scaling, bench_complex_filter, bench_e2e,
               bench_ingest, bench_kernels, bench_label_filter,
               bench_label_scaling, bench_label_storage, bench_media,
               bench_neighbor, bench_partition, bench_pipeline,
               bench_pruning, bench_resident, bench_serving,
               bench_simple_filter, bench_storage, bench_transform,
               bench_traversal)
from .util import header, set_suite, write_json
from repro.launch.compile_cache import use_compile_cache

SUITES = {
    "fig8_storage": bench_storage.run,
    "fig9_neighbor": bench_neighbor.run,
    "fig10_transform": bench_transform.run,
    "fig11_label_storage": bench_label_storage.run,
    "fig12_simple_filter": bench_simple_filter.run,
    "fig13_complex_filter": bench_complex_filter.run,
    "fig14_label_scaling": bench_label_scaling.run,
    "batch_scaling": bench_batch_scaling.run,
    "label_filter": bench_label_filter.run_filter,
    "filtered_retrieval": bench_label_filter.run_retrieval,
    "resident": bench_resident.run,
    "partition": bench_partition.run,
    "pruning": bench_pruning.run,
    "traversal": bench_traversal.run,
    "ingest": bench_ingest.run,
    "table2_media": bench_media.run,
    "table3_e2e": bench_e2e.run,
    "pipeline": bench_pipeline.run,
    "kernels": bench_kernels.run,
    "serving": bench_serving.run,
    "overload": bench_serving.run_overload,
}


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--json", default=None,
                    help="machine-readable results path ('' to skip); "
                         "defaults to BENCH_PR10.json, or bench_smoke.json "
                         "under REPRO_BENCH_SMOKE so shrunk-workload rows "
                         "never overwrite the tracked trajectory")
    args = ap.parse_args()
    if args.json is None:
        args.json = ("bench_smoke.json" if os.environ.get("REPRO_BENCH_SMOKE")
                     else "BENCH_PR10.json")
    names = (args.only.split(",") if args.only else list(SUITES))
    header()
    t0 = time.perf_counter()
    for name in names:
        set_suite(name)
        SUITES[name]()
    print(f"# total_wall_s={time.perf_counter()-t0:.1f}", flush=True)
    if args.json:
        write_json(args.json)
        print(f"# wrote {args.json}", flush=True)


if __name__ == '__main__':
    main()
