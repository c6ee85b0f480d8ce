# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV (the stub contract).
from __future__ import annotations

import argparse
import json
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small datasets (CI-sized)")
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark names")
    args = ap.parse_args()

    from . import (ingest_bench, materialize_bench, paper_figs, query_bench,
                   replica_bench, retrieval_bench, server_bench,
                   shard_bench, storage_bench, temporal_bench)

    benches = [
        materialize_bench.bench_materialize,
        retrieval_bench.bench_retrieval,
        temporal_bench.bench_temporal,
        storage_bench.bench_storage,
        query_bench.bench_query,
        ingest_bench.bench_ingest,
        shard_bench.bench_shard,
        replica_bench.bench_replica,
        server_bench.bench_server,
        paper_figs.fig6_vs_copylog,
        paper_figs.fig7_vs_interval_tree,
        paper_figs.fig8a_graphpool_memory,
        paper_figs.fig8b_partitioned,
        paper_figs.fig8c_multipoint,
        paper_figs.fig8d_columnar,
        paper_figs.fig9_construction_params,
        paper_figs.fig10_materialization,
        paper_figs.fig11_diff_functions,
        paper_figs.bitmap_penalty,
        paper_figs.subgraph_pattern_index,
    ]

    print("name,us_per_call,derived")
    failures = 0
    for bench in benches:
        if args.only and args.only not in bench.__name__:
            continue
        try:
            for name, us, derived in bench(quick=args.quick):
                print(f"{name},{us:.1f},\"{json.dumps(derived)}\"",
                      flush=True)
        except Exception:
            failures += 1
            print(f"{bench.__name__},ERROR,\"{traceback.format_exc(limit=2)}\"",
                  file=sys.stderr, flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
