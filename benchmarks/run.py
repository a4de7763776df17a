"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes a machine-readable
``BENCH_<run>.json`` artifact (suite → name → us_per_call) so the perf
trajectory is trackable across PRs / CI runs.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig7,fig9,...]
                                            [--run-id ID] [--json-dir DIR]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path


def write_json(path: Path, run_id: str, args, rows: list[tuple],
               failed: list[str]) -> None:
    by_suite: dict[str, dict] = {}
    for name, us, derived in rows:
        suite = name.split("/", 1)[0]
        by_suite.setdefault(suite, {})[name] = {
            "us_per_call": round(float(us), 3), "derived": derived,
        }
    doc = {
        "run": run_id,
        "generated_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "quick": bool(args.quick),
        "only": args.only,
        "failed_suites": failed,
        "suites": by_suite,
    }
    path.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    print(f"wrote {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced cardinalities / query subsets")
    ap.add_argument("--only", default=None,
                    help="comma list: fig7,fig8,fig9,fig11,fig13,table4,"
                         "table5,prepared,execmany,shardmany,fused,"
                         "cursorloop,decorr,resilience,routing,fleet")
    ap.add_argument("--run-id", default=None,
                    help="label baked into the BENCH_<run>.json filename "
                         "(default: local timestamp)")
    ap.add_argument("--json-dir", default=".",
                    help="directory for the BENCH_<run>.json artifact "
                         "('' disables JSON emission)")
    args, _ = ap.parse_known_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        bench_batchmode,
        bench_compile,
        bench_cost_routing,
        bench_cursor_loops,
        bench_decorrelate,
        bench_execute_many,
        bench_factor,
        bench_fleet,
        bench_fused,
        bench_invocations,
        bench_native,
        bench_prepared,
        bench_resilience,
        bench_resources,
        bench_sharded_many,
        bench_tpch,
    )
    from benchmarks.common import ROWS

    suites = {
        "fig7": bench_invocations.run,     # invocation-count sweep
        "fig8": bench_compile.run,         # cold-cache compile overhead
        "fig9": bench_tpch.run,            # TPC-H queries with UDFs
        "fig11": bench_factor.run,         # factor of improvement (W1/W2)
        "fig13": bench_resources.run,      # CPU time + logical reads (fig14)
        "table4": bench_batchmode.run,     # batch mode / relagg kernel
        "table5": bench_native.run,        # native compilation quadrant
        "prepared": bench_prepared.run,    # Session prepare/execute lifecycle
        "execmany": bench_execute_many.run,  # batched invocation engine
        "shardmany": bench_sharded_many.run,  # mesh-sharded batches
        "fused": bench_fused.run,          # multi-statement fusion
        "cursorloop": bench_cursor_loops.run,  # loop-to-scan rewrite
        "decorr": bench_decorrelate.run,   # correlated-subquery rewrite
        "resilience": bench_resilience.run,  # ladder overhead + demotions
        "routing": bench_cost_routing.run,  # cost-based routing + d-bucketing
        "fleet": bench_fleet.run,          # persistent tier + worker fleet
    }
    only = args.only.split(",") if args.only else list(suites)

    print("name,us_per_call,derived")
    failed = []
    for key in only:
        try:
            suites[key](quick=args.quick)
        except Exception as e:
            failed.append(key)
            print(f"{key}/ERROR,0,{type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)

    if args.json_dir != "":
        run_id = args.run_id or time.strftime("%Y%m%d_%H%M%S")
        write_json(Path(args.json_dir) / f"BENCH_{run_id}.json",
                   run_id, args, ROWS, failed)
    if failed:
        raise SystemExit(f"benchmark suites failed: {failed}")


if __name__ == "__main__":
    main()
