#!/usr/bin/env python3
"""Offered-rate sweep of an open-loop cell, to find the highest rate it
sustains without a growing backlog: one set-up, then one window per rate.

    python3 bench/sweep.py --workload udf_calls.open --seed 7 \\
        --seconds 15 --rates 100,200,400,800

Prints one JSON line per rate: offered and completed calls per second,
latency median and 95th percentile, the median latency of the last fifth
of the arrivals over that of the first fifth (a backlog that grows
through the window drives it up), the generator's lateness and the
scheduler's calls per wave.  A rate is sustained where at least 95 % of
it completes, that ratio of latencies stays under 2 and no call fails; the
sweep stops after the first rate that is not.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def point(run, rate: float) -> dict:
    from bench.harness import completed_per_s, latencies_ms

    lat = latencies_ms(run)
    fifth = max(1, len(lat) // 5)
    s0, s1 = run.sched_stats
    waves = max(1, s1["batches"] - s0["batches"])
    late = np.asarray(run.lateness_s) * 1e3
    pt = {
        "rate_per_s": rate,
        "completed_per_s": completed_per_s(run),
        "call_p50_ms": float(np.percentile(lat, 50)),
        "call_p95_ms": float(np.percentile(lat, 95)),
        "growth": float(np.median(lat[-fifth:]) / np.median(lat[:fifth])),
        "lateness_p95_ms": float(np.percentile(late, 95)),
        "wave_size": (s1["drained"] - s0["drained"]) / waves,
        "failed": sum(not c.ok for c in run.calls),
        "window_misses": sum(run.window_misses.values()),
    }
    pt["sustained"] = (pt["completed_per_s"] >= 0.95 * rate
                       and pt["growth"] < 2 and not pt["failed"])
    return pt


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from bench import generator, harness

    rates = [float(r) for r in args.rates.split(",")]
    c = harness.cell(args.workload)
    c.mix["rate_per_s"] = max(rates)
    st = harness.setup(c, args.seed, args.seconds, False, t0)
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    for i, rate in enumerate(rates):
        mix = {**c.mix, "rate_per_s": rate}
        st.plan = generator.plan(mix, st.mods, st.data, np.random.default_rng(
            [args.seed, i]), args.seconds)
        run = harness.window(st, args.seconds, False)
        pt = point(run, rate)
        print(json.dumps(pt), flush=True)
        if not pt["sustained"]:
            break   # past the knee: higher rates only queue longer
    return 0


if __name__ == "__main__":
    sys.exit(main())
