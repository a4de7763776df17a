#!/usr/bin/env python3
"""The control of the check that decides ``correct``: the plain reference
computed in bfloat16 (the precision below the configurations' float32),
put in the engine's place and compared with the float64 reference by the
same comparison and on the same answers as a run's.  Its readings have to
come out above the configuration's limits.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 30]

Prints one JSON line per seed.  Needs no chip: it runs no engine.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def readings(workload: str, seed: int, seconds: float,
             config: dict | None = None, mix: dict | None = None) -> dict:
    """The numbers compared, each beside its limit, with the bfloat16
    reference's answers in place of the engine's."""
    from bench import generator, harness, reference

    c = harness.cell(workload)
    c.config.update(config or {})
    c.mix.update(mix or {})
    data_ss, traffic_ss = np.random.SeedSequence(seed).spawn(2)
    data = harness.generate(c.config, data_ss)
    mods = {n: harness.load_named("statements", n)
            for n in c.config["statements"]}
    plan = generator.plan(c.mix, mods, data,
                          np.random.default_rng(traffic_ss), seconds)
    picked = (range(len(plan.requests)) if plan.checked is None
              else sorted(plan.checked))
    tally = reference.Tally()
    cache: dict = {}
    for i in picked:
        req = plan.requests[i]
        mod = mods[req.stmt]
        tally.add(reference.served(mod.reference(data, req.params,
                                                 reference.BF16, cache)),
                  mod.reference(data, req.params, reference.F64, cache),
                  req.stmt)
    return {**tally.checks(c.config["limits"]), "by_stmt": tally.by_label}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from bench import reference

    for seed in (int(s) for s in args.seeds.split(",")):
        checks = readings(args.workload, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_fails": not reference.within(checks),
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
