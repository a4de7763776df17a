"""Run by ``tests/test_placement.py`` in a process of its own, on four
virtual CPU devices (``XLA_FLAGS=--xla_force_host_platform_device_count=4
JAX_PLATFORMS=cpu``): the served statements' waves under ``FROID+data4``
against the serial ``execute`` loop, the ``devices`` attribute of their
``froid.dispatch`` spans, and one run of the ``udf_calls.open.x4`` cell.
Prints one JSON line of findings; the test asserts on it."""
import glob
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import harness  # noqa: E402
from repro.core import Session, resolve_policy  # noqa: E402

TINY = {"scale_factor": 0.001}


def session(cell):
    data = harness.generate({**cell.config, **TINY},
                            np.random.SeedSequence(11))
    db = Session()
    harness.load(db, data)
    for f in cell.config["functions"]:
        harness.load_named("functions", f).register(db)
    return db, data


def same(serial, waves) -> bool:
    """Element for element: the same mask, and the same values on every
    row the mask keeps."""
    for s, w in zip(serial, waves):
        m = np.asarray(s.masked.mask)
        if not np.array_equal(m, np.asarray(w.masked.mask)):
            return False
        for name, c in s.masked.table.columns.items():
            got = w.masked.table.columns[name]
            if not (np.array_equal(np.asarray(c.data)[m],
                                   np.asarray(got.data)[m])
                    and np.array_equal(np.asarray(c.validity())[m],
                                       np.asarray(got.validity())[m])):
                return False
    return len(serial) == len(waves)


def dispatch_devices(trace_dir) -> list:
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(
        glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")[-1])
    return [int(v) for p in prof.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events
            if e.name == "froid.dispatch"
            for k, v in e.stats if k == "devices"]


def main():
    out = {"devices": len(jax.devices())}
    cell = harness.cell("udf_calls.open.x4")
    db, data = session(cell)
    policy = resolve_policy(cell.config["policy"])
    out["shard_devices"] = policy.shard_devices()
    rng = np.random.default_rng(5)
    waves = {}
    for name in cell.config["statements"]:
        mod = harness.load_named("statements", name)
        stmt = db.prepare(mod.build(), policy)
        for k in (1, 2, 3, 4, 5, 8):
            plist = mod.bindings(rng, k, data)
            pad0 = db.timing_stats["pad_calls"]
            rs = stmt.execute_many(plist)
            serial = [stmt.execute(params=p) for p in plist]
            st = rs[0].stats
            waves[f"{name}:{k}"] = {
                "same": same(serial, rs), "sharded": st.get("sharded", False),
                "bucket": st["batch_bucket"],
                "pad": db.timing_stats["pad_calls"] - pad0}
    out["waves"] = waves
    out["timing"] = {k: db.timing_stats[k] for k in (
        "sharded_waves", "sharded_calls", "pad_calls")}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        stmt.execute_many(mod.bindings(rng, 3, data))
        jax.profiler.stop_trace()
        out["dispatch_devices"] = dispatch_devices(d)

    lines = []
    result = harness.run_cell(
        "udf_calls.open.x4", 2**31 + 15, 1.5, False, require_chip=False,
        config=TINY, log=lambda *a, **k: lines.append(" ".join(map(str, a))))
    head = "scheduler over the window: "
    sched = [json.loads(x[len(head):]) for x in lines if x.startswith(head)]
    out["cell"] = {"correct": result["correct"], "failed": result["failed"],
                   "attempted": result["attempted"],
                   "count": result["device"]["count"],
                   "metrics": sorted(result["metrics"]),
                   "sched": sched[0] if sched else None}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
