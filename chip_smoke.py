#!/usr/bin/env python3
"""Chip smoke: the Froid engine's served path, end to end, on one TPU chip.

    python chip_smoke.py [--sf 1.0] [--seed 0]     # one chip (the default)
    python chip_smoke.py --chips 4 [--sf 1.0]      # sharded execute_many only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse-cpu --sf 0.01

Phases, through the entry points a client calls (``Session.prepare`` →
``execute`` / ``execute_many`` → ``CoalescingScheduler``), on TPC-H
generated from ``--seed`` at ``--sf``:

1. device: fails unless JAX's first device is a TPU;
2. load: TPC-H into a ``Session`` backed by a fresh ``PlanStore``;
3. the seven TPC-H UDF queries, cold then warm, checked against their
   original forms and, for Q1 and Q6, a float64 reference;
4. the relagg Pallas kernel inside compiled plans (``table4``, Q12),
   checked against the default lowering and for the kernel in the HLO;
5. four parametric statements through ``execute_many`` and one fused
   scheduler drain, checked against serial ``execute`` and, on a small
   catalog, against ``INTERPRETED``;
6. a fresh session warm-started from the same ``PlanStore``.

Every phase raises on a wrong answer, a demotion down the resilience
ladder, or a persistence error; the process then exits nonzero.  The last
line of a passing run is one JSON object naming the device.  The seconds
printed along the way are smoke timings (cold compiles included), not
benchmark metrics.

``--chips 4`` runs only the sharded ``execute_many`` phase and its
one-device comparison.  ``--rehearse-cpu`` runs everything on the CPU
(Pallas in interpret mode, no kernel-in-HLO check); nothing else selects
the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
STORE_DIR = ROOT / ".smoke_plans"

#: bindings per parametric statement in phase 5
N_BINDINGS = 64
#: the small catalog the INTERPRETED comparison runs on (400 lineitems)
SMALL_SF = 0.0001
SMALL_BINDINGS = 4
RTOL = 1e-3
ATOL = 1e-3
#: scheduler counters that mean a drain fell down the resilience ladder
LADDER_FAULTS = ("demote_fused_to_many", "demote_many_to_serial",
                 "demote_serial_to_interp", "ladder_exhausted",
                 "breaker_open_skips")

T_START = time.perf_counter()


def log(phase: str, msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:8.1f}s] {phase}: {msg}",
          flush=True)


# ---------------------------------------------------------------- checks
def host_cols(masked) -> dict:
    """Selected rows of a result, as ``{column: (data, valid)}`` numpy
    arrays, in a canonical row order (by the non-float columns)."""
    import numpy as np

    m = np.asarray(masked.mask)
    cols = {n: (np.asarray(c.data)[m], np.asarray(c.validity())[m])
            for n, c in masked.table.columns.items()}
    keys = [np.where(v, d, 0) for d, v in cols.values()
            if not np.issubdtype(d.dtype, np.floating)]
    if keys and int(m.sum()) > 1:
        order = np.lexsort(keys[::-1])
        cols = {n: (d[order], v[order]) for n, (d, v) in cols.items()}
    return cols


def assert_same(got, want, what: str, exact: bool = False) -> None:
    """Same rows, same NULLs, same values (floats within RTOL/ATOL unless
    ``exact``)."""
    import numpy as np

    a, b = host_cols(got), host_cols(want)
    if set(a) != set(b):
        raise AssertionError(f"{what}: columns {sorted(a)} != {sorted(b)}")
    for name in a:
        (da, va), (db, vb) = a[name], b[name]
        if da.shape != db.shape:
            raise AssertionError(
                f"{what}: {name} has {da.shape[0]} rows, want {db.shape[0]}")
        np.testing.assert_array_equal(va, vb, err_msg=f"{what}: {name} NULLs")
        da, db = da[va], db[vb]
        if exact or not np.issubdtype(db.dtype, np.floating):
            np.testing.assert_array_equal(da, db, err_msg=f"{what}: {name}")
        else:
            np.testing.assert_allclose(da, db, rtol=RTOL, atol=ATOL,
                                       err_msg=f"{what}: {name}")


def udf_calls_left(plan) -> int:
    from repro.core import relalg as R
    from repro.core import scalar as S

    return sum(isinstance(e, S.UdfCall)
               for n in R.walk_plan_deep(plan)
               for ex in n.exprs() for e in S.walk(ex))


def assert_no_ladder_faults(sched, sessions, what: str) -> None:
    bad = {k: sched.stats[k] for k in LADDER_FAULTS if sched.stats[k]}
    if bad:
        raise AssertionError(f"{what}: the drain fell down the ladder: {bad}")
    for s in sessions:
        if s.persist_stats.get("save_errors"):
            raise AssertionError(f"{what}: PlanStore save errors: "
                                 f"{s.persist_stats}")


# ---------------------------------------------------------------- data
def lineitem_host(db) -> dict:
    import numpy as np

    li = db.catalog["lineitem"].columns
    out = {n: np.asarray(c.data) for n, c in li.items()}
    for n in ("l_returnflag", "l_linestatus"):
        out[n] = np.array(li[n].dictionary.vocab)[out[n]]
    return out


def q1_reference(li: dict, dates: dict) -> dict:
    """Q1 per (returnflag, linestatus), in float64."""
    import numpy as np

    sel = li["l_shipdate"] <= dates["1998-12-01"] - 90
    price = li["l_extendedprice"].astype(np.float64)
    disc = li["l_discount"].astype(np.float64)
    tax = li["l_tax"].astype(np.float64)
    qty = li["l_quantity"].astype(np.float64)
    ref = {}
    for rf in np.unique(li["l_returnflag"]):
        for ls in np.unique(li["l_linestatus"]):
            g = sel & (li["l_returnflag"] == rf) & (li["l_linestatus"] == ls)
            if not g.any():
                continue
            ref[(rf, ls)] = {
                "sum_qty": qty[g].sum(),
                "sum_base": price[g].sum(),
                "sum_disc_price": (price[g] * (1 - disc[g])).sum(),
                "sum_charge": (price[g] * (1 - disc[g]) * (1 + tax[g])).sum(),
                "avg_qty": qty[g].mean(),
                "avg_price": price[g].mean(),
                "count_order": float(g.sum()),
            }
    return ref


def q6_reference(li: dict, dates: dict) -> float:
    """Q6 revenue in float64.  The discount bounds are the UDF's float32
    variables (0.06 ± 0.01 evaluated in float32), as the UDF declares."""
    import numpy as np

    f32 = np.float32
    lo, hi = f32(f32(0.06) - f32(0.01)), f32(f32(0.06) + f32(0.01))
    d = li["l_discount"]
    sel = ((li["l_shipdate"] >= dates["1994-01-01"])
           & (li["l_shipdate"] < dates["1995-01-01"])
           & (li["l_quantity"] < 24) & (d >= lo) & (d <= hi))
    return float((li["l_extendedprice"][sel].astype(np.float64)
                  * d[sel].astype(np.float64)).sum())


def check_q1(result, ref: dict) -> None:
    import numpy as np

    t = result.table
    keys = zip(*(np.array(t.columns[n].dictionary.vocab)[
        np.asarray(t.columns[n].data)] for n in ("l_returnflag", "l_linestatus")))
    cols = {n: np.asarray(c.data) for n, c in t.columns.items()}
    got = {key: {n: float(cols[n][i]) for n in ref.get(key, ())}
           for i, key in enumerate(keys)}
    if set(got) != set(ref):
        raise AssertionError(f"Q1 groups {sorted(got)} != {sorted(ref)}")
    for key, want in ref.items():
        for n, v in want.items():
            np.testing.assert_allclose(got[key][n], v, rtol=RTOL,
                                       err_msg=f"Q1 {key} {n} vs float64")


def check_q6(result, ref: float) -> None:
    import numpy as np

    got = float(np.asarray(result.table.columns["revenue"].data)[0])
    np.testing.assert_allclose(got, ref, rtol=RTOL,
                               err_msg="Q6 revenue vs float64")


# ---------------------------------------------------------------- statements
def register_total_price(db) -> None:
    """Figure 1's ``total_price(@key)``, pointed at TPC-H's orders."""
    from repro.core import UdfBuilder, col, lit, param, scan, sum_, var

    u = UdfBuilder("total_price", [("key", "int32")], "float32")
    u.declare("price", "float32")
    u.select({"price": sum_(col("o_totalprice"))}, frm=scan("orders"),
             where=col("o_custkey") == param("key"))
    with u.if_(var("price").is_null()):
        u.return_(lit(0.0))
    u.return_(var("price"))
    db.create_function(u.build())


def load_session(sf: float, seed: int, store=None):
    """TPC-H at ``sf`` plus the decorrelation and cursor-loop tables at
    their benchmark builders' shapes, with every UDF the smoke calls."""
    from benchmarks import bench_cursor_loops, bench_decorrelate
    from benchmarks.tpch_udfs import register_udfs
    from repro.core import Session
    from repro.data.tpch import generate_tpch

    db = Session(store=store)
    generate_tpch(db, sf=sf, seed=seed)
    register_udfs(db)
    register_total_price(db)
    bench_decorrelate.create_tables(db, bench_decorrelate.SWEEP[-1],
                                    facts="decorr_facts", keys="decorr_keys",
                                    seed=seed)
    bench_cursor_loops.create_tables(db, facts="loop_facts", keys="loop_keys",
                                     fn="floop", seed=seed)
    return db


def served_statements():
    """name -> (query builder, bindings(rng, n, n_customers))."""
    from benchmarks import bench_cursor_loops, bench_decorrelate
    from benchmarks.tpch_udfs import D
    from repro.core import col, count_, lit, param, scan, sum_, udf

    def q6_cutoff():
        return (scan("lineitem")
                .filter(udf("isShippedBefore", col("l_shipdate"), lit(0),
                            param("cutoff")) == 1)
                .agg(revenue=sum_(udf("discount_price", col("l_extendedprice"),
                                      col("l_discount"))),
                     n=count_()))

    def total_price():
        return (scan("customer").filter(col("c_custkey") == param("key"))
                .compute(total=udf("total_price", col("c_custkey")))
                .project("c_custkey", "total"))

    def zipf(rng, n, hi):  # ranks 0.. with a heavy head, clipped to < hi
        return [int(min(z - 1, hi - 1)) for z in rng.zipf(1.3, n)]

    return {
        "q6_cutoff": (q6_cutoff, lambda rng, n, nc: [
            {"cutoff": D["1994-01-01"] + 7 * z} for z in zipf(rng, n, 200)]),
        "total_price": (total_price, lambda rng, n, nc: [
            {"key": z} for z in zipf(rng, n, nc)]),
        "decorr": (lambda: bench_decorrelate.query("decorr_facts",
                                                   "decorr_keys"),
                   lambda rng, n, nc: [{"minq": z}
                                       for z in zipf(rng, n, 10)]),
        "cursor_loop": (lambda: bench_cursor_loops.query("loop_keys", "floop"),
                        lambda rng, n, nc: [
                            {"cut": 1 + z, "shift": float(round(s, 2))}
                            for z, s in zip(zipf(rng, n, bench_cursor_loops.N_KEYS),
                                            rng.uniform(-1, 2, n))]),
    }


# ---------------------------------------------------------------- phases
def phase_device(args):
    import jax

    if args.rehearse_cpu:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        raise SystemExit(f"chip_smoke: no TPU (JAX's first device is "
                         f"{dev.platform}); --rehearse-cpu runs on the CPU")
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    log("device", f"platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} compile_cache={cache}")
    return dev, devices


def device_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    if "bytes_in_use" not in stats:
        return "bytes_in_use=n/a"
    return (f"bytes_in_use={stats['bytes_in_use']} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")


def phase_load(args, dev, store):
    t0 = time.perf_counter()
    db = load_session(args.sf, args.seed, store)
    db._content_env_token()  # the persistent keys' content digest
    catalog_bytes = sum(t.nbytes() for t in db.catalog.values())
    log("load", f"sf={args.sf} lineitem_rows={db.catalog['lineitem'].num_rows}"
        f" load_s={time.perf_counter() - t0:.2f} catalog_bytes={catalog_bytes}"
        f" {device_bytes(dev)}")
    return db


def phase_tpch(db):
    from benchmarks.tpch_udfs import D, QUERIES
    from repro.core import FROID

    li = lineitem_host(db)
    refs = {"Q1": q1_reference(li, D), "Q6": q6_reference(li, D)}
    warm = {}
    for name, (q_udf, q_orig) in QUERIES.items():
        stmt = db.prepare(q_udf(), FROID)
        left = udf_calls_left(stmt.plan)
        if left:
            raise AssertionError(f"{name}: {left} UDF calls survived inlining")
        t0 = time.perf_counter()
        cold = stmt.execute()
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        r = stmt.execute()
        warm_s = time.perf_counter() - t0
        if cold.cache_hit or not r.cache_hit:
            raise AssertionError(f"{name}: cold/warm cache flags "
                                 f"{cold.cache_hit}/{r.cache_hit}")
        assert_same(r.masked, cold.masked, f"{name} warm vs cold", exact=True)
        t0 = time.perf_counter()
        orig = db.prepare(q_orig(), FROID).execute()
        checked = [f"original({time.perf_counter() - t0:.2f}s)"]
        assert_same(r.masked, orig.masked, f"{name} UDF vs original")
        if name == "Q1":
            check_q1(r, refs["Q1"])
            checked.append("float64")
        if name == "Q6":
            check_q6(r, refs["Q6"])
            checked.append("float64")
        warm[name] = r
        log("tpch", f"{name} cold_s={cold_s:.2f} warm_s={warm_s:.4f} "
            f"compile_s~{cold_s - warm_s:.2f} rows={int(r.masked.mask.sum())}"
            f" checked={','.join(checked)}")
    return warm


def phase_pallas(db, rehearse: bool):
    from benchmarks.bench_batchmode import table4_query
    from benchmarks.tpch_udfs import q12_udf
    from repro.core import FROID

    relagg = dataclasses.replace(FROID, name="froid+relagg", pallas_agg=True)
    for name, q in (("table4", table4_query), ("Q12", q12_udf)):
        stmt = db.prepare(q(), relagg)
        t0 = time.perf_counter()
        r = stmt.execute()
        cold_s = time.perf_counter() - t0
        if not r.stats.get("relagg_groupaggs"):
            raise AssertionError(f"{name}: the GroupAgg did not take relagg")
        base = db.prepare(q(), FROID).execute()
        assert_same(r.masked, base.masked, f"{name} relagg vs default")
        entry, _, _ = db._executable(stmt.node, stmt._query_fp, stmt.policy,
                                     None)
        kernel = "tpu_custom_call" in entry.compiled.as_text()
        if not kernel and not rehearse:
            raise AssertionError(f"{name}: no tpu_custom_call in the "
                                 f"compiled HLO (kernel not compiled)")
        log("pallas", f"{name} cold_s={cold_s:.2f} matches_default=True "
            f"kernel_in_hlo={kernel}")


def phase_served(db, small, args):
    import numpy as np

    from repro.core import FROID, INTERPRETED
    from repro.resilience.ladder import ResilienceConfig
    from repro.serve.scheduler import CoalescingScheduler

    rng = np.random.default_rng(args.seed)
    n_cust = db.catalog["customer"].num_rows
    specs = served_statements()
    stmts = {n: db.prepare(b(), FROID) for n, (b, _) in specs.items()}
    plists = {n: f(rng, N_BINDINGS, n_cust) for n, (_, f) in specs.items()}
    serial, many = {}, {}
    for name, stmt in stmts.items():
        t0 = time.perf_counter()
        serial[name] = [stmt.execute(params=p) for p in plists[name]]
        serial_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        rs = stmt.execute_many(plists[name])
        for r in rs:
            r.masked  # noqa: B018 — materialize inside the timing
        many_s = time.perf_counter() - t0
        if not rs[0].stats.get("batched"):
            raise AssertionError(f"{name}: execute_many did not batch")
        for i, (r, s) in enumerate(zip(rs, serial[name])):
            assert_same(r.masked, s.masked, f"{name}[{i}] many vs serial")
        many[name] = rs
        distinct = len({tuple(sorted(p.items())) for p in plists[name]})
        log("served", f"{name} execute_many({N_BINDINGS}, {distinct} "
            f"distinct) cold_s={many_s:.2f} serial_loop_s={serial_s:.2f} "
            f"bucket={rs[0].stats['batch_bucket']} matches_serial=True")

    sched = CoalescingScheduler(
        fuse=True, window_s=3600.0,
        resilience=ResilienceConfig(interp_fallback=False))
    tickets = [(name, i, sched.submit(stmts[name], plists[name][i]))
               for i in range(N_BINDINGS) for name in stmts]
    if sched.pending != len(tickets):
        raise AssertionError("the scheduler drained before the flush")
    t0 = time.perf_counter()
    sched.flush()
    drain_s = time.perf_counter() - t0
    assert_no_ladder_faults(sched, [db], "fused drain")
    if not (sched.stats["tier_fused_ok"] and sched.stats["fused_batches"]):
        raise AssertionError(f"no fused wave formed: {sched.stats}")
    fused = {}
    for name, i, t in tickets:
        r = t.result()
        assert_same(r.masked, serial[name][i].masked,
                    f"{name}[{i}] fused drain vs serial")
        fused.setdefault(name, []).append(r)
    width = max(r.stats.get("fused_statements", 0)
                for rs in fused.values() for r in rs)
    if width < 2:
        raise AssertionError("no ticket rode a multi-statement program")
    log("served", f"fused drain of {len(tickets)} tickets drain_s={drain_s:.2f}"
        f" tier_fused_ok={sched.stats['tier_fused_ok']} fused_statements="
        f"{width} matches_serial=True")

    # the per-row reference, on a catalog small enough to interpret
    srng = np.random.default_rng(args.seed + 1)
    sn_cust = small.catalog["customer"].num_rows
    for name, (builder, f) in specs.items():
        plist = f(srng, SMALL_BINDINGS, sn_cust)
        rs = small.prepare(builder(), FROID).execute_many(plist)
        interp = [small.execute(builder(), INTERPRETED, params=p)
                  for p in plist]
        for i, (r, s) in enumerate(zip(rs, interp)):
            assert_same(r.masked, s.masked, f"{name}[{i}] FROID vs "
                        f"INTERPRETED (small catalog)")
    log("served", f"small catalog (sf={SMALL_SF}): FROID execute_many == "
        f"INTERPRETED for {len(specs)} statements x {SMALL_BINDINGS}")
    return stmts, plists, many, fused


def phase_warm_start(db, store_dir, tpch_warm, served, dev):
    from benchmarks.tpch_udfs import QUERIES
    from repro.core import FROID, Session
    from repro.persist import PlanStore
    from repro.resilience.ladder import ResilienceConfig
    from repro.serve.scheduler import CoalescingScheduler

    stmts0, plists, many0, fused0 = served
    t0 = time.perf_counter()
    fresh = Session(store=PlanStore(store_dir))
    fresh.catalog.update(db.catalog)
    fresh.registry.update(db.registry)
    for name, (q_udf, _) in QUERIES.items():
        r = fresh.prepare(q_udf(), FROID).execute()
        assert_same(r.masked, tpch_warm[name].masked,
                    f"{name} warm-start", exact=True)
    tpch_s = time.perf_counter() - t0
    for name, stmt0 in stmts0.items():
        rs = fresh.prepare(stmt0.node, FROID).execute_many(plists[name])
        for i, (r, s) in enumerate(zip(rs, many0[name])):
            assert_same(r.masked, s.masked, f"{name}[{i}] warm-start "
                        f"execute_many", exact=True)
    sched = CoalescingScheduler(
        fuse=True, window_s=3600.0,
        resilience=ResilienceConfig(interp_fallback=False))
    tickets = [(name, i, sched.submit(fresh.prepare(stmts0[name].node, FROID),
                                      plists[name][i]))
               for i in range(N_BINDINGS) for name in stmts0]
    sched.flush()
    assert_no_ladder_faults(sched, [fresh], "warm-start fused drain")
    for name, i, t in tickets:
        assert_same(t.result().masked, fused0[name][i].masked,
                    f"{name}[{i}] warm-start fused drain", exact=True)
    ps = fresh.persist_stats
    if ps["hits"] <= 0 or ps["rejects"] or ps["save_errors"]:
        raise AssertionError(f"warm start: {ps}")
    log("warm_start", f"fresh session on the same PlanStore: "
        f"tpch_first_calls_s={tpch_s:.2f} total_s="
        f"{time.perf_counter() - t0:.2f} persist_hits={ps['hits']} "
        f"misses={ps['misses']} saves={ps['saves']} rejects={ps['rejects']} "
        f"identical=True {device_bytes(dev)}")


def phase_sharded(args, devices):
    """``--chips 4``: one statement's batch under ``"FROID+data4"`` (the
    placement the ``udf_calls.open.x4`` cell names), against the same batch
    on one device."""
    import jax
    import numpy as np

    from repro.core import FROID, resolve_policy
    from repro.dist.sharding import batch_sharding

    if len(devices) < 4:
        raise SystemExit(f"chip_smoke --chips 4: {len(devices)} devices")
    policy = resolve_policy("FROID+data4")
    mesh = policy.mesh
    t0 = time.perf_counter()
    db = load_session(args.sf, args.seed)
    log("load", f"sf={args.sf} load_s={time.perf_counter() - t0:.2f}")
    builder, bindings = served_statements()["q6_cutoff"]
    plist = bindings(np.random.default_rng(args.seed), N_BINDINGS,
                     db.catalog["customer"].num_rows)
    sharded = db.prepare(builder(), policy)
    t0 = time.perf_counter()
    rs = sharded.execute_many(plist)
    for r in rs:
        r.masked  # noqa: B018
    shard_s = time.perf_counter() - t0
    st = rs[0].stats
    if not st.get("sharded") or st.get("shard_devices") != 4:
        raise AssertionError(f"batch did not shard over 4 devices: {st}")
    t0 = time.perf_counter()
    one = db.prepare(builder(), FROID).execute_many(plist)
    one_s = time.perf_counter() - t0
    for i, (a, b) in enumerate(zip(rs, one)):
        assert_same(a.masked, b.masked, f"q6_cutoff[{i}] sharded vs one "
                    f"device")
    cats = db._catalog_args_replicated(mesh, db._catalog_token(),
                                       sharded.policy.shard_token())
    price = cats["lineitem"]["l_extendedprice"][0]
    log("sharded", "catalog l_extendedprice shards: " + ", ".join(
        f"dev{s.device.id}{s.data.shape}" for s in price.addressable_shards))
    probe = jax.device_put(np.zeros((st["batch_bucket"],), np.int32),
                           batch_sharding(mesh, st["batch_bucket"]))
    log("sharded", "param axis shards: " + ", ".join(
        f"dev{s.device.id}[{s.index[0].start}:{s.index[0].stop}]"
        for s in probe.addressable_shards))
    log("sharded", f"execute_many({N_BINDINGS}) sharded cold_s={shard_s:.2f}"
        f" one_device cold_s={one_s:.2f} bucket={st['batch_bucket']} "
        f"shard_devices={st['shard_devices']} matches_one_device=True")


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (1.0 = 6M lineitems)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the sharded execute_many phase")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run on the CPU, Pallas interpreted")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    dev, devices = phase_device(args)
    if args.chips == 4:
        phase_sharded(args, devices)
        count = 4
    else:
        from repro.persist import PlanStore

        shutil.rmtree(STORE_DIR, ignore_errors=True)
        db = phase_load(args, dev, PlanStore(STORE_DIR))
        tpch_warm = phase_tpch(db)
        phase_pallas(db, args.rehearse_cpu)
        small = load_session(SMALL_SF, args.seed)
        served = phase_served(db, small, args)
        phase_warm_start(db, STORE_DIR, tpch_warm, served, dev)
        count = len(devices)
    log("done", f"all phases passed in {time.perf_counter() - T_START:.1f}s")
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind, "count": count}}


if __name__ == "__main__":
    print(json.dumps(main()), flush=True)
