"""Cost-based routing layer (ISSUE-8 contract): the static cost model,
the online router (measured wave EMAs, fault-window sample exclusion,
bounded decision log), the three routing axes (policy, batch bucket,
fuse-or-not), the ``ROUTED`` preset / ``policy.routed()`` surface, the
routing conformance oracle, and the stats audit (monotone counters,
``wave_tickets`` normalization, no double counting).

Runs everywhere; the generative layer in ``test_property_froid.py``
drives the same routing oracle over random overlap queues in CI.
"""
import numpy as np
import pytest

import jax

from repro.core import FROID, HEKATON, ROUTED, Session, col, param, scan
from repro.cost import (
    CostRouter,
    estimate_compile_s,
    estimate_plan,
    estimate_statement_s,
)
from repro.cost.router import _Ema
from repro.resilience import FaultInjector, FaultSpec
from repro.serve.scheduler import CoalescingScheduler
from conformance_util import (
    FIXED_PROGRAMS,
    N_ROWS,
    assert_rows_equal,
    build_udf,
    check_routing_oracle,
    fusion_calls_spec,
    fusion_queries,
    make_session,
    param_query,
)


def _routed_session(seed: int = 3):
    db = make_session(seed)
    db.create_function(
        build_udf(FIXED_PROGRAMS["uncorrelated_sum_case"]).build())
    return db


# ---------------------------------------------------------------------------
# policy surface: the ROUTED preset and the routed() tuning knob
# ---------------------------------------------------------------------------


def test_routed_preset_and_helper():
    assert ROUTED.route and ROUTED.name == "routed"
    # route is a tuning knob: routed plans/caches are shared with FROID
    assert ROUTED.fingerprint() == FROID.fingerprint()
    assert FROID.routed().route
    assert not ROUTED.routed(False).route
    # no-op toggles return the same object (replace() churns cache keys)
    assert ROUTED.routed() is ROUTED
    assert FROID.routed(False) is FROID


def test_router_attaches_lazily():
    db = Session()
    db.create_table("t", x=np.arange(8))
    assert db.cost_stats == {"enabled": False}
    q = scan("t").compute(y=col("x") * 2.0).project("y")
    db.prepare(q, FROID)
    assert db.cost_router is None  # unrouted statements never pay for one
    db.prepare(q, ROUTED)
    assert isinstance(db.cost_router, CostRouter)
    assert db.cost_stats["enabled"]


# ---------------------------------------------------------------------------
# static cost model sanity
# ---------------------------------------------------------------------------


def test_estimates_scale_with_work():
    db = _routed_session()
    stmt = db.prepare(param_query(), FROID)
    plan = stmt.plan
    prof = estimate_plan(plan, db.catalog)
    assert prof.rows > 0 and prof.flops > 0 and prof.nodes > 0
    assert prof.seconds() > 0
    # more tickets per wave = more estimated work; more devices = less
    e1 = estimate_statement_s(plan, db.catalog, bucket=1)
    e64 = estimate_statement_s(plan, db.catalog, bucket=64)
    assert e64 > e1
    assert estimate_statement_s(plan, db.catalog, bucket=64, devices=8) < e64
    # compile estimates grow with plan size
    small = db.prepare(scan("keys").compute(z=col("k") * 2.0), FROID).plan
    assert estimate_compile_s(plan) > estimate_compile_s(small) > 0


def test_cost_model_peaks_are_the_benchmarks_v5e_peaks():
    """The engine keeps its own copy of the two peaks (the engine imports
    nothing of the benchmark); they must equal the benchmark's table."""
    import sys
    from pathlib import Path

    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench.peaks import peaks
    from repro.cost import model

    v5e = peaks("TPU v5 lite")
    assert model.PEAK_FLOPS == v5e["bf16_flops_per_s"]
    assert model.HBM_BW == v5e["hbm_bytes_per_s"]


# ---------------------------------------------------------------------------
# sample intake: EMA updates and fault-window exclusion
# ---------------------------------------------------------------------------


def test_observe_updates_ema_and_counters():
    db = _routed_session()
    stmt = db.prepare(param_query(), ROUTED)
    r = db.cost_router
    r.observe_serial(stmt._query_fp, stmt.policy, 1.0)
    r.observe_serial(stmt._query_fp, stmt.policy, 0.0)
    key = ("serial", stmt._query_fp, stmt.policy.fingerprint())
    ema = r.measured[key]
    assert ema.n == 2 and 0.0 < ema.wave_s < 1.0  # EMA, not last-write-wins
    assert r.stats["samples"] == 2 and r.stats["samples_excluded"] == 0


def test_suppress_drops_samples_and_is_reentrant():
    db = _routed_session()
    stmt = db.prepare(param_query(), ROUTED)
    r = db.cost_router
    with r.suppress():
        with r.suppress():  # ladder tiers nest retries inside demotions
            r.observe_serial(stmt._query_fp, stmt.policy, 9.9)
        assert r.suppressed
        r.observe_many(stmt._query_fp, stmt.policy, (), 4, 9.9, 4,
                       shard=False)
    assert not r.suppressed
    assert r.stats["samples_excluded"] == 2 and r.stats["samples"] == 0
    assert not r.measured and not r.per_ticket  # nothing trained


def test_fault_window_samples_excluded_end_to_end():
    """Dispatch faults push the ladder into retries/demotions; the routed
    session must drop those samples instead of training on them."""
    db = _routed_session()
    qs = fusion_queries()
    stmts = [db.prepare(q, ROUTED) for q in qs]
    FaultInjector([FaultSpec(site="dispatch", times=3)]).install(db)
    sched = CoalescingScheduler(max_batch=256, window_s=10.0,
                                clock=lambda: 0.0, fuse=True,
                                sleep=lambda s: None)
    tickets = [sched.submit(stmts[i], p) for i, p in fusion_calls_spec()]
    sched.flush()
    for t in tickets:
        t.result()  # the ladder recovers every ticket fault-free
    cs = db.cost_stats
    assert cs["samples_excluded"] >= 1, cs
    # the fault-free oracle answer still comes back (ladder floor)
    oracle = _routed_session()
    o_stmts = [oracle.prepare(q, FROID) for q in qs]
    for (i, p), t in zip(fusion_calls_spec(), tickets):
        assert_rows_equal(o_stmts[i].execute(params=p), t.result(),
                          "faulted routed ticket vs oracle")


# ---------------------------------------------------------------------------
# axis: FROID vs HEKATON policy
# ---------------------------------------------------------------------------


def test_choose_policy_prefers_measured_winner():
    db = _routed_session()
    stmt = db.prepare(param_query(), ROUTED)
    r = db.cost_router
    cands = r._policy_candidates(stmt)
    assert len(cands) >= 2  # the UDF makes froid/hekaton genuinely differ
    alt = next(c for c, cfp in cands
               if cfp != stmt.policy.fingerprint())
    fp = stmt._query_fp
    # same-kind measured evidence: the alternative is 10x cheaper
    r.per_ticket[("many", fp, stmt.policy.fingerprint())] = _Ema(1e-2)
    r.per_ticket[("many", fp, alt.fingerprint())] = _Ema(1e-3)
    chosen = r.choose_policy(stmt)
    assert chosen.fingerprint() == alt.fingerprint()
    assert r.stats["policy_reroutes"] == 1
    assert any(d["axis"] == "policy" and d["why"] == "measured"
               for d in r.decisions)
    # flipped evidence flips the route back
    r.per_ticket[("many", fp, alt.fingerprint())] = _Ema(1e-1)
    assert r.choose_policy(stmt).fingerprint() == stmt.policy.fingerprint()


def test_choose_policy_estimate_gated_exploration():
    """Without measurements, an alternative is explored only on a clear
    estimated win — equal estimates never justify a fresh compile."""
    db = _routed_session()
    stmt = db.prepare(param_query(), ROUTED)
    r = db.cost_router
    # force equal estimates: every candidate looks the same on paper
    for c, cfp in r._policy_candidates(stmt):
        key = ("policy", stmt._query_fp, cfp,
               db._catalog_token())
        r.estimates[key] = 1.0
    assert r.choose_policy(stmt).fingerprint() == stmt.policy.fingerprint()
    assert r.stats["policy_reroutes"] == 0


def test_routed_execute_delegates_and_matches():
    """A policy reroute actually executes under the delegate — and the
    answer is still the oracle's (the mode oracle's guarantee, now load-
    bearing for routing)."""
    db = _routed_session()
    stmt = db.prepare(param_query(), ROUTED)
    params = {"cut": 5, "shift": 0.5}
    expected = _routed_session().execute(param_query(), FROID, params=params)
    r = db.cost_router
    alt = next(c for c, cfp in r._policy_candidates(stmt)
               if cfp != stmt.policy.fingerprint())
    fp = stmt._query_fp
    r.per_ticket[("many", fp, stmt.policy.fingerprint())] = _Ema(1.0)
    r.per_ticket[("many", fp, alt.fingerprint())] = _Ema(1e-6)
    got = stmt.execute(params=params)
    assert_rows_equal(expected, got, "rerouted execute vs oracle")
    assert db.cost_stats["policy_reroutes"] >= 1
    # the delegate runs unrouted: one routing decision per call, no loops
    batched = stmt.execute_many([params, {"cut": 3, "shift": 1.5}])
    assert_rows_equal(expected, batched[0], "rerouted execute_many vs oracle")


# ---------------------------------------------------------------------------
# axis: batch bucket (ride a warm larger bucket over a cold compile)
# ---------------------------------------------------------------------------


def test_choose_bucket_rides_warm_bucket():
    db = _routed_session()
    stmt = db.prepare(param_query(), ROUTED)
    r = db.cost_router
    # warm the bucket-8 configuration organically
    params8 = [{"cut": int(k % 6), "shift": 0.5} for k in range(8)]
    stmt.execute_many(params8)
    key8 = next(k for k in r.measured if k[0] == "many" and k[-1] == 8)
    sig = key8[3]
    # measured says bucket 8 is nearly free; the cold bucket-4 compile
    # estimate cannot beat that
    r.measured[key8].wave_s = 1e-9
    assert r.choose_bucket(stmt, sig, 3, 4, 256, shard=False) == 8
    assert r.stats["bucket_rides"] == 1
    # a warm *natural* bucket is never overridden
    assert r.choose_bucket(stmt, sig, 7, 8, 256, shard=False) == 8
    # measured says the warm bucket is terrible: pay the cold compile
    r.measured[key8].wave_s = 1e9
    assert r.choose_bucket(stmt, sig, 3, 4, 256, shard=False) == 4


def test_bucket_ride_preserves_results_end_to_end():
    db = _routed_session()
    stmt = db.prepare(param_query(), ROUTED)
    stmt.execute_many([{"cut": int(k % 6), "shift": 0.5} for k in range(8)])
    r = db.cost_router
    for k in list(r.measured):
        if k[0] == "many":
            r.measured[k].wave_s = 1e-9  # make every warm bucket a ride
    small = [{"cut": 2, "shift": 0.5}, {"cut": 5, "shift": 0.5},
             {"cut": 1, "shift": 0.5}]
    got = stmt.execute_many(small)
    oracle = _routed_session()
    o = oracle.prepare(param_query(), FROID)
    for i, (p, g) in enumerate(zip(small, got)):
        assert_rows_equal(o.execute(params=p), g, f"bucket-ride[{i}]")
    assert db.cost_stats["bucket_rides"] >= 1
    # the ridden wave reports the bucket it actually ran in
    assert got[0].stats["batch_bucket"] == 8


# ---------------------------------------------------------------------------
# axis: fuse-or-not (wave-level routing through the scheduler)
# ---------------------------------------------------------------------------


def test_fuse_axis_explores_both_arms_then_measures():
    """Drain the same mixed wave three times: explore-fused, then
    explore-unfused, then a measured decision — every wave conformant."""
    cs = check_routing_oracle(7, N_ROWS, fuse=True, waves=3)
    assert cs["waves_fused"] >= 1 and cs["waves_unfused"] >= 1, cs
    fuse_whys = [d["why"] for d in cs["decision_log"]
                 if d["axis"] == "fuse"]
    assert fuse_whys[0] == "explore-fused"
    assert "explore-unfused" in fuse_whys
    assert fuse_whys[-1] == "measured"


def test_route_fuse_requires_all_routed():
    """A wave with any unrouted member keeps the scheduler's static fuse
    knob — routing is per-statement opt-in, not a session-wide ambush."""
    db = _routed_session()
    qs = fusion_queries()
    stmts = [db.prepare(qs[0], ROUTED), db.prepare(qs[1], FROID)]
    sched = CoalescingScheduler(max_batch=256, window_s=10.0,
                                clock=lambda: 0.0, fuse=True)
    t1 = sched.submit(stmts[0], {"cut": 5, "shift": 0.5})
    t2 = sched.submit(stmts[1], {"minq": 4, "scale": 2.0})
    sched.flush()
    t1.result(), t2.result()
    assert sched.stats["routed_waves"] == 0
    assert sched.stats["fused_batches"] >= 1  # static knob still fused it


# ---------------------------------------------------------------------------
# routing conformance oracle: sharded/unsharded × fused/unfused
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "unfused"])
@pytest.mark.parametrize("shard", [False, True], ids=["unsharded", "sharded"])
def test_routing_oracle_matrix(fuse, shard):
    check_routing_oracle(11, N_ROWS, fuse=fuse, shard=shard, waves=2)


def test_routing_oracle_empty_table():
    check_routing_oracle(12, 0, fuse=True, waves=2)


# ---------------------------------------------------------------------------
# stats audit: monotone counters, wave normalization, snapshot shape
# ---------------------------------------------------------------------------


def test_stats_audit_monotone_and_consistent():
    """Scripted drain: cumulative counters never decrease across waves,
    per-ticket wave stats carry the ``wave_tickets`` divisor, and the
    router's sample accounting adds up."""
    db = _routed_session()
    qs = fusion_queries()
    stmts = [db.prepare(q, ROUTED) for q in qs]
    spec = fusion_calls_spec()
    sched = CoalescingScheduler(max_batch=256, window_s=10.0,
                                clock=lambda: 0.0, fuse=True)
    mono_keys = ("samples", "samples_excluded", "decisions",
                 "policy_reroutes", "bucket_rides", "waves_fused",
                 "waves_unfused")
    cache_keys = ("fuse_hits", "fuse_misses", "cse_hits",
                  "cse_shared_nodes", "persist_hits", "persist_misses",
                  "persist_rejects")
    prev_cost = {k: 0 for k in mono_keys}
    prev_cache = {k: 0 for k in cache_keys}
    prev_sched = {"demote_fused_to_many": 0, "demote_many_to_serial": 0,
                  "demote_serial_to_interp": 0, "deadline_shed": 0}
    for wave in range(3):
        tickets = [sched.submit(stmts[i], p) for i, p in spec]
        sched.flush()
        results = [t.result() for t in tickets]
        cs = db.cost_stats
        for k in mono_keys:
            assert cs[k] >= prev_cost[k], (wave, k, cs)
            prev_cost[k] = cs[k]
        for k in cache_keys:
            assert db.cache_stats[k] >= prev_cache[k], (wave, k)
            prev_cache[k] = db.cache_stats[k]
        for k in prev_sched:
            assert sched.stats[k] >= prev_sched[k], (wave, k)
            prev_sched[k] = sched.stats[k]
        for r in results:
            st = r.stats
            assert st.get("dispatch_s", 0.0) >= 0.0
            assert st.get("sync_s", 0.0) >= 0.0
            if st.get("fused"):
                # wave-level numbers are broadcast to every ticket of the
                # wave; wave_tickets is the divisor that undoes it
                assert st["wave_tickets"] == len(results)
                assert st["cse_pool_slots"] >= st["cse_bindings"] >= 0
            elif "wave_tickets" in st:
                assert 1 <= st["wave_tickets"] <= len(spec)
    # router sample accounting: each intake either trains or is excluded
    n_emas = sum(e.n for e in db.cost_router.measured.values())
    assert n_emas == cs["samples"]


def test_cost_stats_snapshot_printable():
    cs = check_routing_oracle(13, N_ROWS, fuse=True, waves=2)
    for label, rec in cs["measured"].items():
        assert isinstance(label, str) and ":" in label
        assert rec["n"] >= 1 and rec["wave_s"] >= 0.0
    for d in cs["decision_log"]:
        assert {"axis", "choice", "why"} <= d.keys()
    # decision log is bounded: it must never grow past the deque cap
    from repro.cost.router import DECISION_LOG
    assert len(cs["decision_log"]) <= DECISION_LOG


def test_routed_sharded_many_matches_serial():
    """The routed execute_many path on a sharded mesh still equals the
    serial oracle (bucket riding and sharding compose)."""
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    db = _routed_session()
    stmt = db.prepare(param_query(), ROUTED.sharded(mesh))
    params = [{"cut": int(k % 6), "shift": 0.5} for k in range(8)]
    got = stmt.execute_many(params)
    oracle = _routed_session()
    o = oracle.prepare(param_query(), FROID)
    for i, (p, g) in enumerate(zip(params, got)):
        assert_rows_equal(o.execute(params=p), g, f"routed sharded[{i}]")
    assert db.cost_stats["samples"] >= 1
