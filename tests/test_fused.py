"""Multi-statement fusion engine: plan merge, fusability analysis, the
fused-executable session cache tier, the scheduler's fusion drain mode,
and the fusion conformance oracle (ISSUE-4 contract).

Runs everywhere; the CI sharded-8dev job re-runs it under a forced
8-device CPU mesh so the sharded fused program exercises real placement.
"""
import numpy as np
import pytest

import jax

from repro.core import (
    FROID,
    HEKATON,
    INTERPRETED,
    Session,
    UdfBuilder,
    col,
    lit,
    param,
    scan,
    sum_,
    udf,
    var,
)
from repro.fuse import (
    is_fusable,
    merge_plans,
    partition_calls,
    plan_is_pure,
    subtree_is_constant,
)
from repro.serve.scheduler import CoalescingScheduler
from conformance_util import check_fusion_oracle


def _populate(db, n_detail=2000, n_t=200, seed=0):
    rng = np.random.default_rng(seed)
    db.create_table(
        "detail",
        d_key=rng.integers(0, 50, n_detail),
        d_val=rng.uniform(0, 100, n_detail).astype(np.float32),
    )
    db.create_table("T", a=rng.integers(0, 50, n_t))
    u = UdfBuilder("key_total", [("k", "int32")], "float32")
    u.declare("s", "float32")
    u.select({"s": sum_(col("d_val"))}, frm=scan("detail"),
             where=col("d_key") == param("k"))
    with u.if_(var("s").is_null()):
        u.return_(lit(0.0))
    u.return_(var("s"))
    db.create_function(u.build())


def _q_udf():
    return (
        scan("T")
        .filter(col("a") < param("cutoff"))
        .compute(v=udf("key_total", col("a")))
        .project("v")
    )


def _q_arith():
    return (
        scan("T")
        .filter(col("a") >= param("lo"))
        .compute(w=col("a") * param("scale"))
        .project("a", "w")
    )


def _q_paramfree():
    return scan("T").compute(z=col("a") * 2).project("z")


def _assert_same(serial, fused):
    assert len(serial) == len(fused)
    for s, f in zip(serial, fused):
        m = np.asarray(s.masked.mask)
        np.testing.assert_array_equal(m, np.asarray(f.masked.mask))
        for n, c in s.masked.table.columns.items():
            np.testing.assert_allclose(
                np.asarray(f.masked.table.columns[n].data)[m],
                np.asarray(c.data)[m], rtol=1e-5,
            )


@pytest.fixture
def db():
    s = Session()
    _populate(s)
    return s


def _mixed_calls(s1, s2, s3):
    return [
        (s1, {"cutoff": 10}), (s2, {"lo": 5, "scale": 2.0}), (s3, None),
        (s1, {"cutoff": 30}), (s2, {"lo": 20, "scale": 0.5}),
        (s1, {"cutoff": 7.5}),  # mixed signature member for s1
        (s3, {}),
    ]


# ---------------------------------------------------------------------------
# plan-merge pass
# ---------------------------------------------------------------------------


def test_merge_dedups_shared_scans(db):
    p1 = db.prepare(_q_udf(), FROID).plan
    p2 = db.prepare(_q_arith(), FROID).plan
    p3 = db.prepare(_q_paramfree(), FROID).plan
    merged = merge_plans([p1, p2, p3])
    # every plan scans T; the scan is param-free, so it dedups
    assert merged.stats["shared_subtrees"] >= 1
    assert merged.stats["shared_refs"] > merged.stats["shared_subtrees"]
    assert merged.stats["total_scans"] >= 3
    # marked ids resolve to fingerprints present in the shared list
    shared_fps = {fp for fp, _ in merged.shared}
    assert set(merged.shared_ids.values()) <= shared_fps


def test_merge_shares_nested_subtrees():
    """Every shared occurrence is marked and pooled — the repeated Filter
    *and* its repeated Scan child.  The pool is ordered innermost-first, so
    the Filter's pool build answers the Scan from the pool (nested
    sharing), while member traces are intercepted at the topmost mark and
    count maximal coverage only."""
    from repro.core import relalg as R

    scan_t = R.Scan("T")
    f1 = R.Filter(scan_t, col("a") < lit(5))
    # independently-built structurally-equal twin under a *different* root
    f2 = R.Filter(R.Scan("T"), col("a") < lit(5))
    merged = merge_plans([R.Project(f1, ["a"]), R.Compute(f2, {"b": col("a")})])
    fps = dict(merged.shared)
    assert len(fps) == 2  # the Filter and its shared Scan child
    assert merged.shared_ids[f1.node_id] == merged.shared_ids[f2.node_id]
    assert scan_t.node_id in merged.shared_ids  # nested occurrence pooled
    # innermost-first pool order: the Scan precedes the Filter that uses it
    order = [fp for fp, _ in merged.shared]
    assert order.index(merged.shared_ids[scan_t.node_id]) \
        < order.index(merged.shared_ids[f1.node_id])
    # coverage counts maximal marks only: two Filter refs, Scan subsumed
    assert merged.stats["shared_refs"] == 2
    assert merged.stats["cse_shared_nodes"] == 4  # 2 refs x 2-node subtree
    # identical whole plans share at the root (coverage goes all the way)
    whole = merge_plans([R.Project(f1, ["a"]), R.Project(f2, ["a"])])
    assert whole.stats["shared_refs"] == 2
    assert whole.stats["cse_shared_nodes"] == 6  # 2 refs x 3-node plan


def test_subtree_constness():
    from repro.core import relalg as R

    assert subtree_is_constant(R.Scan("T"))
    assert not subtree_is_constant(
        R.Filter(R.Scan("T"), col("a") < param("c"))
    )
    assert plan_is_pure(R.Project(R.Scan("T"), ["a"]))


# ---------------------------------------------------------------------------
# fusability analysis
# ---------------------------------------------------------------------------


def test_fusability_gates(db):
    s_froid = db.prepare(_q_udf(), FROID)
    s_eager = db.prepare(_q_udf(), INTERPRETED)
    s_nofuse = db.prepare(_q_arith(), FROID.fused(fuse=False))
    other = Session()
    _populate(other)
    s_foreign = other.prepare(_q_arith(), FROID)
    assert is_fusable(db, s_froid)
    assert not is_fusable(db, s_eager)       # no compiled plan to merge
    assert not is_fusable(db, s_nofuse)      # knob off
    assert not is_fusable(db, s_foreign)     # foreign session state
    groups, fallbacks = partition_calls(db, [
        (s_froid, {"cutoff": 1}), (s_eager, {"cutoff": 1}),
        (s_nofuse, {"lo": 1, "scale": 1.0}), (s_foreign, {"lo": 1, "scale": 1.0}),
    ])
    assert groups == []  # a single fusable statement gains nothing
    assert len(fallbacks) == 4


def test_max_fused_statements_splits(db):
    policy = FROID.fused(max_fused_statements=2)
    s1 = db.prepare(_q_udf(), policy)
    s2 = db.prepare(_q_arith(), policy)
    s3 = db.prepare(_q_paramfree(), policy)
    calls = [(s1, {"cutoff": 5}), (s2, {"lo": 1, "scale": 1.0}), (s3, {})]
    groups, fallbacks = partition_calls(db, calls)
    # 3 distinct statements, cap 2 -> one fused pair + one singleton fallback
    assert len(groups) == 1 and len({s._query_fp for _, s, _ in groups[0]}) == 2
    assert len(fallbacks) == 1
    rs = db.execute_fused(calls)
    _assert_same([s.execute(params=p) for s, p in calls], rs)
    assert rs[0].stats["fused_statements"] == 2
    assert "fused" not in rs[2].stats


def test_fuse_policy_knobs_are_not_identity():
    assert FROID.fused(fuse=False) == FROID
    assert FROID.fused(fuse=False).fingerprint() == FROID.fingerprint()
    assert FROID.fused(max_fused_statements=2).max_fused_statements == 2
    assert FROID.fuse and FROID.max_fused_statements == 8


# ---------------------------------------------------------------------------
# execute_fused: parity + tagged stats
# ---------------------------------------------------------------------------


def test_execute_fused_matches_serial(db):
    s1 = db.prepare(_q_udf(), FROID)
    s2 = db.prepare(_q_arith(), FROID)
    s3 = db.prepare(_q_paramfree(), FROID)
    calls = _mixed_calls(s1, s2, s3)
    fused = db.execute_fused(calls)
    _assert_same([s.execute(params=p) for s, p in calls], fused)
    st = fused[0].stats
    assert st["fused"] and st["fused_programs"] == 1
    assert st["fused_programs"] < st["fused_statements"] == 3
    assert st["fused_members"] == 4  # s1 contributes two signatures
    assert st["shared_subtrees"] >= 1
    assert st["batch_size"] == 2 and st["batch_bucket"] == 2


def test_execute_fused_hekaton(db):
    s1 = db.prepare(_q_udf(), HEKATON)
    s2 = db.prepare(_q_arith(), HEKATON)
    calls = [(s1, {"cutoff": 10}), (s2, {"lo": 5, "scale": 2.0}),
             (s1, {"cutoff": 44})]
    fused = db.execute_fused(calls)
    _assert_same([s.execute(params=p) for s, p in calls], fused)
    assert fused[0].stats["fused"]


def test_execute_fused_empty_and_single(db):
    assert db.execute_fused([]) == []
    s1 = db.prepare(_q_udf(), FROID)
    rs = db.execute_fused([(s1, {"cutoff": 5}), (s1, {"cutoff": 9})])
    _assert_same([s1.execute(params={"cutoff": 5}),
                  s1.execute(params={"cutoff": 9})], rs)
    assert "fused" not in rs[0].stats  # single statement: per-statement path


def test_fused_cache_tier(db):
    s1 = db.prepare(_q_udf(), FROID)
    s2 = db.prepare(_q_arith(), FROID)
    calls = [(s1, {"cutoff": 5}), (s2, {"lo": 3, "scale": 1.0}),
             (s1, {"cutoff": 8})]
    r1 = db.execute_fused(calls)
    assert db.cache_stats["fuse_misses"] == 1 and not r1[0].cache_hit
    # warm wave, different param values and arrival order: same program
    calls2 = [(s2, {"lo": 9, "scale": 4.0}), (s1, {"cutoff": 40}),
              (s1, {"cutoff": 2})]
    r2 = db.execute_fused(calls2)
    assert db.cache_stats["fuse_hits"] == 1
    assert db.cache_stats["fuse_misses"] == 1 and r2[0].cache_hit
    _assert_same([s.execute(params=p) for s, p in calls2], r2)


def test_fused_cache_invalidates_on_ddl(db):
    s1 = db.prepare(_q_udf(), FROID)
    s2 = db.prepare(_q_arith(), FROID)
    calls = [(s1, {"cutoff": 49}), (s2, {"lo": 0, "scale": 1.0})]
    r1 = db.execute_fused(calls)
    misses = db.cache_stats["fuse_misses"]
    rng = np.random.default_rng(99)
    db.create_table(
        "detail",
        d_key=rng.integers(0, 50, 2000),
        d_val=rng.uniform(0, 100, 2000).astype(np.float32),
    )
    r2 = db.execute_fused(calls)
    assert db.cache_stats["fuse_misses"] == misses + 1 and not r2[0].cache_hit
    _assert_same([s.execute(params=p) for s, p in calls], r2)
    # the UDF aggregates over detail: new data must actually flow through
    m = np.asarray(r2[0].masked.mask)
    assert not np.allclose(
        np.asarray(r1[0].masked.table.columns["v"].data)[m],
        np.asarray(r2[0].masked.table.columns["v"].data)[m],
    )


def test_fused_group_honors_strictest_max_batch(db):
    """max_batch is non-identity, so fingerprint-equal members may carry
    different bounds — the fused wave must honor the strictest one (and
    stay arrival-order independent), not whichever statement arrived
    first."""
    s_big = db.prepare(_q_udf(), FROID)                     # max_batch 1024
    s_small = db.prepare(_q_arith(), FROID.batched(max_batch=2))
    calls = ([(s_big, {"cutoff": int(k)}) for k in range(3)]
             + [(s_small, {"lo": int(k), "scale": 1.0}) for k in range(3)])
    rs = db.execute_fused(calls)
    _assert_same([s.execute(params=p) for s, p in calls], rs)
    fused_rs = [r for r in rs if "fused" in r.stats]
    assert fused_rs and all(r.stats["batch_bucket"] <= 2 for r in fused_rs)
    # arrival order reversed -> same buckets, warm fused-cache hit
    hits = db.cache_stats["fuse_hits"]
    rs2 = db.execute_fused(list(reversed(calls)))
    assert db.cache_stats["fuse_hits"] > hits
    _assert_same([s.execute(params=p) for s, p in reversed(calls)], rs2)


def test_merge_blocks_nondeterministic_subtrees():
    """A param-free subtree containing rand() must evaluate per statement,
    never once per pool."""
    from repro.core import relalg as R
    from repro.core import scalar as S

    det = R.Filter(R.Scan("T"), col("a") < lit(5))
    rnd = R.Compute(R.Scan("T"), {"r": S.Func("rand", [])})
    assert subtree_is_constant(det)
    assert not subtree_is_constant(rnd)
    merged = merge_plans([R.Project(rnd, ["r"]), R.Compute(rnd, {"b": col("r")})])
    assert rnd.node_id not in merged.shared_ids


def test_fused_overflow_spills_to_per_statement_path(db):
    policy = FROID.batched(max_batch=4)
    s1 = db.prepare(_q_udf(), policy)
    s2 = db.prepare(_q_arith(), policy)
    calls = ([(s1, {"cutoff": int(k)}) for k in range(6)]   # > max_batch
             + [(s2, {"lo": 5, "scale": 2.0})])
    rs = db.execute_fused(calls)
    _assert_same([s.execute(params=p) for s, p in calls], rs)
    assert rs[0].stats["fused"]          # first wave rides the fused program
    assert rs[5].stats.get("batched") and "fused" not in rs[5].stats  # spill


# ---------------------------------------------------------------------------
# scheduler fusion drain mode
# ---------------------------------------------------------------------------


def test_scheduler_fused_drain(db):
    s1 = db.prepare(_q_udf(), FROID)
    s2 = db.prepare(_q_arith(), FROID)
    s3 = db.prepare(_q_paramfree(), FROID)
    calls = _mixed_calls(s1, s2, s3)
    sched = CoalescingScheduler(max_batch=64, window_s=10.0,
                                clock=lambda: 0.0, fuse=True)
    tickets = [sched.submit(s, p) for s, p in calls]
    assert sched.flush() == len(calls)
    _assert_same([s.execute(params=p) for s, p in calls],
                 [t.result() for t in tickets])
    assert sched.stats["batches"] == 1  # one fused wave, not 3 drains
    assert sched.stats["fused_batches"] == 1
    assert sched.stats["fused_statements"] == 3
    assert tickets[0].result().stats["fused"]


def test_scheduler_fuse_off_drains_per_statement(db):
    s1 = db.prepare(_q_udf(), FROID)
    s2 = db.prepare(_q_arith(), FROID)
    sched = CoalescingScheduler(max_batch=64, window_s=10.0,
                                clock=lambda: 0.0)
    t1 = sched.submit(s1, {"cutoff": 5})
    t2 = sched.submit(s2, {"lo": 1, "scale": 1.0})
    sched.flush()
    assert sched.stats["batches"] == 2 and sched.stats["fused_batches"] == 0
    assert "fused" not in t1.result().stats
    assert "fused" not in t2.result().stats


def test_scheduler_fused_single_group_skips_fusion(db):
    s1 = db.prepare(_q_udf(), FROID)
    sched = CoalescingScheduler(max_batch=64, window_s=10.0,
                                clock=lambda: 0.0, fuse=True)
    ts = [sched.submit(s1, {"cutoff": k}) for k in (5, 9)]
    sched.flush()
    assert sched.stats["fused_batches"] == 0
    assert "fused" not in ts[0].result().stats


# ---------------------------------------------------------------------------
# conformance oracle (fixed entry points; CI re-runs under 8 forced devices)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", [FROID, HEKATON],
                         ids=["froid", "hekaton"])
def test_fusion_oracle_modes(policy):
    check_fusion_oracle(11, 23, policy)


def test_fusion_oracle_interpreted_falls_back():
    check_fusion_oracle(12, 23, INTERPRETED, expect_fused=False)


def test_fusion_oracle_fuse_knob_off_falls_back():
    fused = check_fusion_oracle(13, 23, FROID.fused(fuse=False))
    assert all("fused" not in r.stats for r in fused)


def test_fusion_oracle_empty_table():
    check_fusion_oracle(14, 0, FROID)


def test_fusion_oracle_ddl_between_submit_and_drain():
    """DDL landing while mixed-statement tickets sit in the queue must
    re-specialize the fused program at drain time (env token is read at
    drain, invalidating every member at once)."""
    check_fusion_oracle(15, 23, FROID, ddl=True)


def test_fusion_oracle_sharded():
    """Fused programs still place over the mesh: 8 tickets per statement
    make every member bucket divisible on the CI mesh (on fewer devices
    the same spec exercises divisibility gating / replication)."""
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    spec = ([(0, {"cut": int(k % 6), "shift": 0.5}) for k in range(8)]
            + [(1, {"minq": int(k % 4), "scale": 2.0}) for k in range(8)]
            + [(2, None) for _ in range(8)])
    fused = check_fusion_oracle(16, 23, FROID.sharded(mesh), spec)
    if len(jax.devices()) > 1:
        st = next(r.stats for r in fused if r.stats.get("fused"))
        assert st.get("sharded") and st["shard_devices"] == len(jax.devices())


def test_fusion_oracle_sharded_mixed_divisibility():
    """Regression (ISSUE-8 bugfix): one batched member's bucket divides
    the data axes (8 tickets) and another's does not (3 tickets → bucket
    4 on the forced-8-device CI mesh).  The wave must still shard — the
    non-dividing member pads its parameter axis up to the next multiple
    of the data-axis size instead of demoting the whole fused program to
    replicated — and results stay element-wise equal to the serial loop
    (the padding rows are discarded, exactly like power-of-two bucket
    padding)."""
    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("data",))
    spec = ([(0, {"cut": int(k % 6), "shift": 0.5}) for k in range(8)]
            + [(1, {"minq": int(k % 4), "scale": 2.0}) for k in range(3)]
            + [(2, None) for _ in range(2)])
    fused = check_fusion_oracle(17, 23, FROID.sharded(mesh), spec)
    if n_dev > 1:
        sts = [r.stats for r in fused if r.stats.get("fused")]
        # every fused ticket ran in the one sharded program
        assert all(st.get("sharded") and st["shard_devices"] == n_dev
                   for st in sts), sts[0]
        # the 3-ticket member's bucket padded up to a mesh multiple
        member1 = fused[8].stats
        assert member1["batch_bucket"] % n_dev == 0, member1
        assert member1["batch_bucket"] >= 3
        # the dividing member kept its natural bucket
        assert fused[0].stats["batch_bucket"] == 8


# ---------------------------------------------------------------------------
# serving pass-through
# ---------------------------------------------------------------------------


def test_admission_policy_fuse_adaptive_passthrough():
    from repro.serve.admission import AdmissionPolicy

    ap = AdmissionPolicy(froid=True, fuse=True, adaptive=True)
    assert ap.scheduler.fuse and ap.scheduler.adaptive
    # the default admission workload (one request statement) still drains
    # correctly through the fusion-mode scheduler
    reqs = {
        "tier": np.array([0, 2]),
        "prompt_len": np.array([100, 9000]),
        "max_new_tokens": np.array([50, 800]),
        "temperature": np.array([0.5, 0.7], np.float32),
    }
    tick = ap.evaluate(reqs)
    co = ap.evaluate_coalesced(reqs)
    np.testing.assert_array_equal(tick["admit"], co["admit"])
    np.testing.assert_array_equal(tick["granted"], co["granted"])
