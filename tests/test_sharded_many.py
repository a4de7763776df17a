"""Mesh-sharded `execute_many`: the batched invocation engine one level up
the hardware hierarchy.

Covers the contract: element-wise identity between the sharded path and
the serial `execute` loop, padding (a bucket the mesh's data axes don't
divide pads up to a multiple of them and runs on the mesh), the
sharded-executable cache tier (`shard_hits`/`shard_misses`), mesh-capacity
chunking
(`max_batch` bounds the per-device batch), mesh-sized scheduler flushes,
catalog invalidation of sharded executables, and the sharded admission
path of the serving engine.

Every test passes on a single device (sharding degrades to the one-device
path) and is exercised for real under the CI job that forces
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import numpy as np
import pytest

import jax

from repro.core import (
    FROID,
    ExecutionPolicy,
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
from repro.dist.sharding import data_axis_size, pick_data_axes
from repro.serve.scheduler import CoalescingScheduler

N_DEV = len(jax.devices())

multi_device = pytest.mark.skipif(
    N_DEV < 2, reason="needs >1 device (XLA_FLAGS=--xla_force_host_"
                      "platform_device_count=8)"
)


def _mesh():
    return jax.make_mesh((N_DEV,), ("data",))


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


def _q():
    return (
        scan("T")
        .filter(col("a") < param("cutoff"))
        .compute(v=udf("key_total", col("a")))
        .project("v")
    )


def _assert_same(serial, batched):
    assert len(serial) == len(batched)
    for s, b in zip(serial, batched):
        m = np.asarray(s.masked.mask)
        np.testing.assert_array_equal(m, np.asarray(b.masked.mask))
        # surviving rows only: dead lanes carry arbitrary values and may
        # differ between single-device and mesh-partitioned compilations
        np.testing.assert_allclose(
            np.asarray(s.masked.table.columns["v"].data)[m],
            np.asarray(b.masked.table.columns["v"].data)[m],
            rtol=1e-5,
        )


@pytest.fixture
def db():
    s = Session()
    _populate(s)
    return s


# ---------------------------------------------------------------------------
# policy knobs
# ---------------------------------------------------------------------------


def test_shard_knobs_are_not_identity():
    mesh = _mesh()
    pol = FROID.sharded(mesh)
    assert pol == FROID
    assert pol.fingerprint() == FROID.fingerprint()
    assert pol.mesh is mesh and pol.shard_batches
    assert pol.shard_devices() == data_axis_size(mesh)
    assert FROID.shard_devices() == 1 and FROID.shard_token() == ()
    # eager (no compiled plan) never shards, even with a mesh attached
    assert pol.eager().shard_devices() == 1


def test_shard_token_tracks_mesh_identity():
    mesh = _mesh()
    pol = FROID.sharded(mesh)
    if N_DEV == 1:
        assert pol.shard_token() == ()  # 1-device mesh: no data sharding
        return
    axes, devices = pol.shard_token()
    assert axes == (("data", N_DEV),)
    assert len(devices) == N_DEV
    # a rebuilt mesh over the same devices produces the same token (cache
    # hits survive mesh reconstruction)
    assert FROID.sharded(_mesh()).shard_token() == pol.shard_token()


def test_prepare_sharded_and_unsharded_do_not_alias(db):
    s1 = db.prepare(_q(), FROID)
    s2 = db.prepare(_q(), FROID.sharded(_mesh()))
    if N_DEV == 1:
        assert s2.policy.shard_devices() == 1
        return
    assert s1 is not s2
    assert s1.policy.mesh is None and s2.policy.mesh is not None


# ---------------------------------------------------------------------------
# element-wise identity with the serial loop
# ---------------------------------------------------------------------------


def test_sharded_execute_many_matches_serial_loop(db):
    stmt = db.prepare(_q(), FROID.sharded(_mesh()))
    rng = np.random.default_rng(1)
    params_list = [{"cutoff": int(k)} for k in rng.integers(1, 50, 2 * N_DEV)]
    serial = [stmt.execute(params=p) for p in params_list]
    batched = stmt.execute_many(params_list)
    _assert_same(serial, batched)
    st = batched[0].stats
    assert st["batched"] and st["batch_size"] == 2 * N_DEV
    if N_DEV > 1:
        assert st["sharded"] and st["shard_devices"] == N_DEV


def test_sharded_mixed_signatures_match_serial(db):
    stmt = db.prepare(_q(), FROID.sharded(_mesh()))
    params_list = (
        [{"cutoff": int(k)} for k in range(1, 1 + 2 * N_DEV)]
        + [{"cutoff": float(k) + 0.5} for k in range(1, 1 + N_DEV)]
    )
    batched = stmt.execute_many(params_list)
    serial = [stmt.execute(params=p) for p in params_list]
    _assert_same(serial, batched)


def test_sharded_empty_table_matches_serial():
    db = Session()
    _populate(db)
    db.create_table("T", a=np.array([], np.int64))
    stmt = db.prepare(_q(), FROID.sharded(_mesh()))
    params_list = [{"cutoff": int(k)} for k in range(N_DEV)]
    batched = stmt.execute_many(params_list)
    serial = [stmt.execute(params=p) for p in params_list]
    _assert_same(serial, batched)
    assert all(r.masked.num_rows == 0 for r in batched)


def test_empty_aggregate_source_table_runs():
    """Aggregating over a zero-row table must produce NULL aggregates (the
    UDF's NULL branch), not crash — on every path."""
    db = Session()
    db.create_table("detail", d_key=np.array([], np.int64),
                    d_val=np.array([], np.float32))
    db.create_table("T", a=np.arange(4))
    u = UdfBuilder("key_total", [("k", "int32")], "float32")
    u.declare("s", "float32")
    u.select({"s": sum_(col("d_val"))}, frm=scan("detail"),
             where=col("d_key") == param("k"))
    with u.if_(var("s").is_null()):
        u.return_(lit(0.0))
    u.return_(var("s"))
    db.create_function(u.build())
    stmt = db.prepare(_q(), FROID.sharded(_mesh()))
    rs = stmt.execute_many([{"cutoff": 3}] * max(2, N_DEV))
    serial = [stmt.execute(params={"cutoff": 3})] * max(2, N_DEV)
    _assert_same(serial, rs)
    np.testing.assert_array_equal(
        np.asarray(rs[0].masked.table.columns["v"].data)[
            np.asarray(rs[0].masked.mask)],
        0.0,
    )


# ---------------------------------------------------------------------------
# divisibility gating + cache tier
# ---------------------------------------------------------------------------


@multi_device
def test_small_bucket_runs_replicated(db):
    """A bucket the data axes don't divide (here bucket 1 < devices) pads
    up to the next multiple of the data-axis product by repeating its last
    parameter set, and runs on the whole mesh, never on one device."""
    stmt = db.prepare(_q(), FROID.sharded(_mesh()))
    before = db.timing_stats["pad_calls"]
    rs = stmt.execute_many([{"cutoff": 7}])
    assert pick_data_axes(_mesh(), 1) is None
    st = rs[0].stats
    assert st["sharded"] and st["shard_devices"] == N_DEV
    assert st["batch_size"] == 1 and st["batch_bucket"] == N_DEV
    assert db.cache_stats["shard_misses"] == 1
    assert db.cache_stats["batch_misses"] == 0
    assert db.timing_stats["pad_calls"] - before == N_DEV - 1
    _assert_same([stmt.execute(params={"cutoff": 7})], rs)


@multi_device
def test_shard_cache_tier_hits(db):
    stmt = db.prepare(_q(), FROID.sharded(_mesh()))
    params_list = [{"cutoff": int(k)} for k in range(N_DEV)]
    r1 = stmt.execute_many(params_list)
    assert r1[0].stats["sharded"] and not r1[0].cache_hit
    assert db.cache_stats["shard_misses"] == 1
    r2 = stmt.execute_many([{"cutoff": int(k) + 9} for k in range(N_DEV)])
    assert r2[0].cache_hit
    assert db.cache_stats["shard_hits"] == 1
    assert db.cache_stats["shard_misses"] == 1
    # the sharded tier is separate from the single-device batch tier: an
    # unsharded statement on the same query re-specializes there
    un = db.prepare(_q(), FROID)
    un.execute_many(params_list)
    assert db.cache_stats["batch_misses"] >= 1


@pytest.mark.skipif(N_DEV < 8, reason="needs 8 forced devices")
def test_replicated_fallback_respects_max_batch(db):
    """A bucket the data axes don't divide pads to the next multiple of
    their product, and the per-device bound holds: the mesh-capacity cap
    is itself such a multiple, so no device gets more than max_batch."""
    from jax.sharding import Mesh

    mesh6 = Mesh(np.array(jax.devices()[:6]), ("data",))
    stmt = db.prepare(_q(), FROID.sharded(mesh6).batched(max_batch=2))
    plist = [{"cutoff": int(k)} for k in range(5)]  # bucket 8, 8 % 6 != 0
    rs = stmt.execute_many(plist)
    assert all(r.stats["sharded"] and r.stats["shard_devices"] == 6
               for r in rs)
    assert all(r.stats["batch_bucket"] == 12 for r in rs)
    assert all(r.stats["batch_bucket"] // 6 <= 2 for r in rs)
    assert [r.stats["batch_size"] for r in rs] == [5] * 5
    _assert_same([stmt.execute(params=p) for p in plist], rs)


@multi_device
def test_mesh_capacity_chunking(db):
    """`max_batch` bounds the per-device batch: a mesh of D devices takes
    max_batch × D parameter sets in one sharded dispatch."""
    stmt = db.prepare(_q(), FROID.sharded(_mesh()).batched(max_batch=2))
    n = 2 * N_DEV + 2  # one full mesh dispatch + a remainder chunk
    params_list = [{"cutoff": int(k % 50)} for k in range(n)]
    rs = stmt.execute_many(params_list)
    sizes = [r.stats["batch_size"] for r in rs]
    assert sizes[: 2 * N_DEV] == [2 * N_DEV] * (2 * N_DEV)
    assert sizes[2 * N_DEV:] == [2, 2]
    assert rs[0].stats["sharded"]
    # the remainder chunk pads to the mesh rather than leaving it
    assert rs[-1].stats["sharded"]
    assert rs[-1].stats["batch_bucket"] == N_DEV
    _assert_same([stmt.execute(params=p) for p in params_list], rs)


# ---------------------------------------------------------------------------
# invalidation
# ---------------------------------------------------------------------------


def test_ddl_invalidates_sharded_executables(db):
    stmt = db.prepare(_q(), FROID.sharded(_mesh()))
    params_list = [{"cutoff": int(k)} for k in range(max(2, N_DEV))]
    r1 = stmt.execute_many(params_list)
    assert stmt.execute_many(params_list)[0].cache_hit
    rng = np.random.default_rng(42)
    db.create_table(
        "detail",
        d_key=rng.integers(0, 50, 2000),
        d_val=rng.uniform(0, 100, 2000).astype(np.float32),
    )
    r2 = stmt.execute_many(params_list)
    assert not r2[0].cache_hit
    _assert_same([stmt.execute(params=p) for p in params_list], r2)
    # new data actually flowed through (same T, same mask; fresh detail)
    m = np.asarray(r2[-1].masked.mask)
    a1 = np.asarray(r1[-1].masked.table.columns["v"].data)[m]
    a2 = np.asarray(r2[-1].masked.table.columns["v"].data)[m]
    assert not np.allclose(a1, a2)


# ---------------------------------------------------------------------------
# scheduler + serving integration
# ---------------------------------------------------------------------------


def test_scheduler_flushes_mesh_sized_buckets(db):
    """Flush-on-full for a sharded statement waits for max_batch × devices
    requests — online traffic fills every device, not one."""
    clock = lambda: 0.0  # noqa: E731 — window never expires
    sched = CoalescingScheduler(window_s=10.0, clock=clock)
    stmt = db.prepare(_q(), FROID.sharded(_mesh()).batched(max_batch=2))
    target = 2 * N_DEV
    tickets = [sched.submit(stmt, {"cutoff": int(k % 50)})
               for k in range(target - 1)]
    assert sched.pending == target - 1  # still coalescing
    tickets.append(sched.submit(stmt, {"cutoff": 1}))  # fills the mesh
    assert sched.pending == 0 and sched.stats["flush_full"] == 1
    assert all(t.done() for t in tickets)
    if N_DEV > 1:
        assert tickets[0].result().stats["sharded"]
    assert tickets[0].result().stats["batch_size"] == target


def test_ddl_between_submit_and_drain_not_stale_sharded(db):
    """Catalog replacement while tickets are queued must re-specialize the
    sharded executable at drain time — never serve stale results."""
    clock = lambda: 0.0  # noqa: E731
    sched = CoalescingScheduler(window_s=10.0, clock=clock)
    stmt = db.prepare(_q(), FROID.sharded(_mesh()))
    params_list = [{"cutoff": int(k)} for k in range(max(2, N_DEV))]
    stmt.execute_many(params_list)  # warm the pre-DDL executable
    tickets = [sched.submit(stmt, p) for p in params_list]
    rng = np.random.default_rng(7)
    db.create_table(
        "detail",
        d_key=rng.integers(0, 50, 2000),
        d_val=rng.uniform(0, 100, 2000).astype(np.float32),
    )
    sched.flush()
    results = [t.result() for t in tickets]
    assert not results[0].cache_hit  # re-specialized, not stale
    _assert_same([stmt.execute(params=p) for p in params_list], results)


def test_admission_sharded_matches_tick_path():
    from repro.serve.admission import AdmissionPolicy

    n = 4 * max(2, N_DEV)
    rng = np.random.default_rng(5)
    reqs = {
        "tier": rng.integers(0, 3, n),
        "prompt_len": rng.integers(10, 40000, n),
        "max_new_tokens": rng.integers(1, 9000, n),
        "temperature": rng.uniform(-1, 3, n).astype(np.float32),
    }
    ap = AdmissionPolicy(froid=True, mesh=_mesh())
    tick = ap.evaluate(reqs)
    co = ap.evaluate_coalesced(reqs)
    np.testing.assert_array_equal(tick["admit"], co["admit"])
    np.testing.assert_array_equal(tick["granted"], co["granted"])
    np.testing.assert_allclose(tick["temp"], co["temp"], rtol=1e-6)
    if N_DEV > 1:
        assert ap.request_statement().policy.shard_devices() == N_DEV
