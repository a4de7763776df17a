"""The engine's own spans and counters (``repro.telemetry``,
``Session.timing_stats``, the scheduler's wait counters) on the CPU."""
import glob

import jax
import numpy as np
import pytest

from repro import telemetry
from repro.core import FROID, Session, col, param, scan
from repro.serve.scheduler import CoalescingScheduler


def _db(n=64):
    db = Session()
    rng = np.random.default_rng(0)
    db.create_table("orders", o_key=np.arange(n, dtype=np.int32),
                    o_cust=rng.integers(0, 8, n).astype(np.int32),
                    o_price=rng.uniform(1, 10, n).astype(np.float32))
    db.create_table("cust", c_key=np.arange(8, dtype=np.int32),
                    c_nation=rng.integers(0, 3, 8).astype(np.int32))
    return db


def _q():
    return (scan("orders").filter(col("o_price") > param("p"))
            .join(scan("cust"), on=[("o_cust", "c_key")])
            .project("o_key", "c_nation"))


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_create_table_counts_the_catalog():
    before = telemetry.totals()["catalog"]
    db = _db()
    ts = db.timing_stats
    assert ts["tables"] == 2 and ts["catalog_s"] > 0
    n, s = telemetry.totals()["catalog"]
    assert n == before[0] + 2
    assert s - before[1] == pytest.approx(ts["catalog_s"])


def test_serial_execute_counts_one_execution_from_one_set_of_stamps():
    db = _db()
    stmt = db.prepare(_q(), FROID)
    stmt.execute(params={"p": 2.0})
    before = dict(db.timing_stats)
    r = stmt.execute(params={"p": 3.0})
    ts = db.timing_stats
    assert ts["executions"] == before["executions"] + 1
    for k in ("args_s", "dispatch_s", "sync_s"):
        assert ts[k] > before[k]
    # the result's sync_s is the counter's increment: one measurement
    assert r.stats["sync_s"] == pytest.approx(ts["sync_s"] - before["sync_s"])
    assert r.elapsed_s == pytest.approx(r.stats["dispatch_s"]
                                        + r.stats["sync_s"])


def test_first_call_of_a_new_shape_compiles_and_the_second_does_not():
    db = _db(n=96)   # a shape no other test compiles
    stmt = db.prepare(_q(), FROID)
    c0 = telemetry.totals()["backend_compile"][0]
    busy0 = telemetry.busy_seconds()
    stmt.execute(params={"p": 2.0})
    c1 = telemetry.totals()["backend_compile"][0]
    assert c1 > c0
    assert telemetry.busy_seconds() > busy0
    stmt.execute(params={"p": 5.0})
    assert telemetry.totals()["backend_compile"][0] == c1


def test_compile_seconds_end_where_asked():
    db = _db(n=80)
    stmt = db.prepare(_q(), FROID)
    import time

    t = time.perf_counter()
    before = telemetry.busy_seconds(until=t)
    stmt.execute(params={"p": 2.0})
    assert telemetry.busy_seconds(until=t) == before
    assert telemetry.busy_seconds() > before


def test_batched_and_async_paths_count_dispatch_sync_and_materialize():
    db = _db()
    stmt = db.prepare(_q(), FROID)
    ps = [{"p": float(k)} for k in range(3)]
    for r in stmt.execute_many(ps):
        _ = r.masked
    stmt.execute_async({"p": 1.0}).result()
    before = dict(db.timing_stats)
    rs = stmt.execute_many(ps)
    ts = db.timing_stats
    assert ts["executions"] == before["executions"] + 1   # one wave
    assert ts["materializations"] == before["materializations"]
    for r in rs:
        _ = r.masked
    assert ts["materializations"] == before["materializations"] + 3
    assert ts["materialize_s"] > before["materialize_s"]
    a = stmt.execute_async({"p": 2.0})
    assert ts["executions"] == before["executions"] + 2
    sync0 = ts["sync_s"]
    r = a.result()
    assert r.stats["sync_s"] == pytest.approx(ts["sync_s"] - sync0)
    assert ts["materializations"] == before["materializations"] + 4


@pytest.mark.parametrize("resilience", [True, False])
def test_scheduler_queue_wait_is_the_clock_from_submit_to_drain(resilience):
    db = _db()
    stmt = db.prepare(_q(), FROID)
    clock = FakeClock()
    sched = CoalescingScheduler(max_batch=8, window_s=10.0, clock=clock,
                                resilience=resilience)
    tickets = [sched.submit(stmt, {"p": 2.0})]
    clock.t = 1.0
    tickets.append(sched.submit(stmt, {"p": 3.0}))
    clock.t = 5.0
    assert sched.flush() == 2
    assert sched.stats["queue_wait_s"] == pytest.approx(5.0 + 4.0)
    assert sched.stats["lock_wait_s"] == 0.0   # the clock stood still
    assert sched.stats["submit_drain_s"] == 0.0   # no submit drained
    assert all(t.done() for t in tickets)
    # a submit that fills its batch drains on the submitting thread
    full = CoalescingScheduler(max_batch=2, window_s=10.0, clock=clock,
                               resilience=resilience)
    full.submit(stmt, {"p": 2.0})
    clock.t = 7.0
    full.submit(stmt, {"p": 3.0})
    assert full.stats["drained"] == 2
    assert full.stats["queue_wait_s"] == pytest.approx(2.0)


def test_operators_lower_under_their_family_scope():
    db = _db()
    stmt = db.prepare(_q(), FROID)
    entry, _, _ = db._executable(stmt.node, stmt._query_fp, FROID,
                                 {"p": 2.0})
    hlo = jax.jit(entry.raw).lower(*entry.args({"p": 2.0})).as_text(
        debug_info=True)
    for family in ("froid.scan", "froid.filter", "froid.join",
                   "froid.project"):
        assert family in hlo
    # a cached executable must carry these scopes, not another build's
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def _froid_spans(trace_dir):
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(
        glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")[-1])
    return [e.name for p in prof.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events
            if e.name.startswith("froid.")]


def test_a_warm_call_and_a_warm_wave_record_at_most_six_spans(tmp_path):
    db = _db()
    stmt = db.prepare(_q(), FROID)
    sched = CoalescingScheduler(max_batch=1)
    stmt.execute(params={"p": 2.0})
    sched.submit(stmt, {"p": 2.0}).result()
    jax.profiler.start_trace(str(tmp_path / "call"))
    stmt.execute(params={"p": 3.0})
    jax.profiler.stop_trace()
    call = _froid_spans(tmp_path / "call")
    assert sorted(call) == ["froid.args", "froid.dispatch", "froid.execute",
                            "froid.sync"]
    jax.profiler.start_trace(str(tmp_path / "wave"))
    t = sched.submit(stmt, {"p": 3.0})
    jax.profiler.stop_trace()
    wave = _froid_spans(tmp_path / "wave")
    assert sorted(wave) == ["froid.args", "froid.dispatch", "froid.execute",
                            "froid.sched.drain", "froid.sched.lock_wait",
                            "froid.sync"]
    jax.profiler.start_trace(str(tmp_path / "fetch"))
    _ = t.result().masked
    jax.profiler.stop_trace()
    assert _froid_spans(tmp_path / "fetch") == ["froid.materialize"]


def _dispatch_devices(trace_dir):
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(
        glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")[-1])
    return [int(v) for p in prof.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events
            if e.name == "froid.dispatch"
            for k, v in e.stats if k == "devices"]


def test_a_wave_dispatch_names_its_devices(tmp_path):
    """One device here; ``tests/test_placement.py`` reads 4 on a mesh."""
    db = _db()
    stmt = db.prepare(_q(), FROID)
    ps = [{"p": float(k)} for k in range(3)]
    stmt.execute_many(ps)
    jax.profiler.start_trace(str(tmp_path))
    stmt.execute_many(ps)
    jax.profiler.stop_trace()
    assert _dispatch_devices(tmp_path) == [1]


@pytest.mark.parametrize("resilience", [True, False])
def test_scheduler_and_session_count_where_waves_ran(resilience):
    db = _db()
    stmt = db.prepare(_q(), FROID)
    sched = CoalescingScheduler(max_batch=8, window_s=10.0,
                                clock=FakeClock(), resilience=resilience)
    for k in range(3):   # one wave of 3 calls, bucket 4
        sched.submit(stmt, {"p": float(k)})
    assert sched.flush() == 3
    assert sched.stats["batches"] == 1
    assert {k: sched.stats[k] for k in (
        "sharded_waves", "sharded_calls", "pad_calls")} == {
        "sharded_waves": 0, "sharded_calls": 0, "pad_calls": 1}
    assert {k: db.timing_stats[k] for k in (
        "sharded_waves", "sharded_calls", "pad_calls")} == {
        "sharded_waves": 0, "sharded_calls": 0, "pad_calls": 1}
    for k in range(4):   # a full bucket adds no padding
        sched.submit(stmt, {"p": float(k)})
    sched.flush()
    assert sched.stats["pad_calls"] == db.timing_stats["pad_calls"] == 1
    assert sched.stats["batches"] == 2
