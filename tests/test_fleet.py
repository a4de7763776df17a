"""Multi-worker fleet serving over the shared persistent plan tier.

The fleet conformance contract (``check_fleet_oracle``): a fleet drain of
a mixed-statement queue equals the single-worker serial drain element-wise
— whatever the store served (hits, cold misses, stale stamps, corrupt
entries), wherever round-robin landed each request, and under injected
faults and DDL broadcasts.  Persistence may only change costs.
"""
from __future__ import annotations

import glob
import os
import warnings

import pytest

from conformance_util import (
    FIXED_PROGRAMS,
    build_udf,
    check_fleet_oracle,
    fleet_setup,
    fusion_calls_spec,
    populate_session,
)
from repro.core import FROID, ROUTED, Session
from repro.persist import PlanCacheWarning, PlanStore, runtime_stamp
from repro.serve import AdmissionPolicy, FleetEngine
from repro.serve.scheduler import CoalescingScheduler

N_ROWS = 23


# ---------------------------------------------------------------------------
# the fleet oracle across its axes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workers", [1, 2, 3])
def test_fleet_oracle_matrix(tmp_path, workers):
    check_fleet_oracle(3, N_ROWS, workers=workers, store=str(tmp_path),
                       waves=2)


def test_fleet_oracle_no_store():
    """A store-less fleet still answers correctly (each worker compiles
    for itself — persistence is an optimization, never a requirement)."""
    stats = check_fleet_oracle(3, N_ROWS, workers=2, store=None)
    assert stats["fleet"]["persist_hits"] == 0
    assert "store" not in stats


def test_fleet_oracle_empty_table(tmp_path):
    check_fleet_oracle(4, 0, workers=2, store=str(tmp_path))


def test_fleet_warm_start_from_store(tmp_path):
    """A fresh fleet over a populated store answers its whole first drain
    from the persistent tier — no worker re-traces anything."""
    check_fleet_oracle(3, N_ROWS, workers=2, store=str(tmp_path))
    stats = check_fleet_oracle(3, N_ROWS, workers=2, store=str(tmp_path))
    assert stats["fleet"]["persist_hits"] >= 1
    assert stats["fleet"]["persist_misses"] == 0


def test_fleet_intra_cold_sharing(tmp_path):
    """Within one cold fleet, later workers warm-start from entries the
    first worker saved — compilation is a fleet-wide cost."""
    stats = check_fleet_oracle(5, N_ROWS, workers=2, store=str(tmp_path))
    per_worker = {pw["wid"]: pw["cache"] for pw in stats["workers"]}
    assert per_worker[0]["persist_misses"] >= 1  # paid the compile
    assert per_worker[1]["persist_hits"] >= 1    # rode it


def test_fleet_ddl_broadcast(tmp_path):
    """DDL landing between submit and drain (broadcast to every worker):
    the drain sees the new catalog state on every worker."""
    check_fleet_oracle(3, N_ROWS, workers=2, store=str(tmp_path), ddl=True)


def test_fleet_parallel_drain(tmp_path):
    check_fleet_oracle(3, N_ROWS, workers=3, store=str(tmp_path),
                       parallel=True, waves=2)


def test_fleet_corrupt_store_silent_recompile(tmp_path):
    """Every store entry corrupted: the fleet recompiles behind a typed
    warning and still equals the single-worker oracle — never stale plans,
    never an error surfaced to a ticket."""
    check_fleet_oracle(6, N_ROWS, workers=2, store=str(tmp_path))
    for p in glob.glob(os.path.join(str(tmp_path), "*.plan")):
        with open(p, "r+b") as f:
            f.truncate(32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", PlanCacheWarning)
        stats = check_fleet_oracle(6, N_ROWS, workers=2, store=str(tmp_path))
    assert stats["fleet"]["persist_rejects"] >= 1


def test_fleet_version_stamp_mismatch_silent_recompile(tmp_path):
    """Entries written by a different jax/jaxlib (simulated via a stale
    runtime stamp): silently rejected, recompiled, oracle-equal."""
    check_fleet_oracle(6, N_ROWS, workers=2, store=str(tmp_path))
    stale = PlanStore(str(tmp_path),
                      stamp={**runtime_stamp(), "jax": "0.0.0"})
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # version skew must NOT warn
        stats = check_fleet_oracle(6, N_ROWS, workers=2, store=stale)
    assert stats["fleet"]["persist_rejects"] >= 1
    # the first worker never loads a mismatched entry (it recompiles and
    # re-saves under the store's own stamp; later workers may hit those)
    first = min(stats["workers"], key=lambda pw: pw["wid"])["cache"]
    assert first["persist_hits"] == 0 and first["persist_rejects"] >= 1


def test_fleet_injected_faults(tmp_path):
    """Faults on non-interp seams in every worker: the resilient drains
    still deliver the oracle answer on every ticket."""
    from repro.resilience import FaultSpec

    specs = [FaultSpec(site="dispatch", times=2),
             FaultSpec(site="compile", times=1)]
    check_fleet_oracle(7, N_ROWS, workers=2, store=str(tmp_path),
                       fault_specs=specs, waves=2)


# ---------------------------------------------------------------------------
# engine mechanics: intake, latency, stats, cost persistence
# ---------------------------------------------------------------------------


def test_fleet_round_robin_and_pinning(tmp_path):
    fleet = FleetEngine(fleet_setup(3, N_ROWS, FROID), workers=2,
                        store=str(tmp_path))
    for _ in range(4):
        fleet.submit("q2")
    fleet.submit("q2", worker=1)
    fleet.drain()
    sub = [w.scheduler.stats["submitted"] for w in fleet.workers]
    assert sub == [2, 3]  # round-robin 2/2, then the pinned one


def test_fleet_rejects_bad_setup(tmp_path):
    with pytest.raises(TypeError):
        FleetEngine(lambda s: None, workers=1, store=str(tmp_path))
    with pytest.raises(ValueError):
        FleetEngine(fleet_setup(3, N_ROWS, FROID), workers=0)
    fleet = FleetEngine(fleet_setup(3, N_ROWS, FROID), workers=1,
                        store=str(tmp_path))
    with pytest.raises(KeyError):
        fleet.submit("nope")


def test_ticket_latency_stamped():
    """Tickets carry submit-to-fill latency on the scheduler's own clock
    (deterministic under an injected clock)."""
    now = [0.0]
    sched = CoalescingScheduler(max_batch=256, window_s=10.0,
                                clock=lambda: now[0])
    s = Session()
    populate_session(s, 3, N_ROWS)
    s.create_function(
        build_udf(FIXED_PROGRAMS["uncorrelated_sum_case"]).build())
    from conformance_util import param_query

    stmt = s.prepare(param_query(), FROID)
    t = sched.submit(stmt, {"cut": 5, "shift": 0.5})
    assert t.submitted_at == 0.0 and t.latency_s is None
    now[0] = 1.5
    sched.flush()
    t.result()
    assert t.latency_s == pytest.approx(1.5)


def test_fleet_latency_collection(tmp_path):
    fleet = FleetEngine(fleet_setup(3, N_ROWS, FROID), workers=2,
                        store=str(tmp_path))
    spec = fusion_calls_spec()
    for i, p in spec:
        fleet.submit(f"q{i}", p)
    fleet.drain()
    assert len(fleet.latencies_s) == len(spec)
    assert all(l >= 0.0 for l in fleet.latencies_s)


def test_fleet_stats_shape(tmp_path):
    fleet = FleetEngine(fleet_setup(3, N_ROWS, FROID), workers=2,
                        store=str(tmp_path))
    fleet.submit("q2")
    fleet.drain()
    stats = fleet.stats
    assert len(stats["workers"]) == 2
    for pw in stats["workers"]:
        assert {"cache", "persist", "scheduler"} <= pw.keys()
        assert pw["persist"]["enabled"]
    assert stats["store"]["entries"] >= 1
    assert stats["fleet"]["drained"] == 1


def test_fleet_cost_persistence_warm_routing(tmp_path):
    """A routed fleet saves its measured costs; a fresh fleet's workers
    route warm from the shared store (costs_loaded > 0) and still match
    the oracle."""
    fleet = FleetEngine(fleet_setup(3, N_ROWS, ROUTED), workers=2,
                        store=str(tmp_path))
    for _ in range(3):
        for i, p in fusion_calls_spec():
            fleet.submit(f"q{i}", p)
        fleet.drain()
    assert fleet.save_costs() >= 1

    check_fleet_oracle(3, N_ROWS, workers=2, store=str(tmp_path),
                       policy=ROUTED)
    fresh = FleetEngine(fleet_setup(3, N_ROWS, ROUTED), workers=2,
                        store=str(tmp_path))
    fresh.broadcast(lambda s: s._ensure_router())
    assert all(w.session.persist_stats["costs_loaded"] > 0
               for w in fresh.workers)


def test_fleet_broadcast_returns_worker_order(tmp_path):
    fleet = FleetEngine(fleet_setup(3, N_ROWS, FROID), workers=3,
                        store=str(tmp_path))
    wids = fleet.broadcast(lambda s: s)  # sessions in worker order
    assert [id(s) for s in wids] == [id(w.session) for w in fleet.workers]


# ---------------------------------------------------------------------------
# admission-path persistence
# ---------------------------------------------------------------------------


def test_admission_store_warm_start(tmp_path):
    reqs = dict(
        tier=__import__("numpy").array([0, 1, 2]),
        prompt_len=__import__("numpy").array([10, 100, 3000]),
        max_new_tokens=__import__("numpy").array([50, 2000, 500]),
        temperature=__import__("numpy").array([0.5, 3.0, 0.9],
                                              dtype="float32"),
    )
    cold = AdmissionPolicy(store=str(tmp_path))
    v_cold = cold.evaluate_coalesced(reqs)
    assert cold._request_session.persist_stats["saves"] >= 1

    warm = AdmissionPolicy(store=str(tmp_path))
    v_warm = warm.evaluate_coalesced(reqs)
    assert warm._request_session.cache_stats["persist_hits"] >= 1
    for k in v_cold:
        assert (v_cold[k] == v_warm[k]).all(), k
