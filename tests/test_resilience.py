"""Resilience layer tests: degradation ladder, circuit breakers, deadlines,
fault injection, and the chaos conformance oracle (fixed schedules).

The generative layer (random seeded fault schedules through hypothesis)
rides in ``tests/test_property_froid.py``; this module is the
deterministic floor that runs everywhere — including the forced-8-device
CI chaos smoke job — plus unit coverage for the breaker state machine,
the injector's schedule semantics, the ``Ticket`` result sentinel, and
the fused-drain result-count guard.
"""
import numpy as np
import pytest

from conformance_util import check_chaos_oracle
from repro.core import FROID, Session, col, param, scan
from repro.resilience import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    BreakerConfig,
    CircuitBreaker,
    DeadlineExceeded,
    FaultInjector,
    FaultSpec,
    InjectedFault,
    ResilienceConfig,
    ResilienceError,
    RetryPolicy,
    WaveResultMismatch,
)
from repro.serve.scheduler import CoalescingScheduler


class Clock:
    """Manually-advanced monotonic clock for deterministic deadline and
    breaker-cooldown tests."""

    def __init__(self, now: float = 0.0):
        self.now = now

    def __call__(self) -> float:
        return self.now


def _mk(n: int = 8):
    """Session + two distinct prepared statements over one small table."""
    s = Session()
    s.create_table("T", x=np.arange(n, dtype=np.int32))
    q1 = scan("T").filter(col("x") < param("cutoff")).project("x")
    q2 = scan("T").compute(y=col("x") * param("m")).project("x", "y")
    return s, s.prepare(q1, FROID), s.prepare(q2, FROID)


def _sched(clock=None, **kw):
    kw.setdefault("max_batch", 64)
    kw.setdefault("window_s", 1e9)
    kw.setdefault("sleep", lambda s: None)
    if clock is not None:
        kw["clock"] = clock
    return CoalescingScheduler(**kw)


def _xs(result):
    return np.asarray(result.table.columns["x"].data).tolist()


# ---------------------------------------------------------------------------
# circuit breaker state machine
# ---------------------------------------------------------------------------


def test_breaker_opens_at_threshold_within_window():
    c = Clock()
    b = CircuitBreaker(BreakerConfig(failure_threshold=3, window_s=10.0,
                                     cooldown_s=5.0), clock=c)
    assert b.state == CLOSED and b.allow()
    b.record_failure(); b.record_failure()
    assert b.state == CLOSED  # below threshold
    b.record_failure()
    assert b.state == OPEN and b.stats["opened"] == 1
    assert not b.allow() and b.stats["rejected"] == 1


def test_breaker_window_prunes_old_failures():
    c = Clock()
    b = CircuitBreaker(BreakerConfig(failure_threshold=3, window_s=10.0),
                       clock=c)
    b.record_failure()
    c.now = 11.0  # first failure ages out of the window
    b.record_failure(); b.record_failure()
    assert b.state == CLOSED  # only 2 failures inside the window
    b.record_failure()
    assert b.state == OPEN


def test_breaker_half_open_probe_restores():
    c = Clock()
    b = CircuitBreaker(BreakerConfig(failure_threshold=1, cooldown_s=5.0),
                       clock=c)
    b.record_failure()
    assert b.state == OPEN and not b.allow()
    c.now = 6.0  # past cooldown: one probe admitted
    assert b.allow() and b.state == HALF_OPEN and b.stats["probes"] == 1
    b.record_success()
    assert b.state == CLOSED and b.stats["restored"] == 1
    assert b.allow()


def test_breaker_half_open_probe_failure_reopens():
    c = Clock()
    b = CircuitBreaker(BreakerConfig(failure_threshold=1, cooldown_s=5.0),
                       clock=c)
    b.record_failure()
    c.now = 6.0
    assert b.allow() and b.state == HALF_OPEN
    b.record_failure()
    assert b.state == OPEN and b.stats["reopened"] == 1
    assert not b.allow()  # fresh cooldown from the reopen
    c.now = 12.0
    assert b.allow() and b.state == HALF_OPEN  # probes again


# ---------------------------------------------------------------------------
# fault injector schedule semantics
# ---------------------------------------------------------------------------


def test_fault_spec_site_stmt_after_times():
    fp = ("some", "fingerprint")
    fi = FaultInjector([FaultSpec(site="dispatch", stmt=fp, after=1, times=2)])
    fi.check("dispatch", ())          # wrong statement: no match
    fi.check("compile", (fp,))        # wrong site: no match
    fi.check("dispatch", (fp,))       # match 1: skipped by after=1
    with pytest.raises(InjectedFault):
        fi.check("dispatch", (fp,))   # match 2: fires
    with pytest.raises(InjectedFault):
        fi.check("dispatch", (fp, ("other",)))  # fused wave membership
    fi.check("dispatch", (fp,))       # times=2 exhausted: quiet
    assert fi.fired == 2
    assert fi.events == {"dispatch": 5, "compile": 1}


def test_fault_spec_times_none_fires_forever():
    fi = FaultInjector([FaultSpec(site="sync", times=None)])
    for _ in range(5):
        with pytest.raises(InjectedFault):
            fi.check("sync", ())
    assert fi.fired == 5


def _fire_pattern(fi: FaultInjector, site: str, n: int) -> list:
    pat = []
    for _ in range(n):
        try:
            fi.check(site, ())
            pat.append(0)
        except InjectedFault:
            pat.append(1)
    return pat


def test_seeded_schedule_is_deterministic_and_seed_sensitive():
    a = _fire_pattern(FaultInjector.seeded(5, 0.5), "dispatch", 64)
    b = _fire_pattern(FaultInjector.seeded(5, 0.5), "dispatch", 64)
    other = _fire_pattern(FaultInjector.seeded(6, 0.5), "dispatch", 64)
    assert a == b            # same seed -> identical schedule
    assert a != other        # different seed -> different schedule
    assert 0 < sum(a) < 64   # rate 0.5 fires some, not all


def test_seeded_schedule_max_faults_bounds_firing():
    fi = FaultInjector.seeded(5, 1.0, max_faults=3)
    pat = _fire_pattern(fi, "dispatch", 10)
    assert sum(pat) == 3 and fi.fired == 3
    assert pat[:3] == [1, 1, 1]  # rate 1.0 fires until the bound


# ---------------------------------------------------------------------------
# Ticket sentinel (satellite a) and result-count guard (satellite b)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("resilience", [True, False])
def test_ticket_sentinel_distinguishes_none_result(monkeypatch, resilience):
    """A drain legitimately returning ``None`` must still mark the ticket
    done — the old ``_result is None`` check conflated that with
    "unfilled" and would deadlock/assert in ``result()``."""
    s, stmt, _ = _mk()
    sched = _sched(resilience=resilience)
    t = sched.submit(stmt, {"cutoff": 3})
    monkeypatch.setattr(stmt, "execute_many",
                        lambda plist: [None] * len(plist))
    sched.flush()
    assert t.done()
    assert t.result() is None


def test_bare_fused_drain_result_mismatch_is_typed(monkeypatch):
    """Bare scheduler (resilience off): a short ``execute_fused`` result
    list must fail the wave with WaveResultMismatch (isolation retry then
    recovers per statement), never leak StopIteration from the zip."""
    s, stmt1, stmt2 = _mk()
    sched = _sched(fuse=True, resilience=False)
    real = s.execute_fused
    monkeypatch.setattr(s, "execute_fused", lambda calls: real(calls)[:-1])
    t1 = sched.submit(stmt1, {"cutoff": 3})
    t2 = sched.submit(stmt2, {"m": 2})
    sched.flush()
    assert _xs(t1.result()) == [0, 1, 2]  # isolation retry recovered
    assert len(_xs(t2.result())) == 8
    assert sched.stats["fused_isolated_retries"] == 2
    assert sched.stats["fused_isolated_errors"] == 0


def test_bare_many_drain_result_mismatch_is_typed(monkeypatch):
    s, stmt, _ = _mk()
    sched = _sched(resilience=False)
    real = stmt.execute_many
    monkeypatch.setattr(stmt, "execute_many", lambda plist: real(plist)[:-1])
    t = sched.submit(stmt, {"cutoff": 3})
    sched.flush()
    assert t.done()
    with pytest.raises(WaveResultMismatch):
        t.result()


def test_ladder_recovers_from_result_mismatch(monkeypatch):
    """Under resilience a short result list is just another tier failure:
    the ladder demotes and the ticket still gets its answer."""
    s, stmt, _ = _mk()
    sched = _sched()
    real = stmt.execute_many
    monkeypatch.setattr(stmt, "execute_many", lambda plist: real(plist)[:-1])
    t = sched.submit(stmt, {"cutoff": 4})
    sched.flush()
    assert _xs(t.result()) == [0, 1, 2, 3]
    assert sched.stats["demote_many_to_serial"] == 1
    assert sched.stats["tier_serial_ok"] == 1


# ---------------------------------------------------------------------------
# degradation ladder: demotions per site and tier
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("site", ["compile", "dispatch", "sync"])
def test_single_statement_fault_demotes_to_serial(site):
    s, stmt, _ = _mk()
    FaultInjector([FaultSpec(site=site, times=1)]).install(s)
    sched = _sched()
    t = sched.submit(stmt, {"cutoff": 4})
    sched.flush()
    assert _xs(t.result()) == [0, 1, 2, 3]
    assert sched.stats["demote_many_to_serial"] == 1
    assert sched.stats["tier_serial_ok"] == 1
    assert sched.stats["ladder_exhausted"] == 0


def test_fault_chain_demotes_to_interp():
    """Two dispatch faults eat the many and serial tiers; the INTERPRETED
    floor answers (dispatch never fires on the eager path)."""
    s, stmt, _ = _mk()
    fi = FaultInjector([FaultSpec(site="dispatch", times=None)]).install(s)
    sched = _sched()
    t = sched.submit(stmt, {"cutoff": 4})
    sched.flush()
    assert _xs(t.result()) == [0, 1, 2, 3]
    assert sched.stats["demote_many_to_serial"] == 1
    assert sched.stats["demote_serial_to_interp"] == 1
    assert sched.stats["tier_interp_ok"] == 1
    assert fi.fired >= 2


def test_interp_fault_surfaces_typed_error():
    """Only when the interpreter floor itself fails does the ticket error —
    and the error is typed (the injected fault), never silent data."""
    s, stmt, _ = _mk()
    FaultInjector([FaultSpec(site="*", times=None)]).install(s)
    sched = _sched()
    t = sched.submit(stmt, {"cutoff": 4})
    sched.flush()
    assert t.done()
    with pytest.raises(InjectedFault):
        t.result()
    assert sched.stats["ladder_exhausted"] == 1
    assert sched.stats["tier_interp_ok"] == 0


def test_fused_wave_fault_demotes_members_independently():
    """A fused-wave dispatch fault targeted at one member demotes the wave;
    per-statement retries then isolate the fault to the targeted member's
    tier walk while the other member succeeds at ``many``."""
    s, stmt1, stmt2 = _mk()
    fi = FaultInjector(
        [FaultSpec(site="dispatch", stmt=stmt1._query_fp, times=None)]
    ).install(s)
    sched = _sched(fuse=True)
    t1 = sched.submit(stmt1, {"cutoff": 3})
    t2 = sched.submit(stmt2, {"m": 2})
    sched.flush()
    assert _xs(t1.result()) == [0, 1, 2]  # via serial-or-deeper tier
    assert len(_xs(t2.result())) == 8     # via its own many tier
    assert sched.stats["fused_batches"] == 1   # the wave was attempted
    assert sched.stats["demote_fused_to_many"] == 2
    assert sched.stats["fused_isolated_retries"] == 2
    assert sched.stats["fused_isolated_errors"] == 0
    assert sched.stats["tier_many_ok"] == 1    # stmt2
    assert sched.stats["tier_interp_ok"] == 1  # stmt1 (dispatch faults
    assert fi.fired >= 3                       # hit many+serial tiers too)


def test_retry_backoff_within_tier():
    """Bounded in-tier retries absorb transient faults without demotion;
    backoff delays follow the exponential policy via the injected sleep."""
    s, stmt, _ = _mk()
    FaultInjector([FaultSpec(site="dispatch", times=2)]).install(s)
    sleeps: list = []
    cfg = ResilienceConfig(
        retry=RetryPolicy(max_attempts=3, backoff_s=0.1, backoff_mult=2.0))
    sched = _sched(resilience=cfg, sleep=sleeps.append)
    t = sched.submit(stmt, {"cutoff": 4})
    sched.flush()
    assert _xs(t.result()) == [0, 1, 2, 3]
    assert sched.stats["tier_many_ok"] == 1
    assert sched.stats["demote_many_to_serial"] == 0
    assert sched.stats["retry_backoffs"] == 2
    np.testing.assert_allclose(sleeps, [0.1, 0.2])


# ---------------------------------------------------------------------------
# circuit breakers on the serving path
# ---------------------------------------------------------------------------


def _drain_one(sched, stmt, cutoff=4):
    t = sched.submit(stmt, {"cutoff": cutoff})
    sched.flush()
    return t


def test_breaker_opens_then_half_open_probe_restores():
    """Persistent tier failures open the (statement, tier) breakers; open
    breakers route straight to the interp floor without burning retries;
    once the fault clears and the cooldown passes, the half-open probe
    restores the fast tiers."""
    s, stmt, _ = _mk()
    fi = FaultInjector([FaultSpec(site="dispatch", times=None)]).install(s)
    c = Clock()
    cfg = ResilienceConfig(breaker=BreakerConfig(
        failure_threshold=2, window_s=100.0, cooldown_s=5.0))
    sched = _sched(clock=c, resilience=cfg)
    key_many = (stmt._query_fp, "many")
    board = sched.ladder.board

    for i in range(2):  # two failing drains trip threshold=2 per tier
        assert _xs(_drain_one(sched, stmt).result()) == [0, 1, 2, 3]
    assert board.state(key_many) == OPEN
    assert board.state((stmt._query_fp, "serial")) == OPEN

    fired_before = fi.fired
    skips_before = sched.stats["breaker_open_skips"]
    t = _drain_one(sched, stmt)  # breakers open: straight to interp
    assert _xs(t.result()) == [0, 1, 2, 3]
    assert sched.stats["breaker_open_skips"] >= skips_before + 2
    assert fi.fired == fired_before  # no dispatch even attempted

    fi.specs.clear()  # the fault heals
    c.now += 10.0     # past cooldown: next ask admits a half-open probe
    t = _drain_one(sched, stmt)
    assert _xs(t.result()) == [0, 1, 2, 3]
    assert board.state(key_many) == CLOSED
    snap = sched.resilience_stats["breakers"][key_many]
    assert snap["opened"] == 1 and snap["probes"] == 1
    assert snap["restored"] == 1
    assert sched.stats["tier_many_ok"] >= 1


def test_breaker_half_open_probe_failure_reopens_on_ladder():
    s, stmt, _ = _mk()
    fi = FaultInjector([FaultSpec(site="dispatch", times=None)]).install(s)
    c = Clock()
    cfg = ResilienceConfig(breaker=BreakerConfig(
        failure_threshold=1, window_s=100.0, cooldown_s=5.0))
    sched = _sched(clock=c, resilience=cfg)
    key = (stmt._query_fp, "many")
    _drain_one(sched, stmt)  # one failure: threshold=1 opens immediately
    assert sched.ladder.board.state(key) == OPEN
    c.now += 10.0            # probe admitted, but the fault persists
    t = _drain_one(sched, stmt)
    assert _xs(t.result()) == [0, 1, 2, 3]  # interp floor still answers
    snap = sched.resilience_stats["breakers"][key]
    assert snap["reopened"] == 1
    assert sched.ladder.board.state(key) == OPEN


def test_fused_tier_breaker_skips_wave_membership():
    """An open fused-tier breaker drops the statement out of the wave
    before it forms; with only one eligible member left, fusion is
    skipped entirely and the groups drain per statement."""
    s, stmt1, stmt2 = _mk()
    fi = FaultInjector([FaultSpec(site="dispatch", times=None)]).install(s)
    cfg = ResilienceConfig(breaker=BreakerConfig(
        failure_threshold=1, window_s=100.0, cooldown_s=1e9))
    sched = _sched(fuse=True, resilience=cfg)
    t1 = sched.submit(stmt1, {"cutoff": 3})
    t2 = sched.submit(stmt2, {"m": 2})
    sched.flush()  # wave fails; both fused breakers open (threshold=1)
    t1.result(); t2.result()
    fb = sched.stats["fused_batches"]
    fi.specs.clear()
    t1 = sched.submit(stmt1, {"cutoff": 3})
    t2 = sched.submit(stmt2, {"m": 2})
    sched.flush()
    assert _xs(t1.result()) == [0, 1, 2]
    assert sched.stats["fused_batches"] == fb  # no new wave attempted
    assert sched.stats["breaker_open_skips"] >= 2


# ---------------------------------------------------------------------------
# deadlines: shed-before-drain
# ---------------------------------------------------------------------------


def test_expired_ticket_sheds_with_typed_error():
    s, stmt, _ = _mk()
    c = Clock()
    sched = _sched(clock=c, default_timeout_s=5.0)
    t_live = sched.submit(stmt, {"cutoff": 3})
    t_dead = sched.submit(stmt, {"cutoff": 4}, timeout_s=1.0)
    c.now = 3.0  # past t_dead's deadline, inside t_live's
    sched.flush()
    assert _xs(t_live.result()) == [0, 1, 2]
    assert t_dead.done()
    with pytest.raises(DeadlineExceeded):
        t_dead.result()
    assert sched.stats["deadline_shed"] == 1


def test_deadline_shed_is_pre_drain_not_mid_ladder():
    """All tickets expired: the drain sheds everything and never touches
    the session (no executor work for dead tickets)."""
    s, stmt, _ = _mk()
    fi = FaultInjector([]).install(s)  # pure event counter
    c = Clock()
    sched = _sched(clock=c, default_timeout_s=1.0)
    ts = [sched.submit(stmt, {"cutoff": k}) for k in (2, 3)]
    c.now = 10.0
    sched.flush()
    for t in ts:
        with pytest.raises(DeadlineExceeded):
            t.result()
    assert sched.stats["deadline_shed"] == 2
    assert fi.events == {}  # no seam was ever reached


def test_no_timeout_means_no_deadline():
    s, stmt, _ = _mk()
    c = Clock()
    sched = _sched(clock=c)
    t = sched.submit(stmt, {"cutoff": 3})
    c.now = 1e12
    sched.flush()
    assert _xs(t.result()) == [0, 1, 2]
    assert sched.stats["deadline_shed"] == 0


def test_admission_timeout_passthrough():
    from repro.serve.admission import AdmissionPolicy

    c = Clock()
    sched = _sched(clock=c)
    ap = AdmissionPolicy(scheduler=sched)
    t = ap.submit(tier=1, prompt_len=100, max_new_tokens=50,
                  temperature=0.5, timeout_s=2.0)
    c.now = 5.0
    ap.scheduler.flush()
    with pytest.raises(DeadlineExceeded):
        t.result()
    assert sched.stats["deadline_shed"] == 1


# ---------------------------------------------------------------------------
# chaos conformance oracle: fixed fault schedules
# (site × schedule shape × ladder tier reached × breaker state)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("site", ["compile", "dispatch", "sync"])
@pytest.mark.parametrize("times", [1, 3, None])
def test_chaos_fixed_schedule_recovers(site, times):
    """Any bounded or persistent fault at a recoverable site: every ticket
    still gets the fault-free oracle's answer."""
    out = check_chaos_oracle(5, 23, [FaultSpec(site=site, times=times)])
    assert all(kind == "ok" for kind, _ in out["outcomes"])
    if times is None:
        # the persistent schedule must have pushed at least one group all
        # the way to the interp floor
        assert out["stats"]["tier_interp_ok"] >= 1


def test_chaos_interp_floor_faults_are_typed():
    out = check_chaos_oracle(
        5, 23, [FaultSpec(site="*", times=None)],
        sites=("compile", "dispatch", "sync", "interp"))
    assert all(kind == "error" for kind, _ in out["outcomes"])
    assert all(isinstance(e, ResilienceError) for _, e in out["outcomes"])
    assert out["stats"]["ladder_exhausted"] == len(out["outcomes"])


def test_chaos_targeted_statement_fault():
    """A persistent fault scoped to one statement fingerprint: the wave
    demotes, the targeted statement walks its ladder, the others recover
    at their own tier — all tickets correct."""
    from conformance_util import fusion_queries, make_session

    probe = make_session(5, 23)
    fp = probe.prepare(fusion_queries()[1], FROID)._query_fp
    out = check_chaos_oracle(
        5, 23, [FaultSpec(site="dispatch", stmt=fp, times=None)])
    assert all(kind == "ok" for kind, _ in out["outcomes"])
    assert out["stats"]["demote_fused_to_many"] >= 2
    assert all(site == "dispatch" for site, _, _ in out["injector"].injected)


def test_chaos_open_breaker_still_conformant():
    """Threshold-1 breakers + persistent dispatch faults: breakers open
    mid-drain and route around the failing tiers; results stay correct
    and the transitions are observable."""
    cfg = ResilienceConfig(breaker=BreakerConfig(
        failure_threshold=1, window_s=100.0, cooldown_s=1e9))
    out = check_chaos_oracle(
        5, 23, [FaultSpec(site="dispatch", times=None)], resilience=cfg,
        clock=Clock())
    assert all(kind == "ok" for kind, _ in out["outcomes"])
    opened = sum(b["opened"] for b in out["resilience"]["breakers"].values())
    assert opened >= 1


def test_chaos_half_open_probe_still_conformant():
    """A fault that dies after one firing + an instant cooldown: the
    breaker opens, the very next ask probes half-open, the probe succeeds
    and restores — under a live queue, with conformant results."""
    c = Clock()
    cfg = ResilienceConfig(breaker=BreakerConfig(
        failure_threshold=1, window_s=100.0, cooldown_s=0.0))
    out = check_chaos_oracle(
        5, 23, [FaultSpec(site="dispatch", times=1)], resilience=cfg,
        clock=c)
    assert all(kind == "ok" for kind, _ in out["outcomes"])


@pytest.mark.parametrize("chaos_seed", [0, 1, 2, 3, 4])
def test_chaos_seeded_sweep(chaos_seed):
    """Deterministic mirror of the hypothesis chaos strategy (per the
    PR-5 precedent: the generative surface keeps a fixed-seed floor that
    runs where hypothesis is absent)."""
    out = check_chaos_oracle(7, 23, chaos_seed=chaos_seed, rate=0.4)
    assert all(kind == "ok" for kind, _ in out["outcomes"])


def test_chaos_seeded_sweep_with_interp_faults():
    out = check_chaos_oracle(
        7, 23, chaos_seed=2, rate=0.5,
        sites=("compile", "dispatch", "sync", "interp"))
    for kind, v in out["outcomes"]:
        assert kind == "ok" or isinstance(v, ResilienceError)


def test_chaos_deadline_under_faults():
    """Deadlines compose with fault schedules: with an advancing clock and
    a tight timeout, tickets either answer correctly, shed typed, or (if
    the schedule exhausts the ladder) carry the typed fault."""

    class Step:
        def __init__(self):
            self.now = 0.0

        def __call__(self):
            self.now += 0.5
            return self.now

    out = check_chaos_oracle(
        5, 23, [FaultSpec(site="dispatch", times=2)], clock=Step(),
        timeout_s=4.0)
    kinds = [k for k, _ in out["outcomes"]]
    assert all(k in ("ok", "error") for k in kinds)
    for kind, v in out["outcomes"]:
        if kind == "error":
            assert isinstance(v, ResilienceError)
