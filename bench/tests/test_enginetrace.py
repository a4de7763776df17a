"""The engine's side of the trace on small synthesized traces: operator
families of device ops, per-thread idle attribution and the metrics that
read them."""
import pytest

from bench import enginetrace as E
from bench import harness
from bench import tracereduce as T
from bench.tracereduce import Summary


def engine_trace():
    """A 10 s window on one device.  Device: a join ``while`` 0-3 s with
    its body fusion 1-2 s (one interval), a groupagg fusion 3-4 s, an
    unscoped op 4-5 s; idle 5-7 s and 8-10 s, a groupagg op 7-8 s.
    Host thread 0 (the benchmark's client): ``bench.materialize`` 5-7 s
    holding the engine's ``froid.materialize`` 5-6.5 s, ``bench.execute``
    8-10 s.  Thread 1 (a submitter): ``froid.sched.drain`` 8.5-10 s."""
    ops = [E.ScopedOp("while.4", 0.0, 3.0, "while", scope="froid.join"),
           E.ScopedOp("fusion.1", 1.0, 2.0, "fusion", scope="froid.join"),
           E.ScopedOp("fusion.2", 3.0, 4.0, "fusion",
                      scope="froid.groupagg"),
           E.ScopedOp("copy.3", 4.0, 5.0, "copy"),
           E.ScopedOp("fusion.2", 7.0, 8.0, "fusion",
                      scope="froid.groupagg")]
    spans = [E.ThreadSpan(T.WINDOW_SPAN, 0.0, 10.0),
             E.ThreadSpan("bench.materialize:q", 5.0, 7.0),
             E.ThreadSpan("froid.materialize", 5.0, 6.5),
             E.ThreadSpan("bench.execute:q", 8.0, 10.0),
             E.ThreadSpan("froid.sched.drain", 8.5, 10.0, thread=1)]
    return ops, spans


def test_family_is_the_innermost_froid_scope():
    assert E.family("jit(raw)/froid.project/froid.join/while/body") \
        == "froid.join"
    assert E.family("jit(f)/vmap(froid.groupagg)/scatter-add") \
        == "froid.groupagg"
    assert E.family("jit(f)/jit(main)/add") == E.UNSCOPED
    assert E.family("") == E.UNSCOPED


def test_scope_seconds_count_a_while_and_its_body_once():
    ops, _ = engine_trace()
    s = E.scope_seconds(ops, 0.0, 10.0)
    assert s == pytest.approx({"froid.join": 3.0, "froid.groupagg": 2.0,
                               E.UNSCOPED: 1.0})
    assert sum(s.values()) == pytest.approx(T.busy_seconds(ops, 0.0, 10.0))


def test_gaps_go_to_the_engine_span_on_any_thread():
    s = E.summarize(*engine_trace())
    # gap 5-7: froid.materialize is innermost at its middle; gap 8-10: the
    # drain on thread 1 before bench.execute on thread 0
    assert s.idle_by_host == pytest.approx(
        {"froid.materialize": 2.0, "froid.sched.drain": 2.0})


def test_engine_before_bench_then_the_later_start():
    ops = [T.Op("a", 0.0, 1.0), T.Op("a", 2.0, 3.0), T.Op("a", 4.0, 5.0)]
    spans = [E.ThreadSpan(T.WINDOW_SPAN, 0.0, 6.0),
             # gap 1-2 (middle 1.5): a bench span on thread 0 starting
             # later than a froid span on thread 1; froid wins
             E.ThreadSpan("bench.poll", 1.2, 2.0),
             E.ThreadSpan("froid.sched.drain", 0.5, 2.0, thread=1),
             # gap 3-4 (middle 3.5): two bench spans; the later start wins
             E.ThreadSpan("bench.wait", 3.0, 4.0),
             E.ThreadSpan("bench.execute:q", 3.2, 4.0, thread=2)]
    # gap 5-6: nothing covers it
    assert E.gaps_by_host(ops, spans, 0.0, 6.0) == pytest.approx(
        {"froid.sched.drain": 1.0, "bench.execute:q": 1.0, "idle": 1.0})


def test_one_thread_of_bench_spans_reads_as_tracereduce_does():
    ops = [T.Op("sort.1", 1.0, 2.0, "sort"), T.Op("fusion.2", 2.0, 3.0),
           T.Op("fusion.3", 2.5, 4.0), T.Op("fusion.2", 6.0, 7.0)]
    spans = [T.Span(T.WINDOW_SPAN, 0.0, 10.0), T.Span("bench.pass", 1.0, 10.0),
             T.Span("bench.execute:Q1", 1.0, 4.0),
             T.Span("bench.materialize:Q1", 4.0, 6.0),
             T.Span("bench.execute:Q3", 6.0, 10.0)]
    threaded = [E.ThreadSpan(sp.label, sp.start, sp.end) for sp in spans]
    assert E.gaps_by_host(ops, threaded, 0.0, 10.0) == \
        T.gaps_by_host(ops, spans, 0.0, 10.0)


def test_summary_shares_and_span_means():
    s = E.summarize(*engine_trace())
    assert s.busy_s == pytest.approx(6.0)
    assert s.share("froid.join") == pytest.approx(50.0)
    assert s.share("froid.sort") == 0.0
    assert s.mean_ms(["froid.materialize"], "froid.materialize") \
        == pytest.approx(1500.0)
    assert s.mean_ms(["froid.args"], "froid.execute") is None
    assert [k for k, _ in s.breakdown()["device_scopes"]] == [
        "froid.join", "froid.groupagg", E.UNSCOPED]


def test_a_trace_without_scopes_has_no_share():
    ops = [E.ScopedOp("fusion.1", 0.0, 1.0)]
    s = E.summarize(ops, [E.ThreadSpan(T.WINDOW_SPAN, 0.0, 2.0)])
    assert s.share("froid.join") is None


def _run(workload):
    run = harness.Run(harness.cell(workload), device_kind="TPU v5 lite")
    run.trace = Summary(window_s=10.0, busy_s=6.0, sort_s=0.0, op_s={},
                        idle_by_host={})
    return run


def test_engine_metrics_read_the_trace(monkeypatch):
    s = E.summarize(*engine_trace())
    s.span_s.update({"froid.execute": [4, 0.4], "froid.args": [4, 0.004],
                     "froid.dispatch": [4, 0.002]})
    monkeypatch.setattr(E, "load", lambda trace_dir: s)
    read = {m: harness.load_named("metrics", m).read(_run("udf_calls.open"))
            for m in ("join_share.queries", "groupagg_share.serial",
                      "materialize_ms.open", "dispatch_ms.serial")}
    assert read == pytest.approx({
        "join_share.queries": 50.0, "groupagg_share.serial": 100 / 3,
        "materialize_ms.open": 1500.0, "dispatch_ms.serial": 1.5})


def test_engine_metrics_read_nothing_untraced():
    run = _run("udf_calls.serial")
    run.trace = None
    for m in ("join_share.queries", "groupagg_share.serial",
              "materialize_ms.open", "dispatch_ms.serial", "queue_ms.open"):
        assert harness.load_named("metrics", m).read(run) is None


def test_queue_ms_reads_the_scheduler_counters():
    run = _run("udf_calls.open")
    read = harness.load_named("metrics", "queue_ms.open").read
    run.sched_stats = ({"drained": 50}, {"drained": 80})   # no counter
    assert read(run) is None
    run.sched_stats = ({"drained": 50, "queue_wait_s": 1.0},
                       {"drained": 80, "queue_wait_s": 2.5})
    assert read(run) == pytest.approx(50.0)


def test_setup_metrics_read_the_process_record_up_to_the_window():
    from repro import telemetry

    run = _run("udf_queries.power")
    run.window_start = float("-inf")
    for m in ("compile_s", "catalog_s"):
        assert harness.load_named("metrics", m).read(run) == 0.0
    run.window_start = float("inf")
    assert harness.load_named("metrics", "compile_s").read(run) == \
        telemetry.busy_seconds(telemetry.COMPILE)


def test_op_paths_read_the_hlo_a_trace_keeps(tmp_path):
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("froid.sort"):
            y = jnp.sort(x)
        with jax.named_scope("froid.groupagg"):
            return jnp.sum(y * 2.0)

    g = jax.jit(f)
    x = jnp.arange(64.0)
    g(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    g(x).block_until_ready()
    jax.profiler.stop_trace()
    paths = E.op_paths(E.newest(str(tmp_path)))
    (module,) = [m for m in paths if m.startswith("jit_f")]
    assert {E.family(p) for p in paths[module].values()} >= {
        "froid.sort", "froid.groupagg"}
    assert E.instruction("%fusion.82 = s32[8]{0} fusion(s32[8]{0} %p)") \
        == "fusion.82"
