"""One benchmark run: set-up, the measured window, the check and the
result line.

Everything that belongs to one configuration, traffic mix or metric is
found by its name in ``BENCHMARK.json``:

* a configuration is ``bench/configs/<config>.json``: its datasets
  (``bench/datasets/<name>.py``), functions (``bench/functions/<name>.py``)
  and statements (``bench/statements/<name>.py``, each with its plain
  reference), its guarantees and the limits of its check;
* a traffic mix is ``bench/traffic/<mix>.json``, read by
  :mod:`bench.generator`;
* a metric is ``bench/metrics/<metric>.py`` with ``read(run)``, which
  returns the number or ``None`` where the run has nothing to read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import inspect
import json
import os
import queue
import shutil
import sys
import threading
import time
from pathlib import Path

import numpy as np

from bench import generator, reference, tracereduce

ROOT = Path(__file__).resolve().parents[1]
#: JAX's persistent compilation cache when the environment names none: a
#: fixed path in the checkout, so that a later run finds what an earlier
#: one compiled
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
#: how long past the window's close a run waits for answers still due
GRACE_S = 60.0


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------- lookup
def spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_named(kind: str, name: str, root: Path = ROOT):
    """The module ``bench/<kind>/<name>.py`` under ``root``."""
    modname = f"bench.{kind}.{name}"
    if modname in sys.modules:
        return sys.modules[modname]
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    s = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(s)
    sys.modules[modname] = mod
    s.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list


def _reports(metric: dict, workload: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or workload in cells


def cell(name: str, root: Path = ROOT) -> Cell:
    """A workload of ``root``'s ``BENCHMARK.json`` with its configuration,
    its traffic mix and the metrics it reports."""
    bench = spec(root)
    w = {c["name"]: c for c in bench["workloads"]}.get(name)
    if w is None:
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name) and (
                     "workloads" in m or m["moves"] in e2e_names)]
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer)


# ---------------------------------------------------------------- records
@dataclasses.dataclass
class Call:
    stmt: str
    due: float          # host clock at which the call was due
    end: float = 0.0    # host clock at which its answer was on the host
    ok: bool = False
    error: str = ""


@dataclasses.dataclass
class Run:
    """What a run measured; the metric readers read this."""

    cell: Cell
    setup_s: float = 0.0
    prepare_s: list = dataclasses.field(default_factory=list)
    warmup_s: float = 0.0
    window_start: float = 0.0
    window_end: float = 0.0
    calls: list = dataclasses.field(default_factory=list)
    sched_stats: tuple | None = None   # scheduler counters at open, close
    trace: tracereduce.Summary | None = None
    least_bytes: int = 0               # summed over the calls completed
    device_kind: str = ""
    window_misses: dict = dataclasses.field(default_factory=dict)
    lateness_s: list = dataclasses.field(default_factory=list)


class Spans:
    """``jax.profiler.TraceAnnotation`` host spans, in traced runs only."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str, **kw):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name, **kw)


# ---------------------------------------------------------------- set-up
def check_device(chips: int):
    """JAX's devices, or :class:`NoChip` unless they are TPUs enough."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devices[0].platform}, "
                     f"not a TPU")
    if len(devices) < chips:
        raise NoChip(f"{len(devices)} chips, the cell needs {chips}")
    return devices


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else :data:`CACHE_DIR`; every program is cached."""
    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def generate(config: dict, seed_seq):
    from bench.data import Data

    data = Data()
    streams = seed_seq.spawn(len(config["datasets"]))
    for (name, params), ss in zip(config["datasets"].items(), streams):
        gen = load_named("datasets", name).generate
        if "scale_factor" in inspect.signature(gen).parameters:
            params = {**params, "scale_factor": config["scale_factor"]}
        gen(data, np.random.default_rng(ss), **params)
    return data


def load(session, data) -> None:
    """The generated tables into the engine, put on the device in one
    transfer."""
    import jax

    from repro.tables.table import Column, DictEncoding, Table

    arrays = jax.device_put(data.tables)
    for tname, cols in arrays.items():
        vocab = data.vocab[tname]
        session.create_table(tname, Table({
            c: Column(a, dictionary=(DictEncoding(vocab[c]) if c in vocab
                                     else None))
            for c, a in cols.items()}))


def least_bytes(stmt_mod, data) -> int:
    """The bytes a statement has to read at least: each column it
    references, every row, once."""
    return sum(data.tables[t][c].nbytes
               for t, cols in stmt_mod.COLUMNS.items() for c in cols)


def fetch(result):
    """A call's whole answer on the host: the mask, every column's data
    and NULL flags, and the vocabularies of string columns."""
    import jax

    m = result.masked
    cols = {n: (c.data, c.validity()) for n, c in m.table.columns.items()}
    mask, cols = jax.device_get((m.mask, cols))
    vocabs = {n: (c.dictionary.vocab if c.dictionary is not None else None)
              for n, c in m.table.columns.items()}
    return mask, cols, vocabs


def latencies_ms(run) -> np.ndarray:
    """Each call's latency, from when it was due to when its whole answer
    was on the host; a failed call counts with the time it took to fail."""
    return np.array([(c.end - c.due) * 1e3 for c in run.calls])


def completed_per_s(run) -> float | None:
    """Calls completed over the time from the window's opening to the last
    completion."""
    ends = [c.end for c in run.calls if c.ok]
    return len(ends) / (max(ends) - run.window_start) if ends else None


def warm_batches(up_to: int) -> list[int]:
    """The ``execute_many`` buckets an open loop can form: powers of two."""
    out, b = [], 1
    while b <= up_to:
        out.append(b)
        b *= 2
    return out


# ---------------------------------------------------------------- drivers
def drive_closed(run, stmts, plan, seconds, spans, keep, whole: int):
    """Closed loop, one client: each call waits for its whole answer.  The
    clock is read every ``whole`` calls, so a pass begun inside the window
    runs to its end."""
    end = run.window_start + seconds
    reqs, i = plan.requests, 0
    while time.perf_counter() < end:
        for _ in range(whole):
            req = reqs[i % len(reqs)]
            call = Call(req.stmt, time.perf_counter())
            out = None
            try:
                with spans("bench.execute", stmt=req.stmt):
                    r = stmts[req.stmt].execute(params=req.params)
                with spans("bench.materialize", stmt=req.stmt):
                    out = fetch(r)
                call.ok = True
            except Exception as e:  # a failed call counts as failed
                call.error = f"{type(e).__name__}: {e}"
            call.end = time.perf_counter()
            run.calls.append(call)
            if out is not None:
                keep(i, req, out)
            i += 1


def drive_open(run, stmts, plan, sched, spans, keep):
    """Open loop: each call is submitted once due, however the earlier
    ones fare; its latency runs from when it was due to when its answer is
    on the host.  Two threads, as a server has them: this one submits each
    call when it is due, and a serving thread polls the scheduler's
    windows and fetches each answer once its wave has run.  Only the
    serving thread has spans, so that they nest."""
    start = run.window_start
    reqs = plan.requests
    calls = [Call(r.stmt, start + r.due) for r in reqs]
    run.calls = calls
    submitted: queue.SimpleQueue = queue.SimpleQueue()   # (index, ticket)
    deadline = start + reqs[-1].due + GRACE_S if reqs else start
    server = threading.Thread(target=serve, name="bench-serve", args=(
        calls, reqs, sched, submitted, deadline, spans, keep))
    server.start()
    try:
        for i, (req, call) in enumerate(zip(reqs, calls)):
            wait = call.due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            submitted.put((i, sched.submit(stmts[req.stmt], req.params)))
            run.lateness_s.append(time.perf_counter() - call.due)
    finally:
        submitted.put(None)
        server.join()


def serve(calls, reqs, sched, submitted, deadline, spans, keep):
    """The serving thread of :func:`drive_open`: takes each submitted
    ticket, polls the scheduler until the ticket's wave has run, and
    fetches the answer; a call still unanswered at ``deadline`` fails."""
    pending: list = []   # (index, ticket), in submit order
    closed = False
    while (not closed or pending) and time.perf_counter() < deadline:
        try:
            # with nothing in flight, block until the next submission
            item = submitted.get(timeout=0.05) if not pending and not closed \
                else submitted.get_nowait()
            while True:
                if item is None:
                    closed = True
                else:
                    pending.append(item)
                item = submitted.get_nowait()
        except queue.Empty:
            pass
        with spans("bench.poll"):
            sched.poll()
        still = []
        for j, t in pending:
            if not t.done():
                still.append((j, t))
                continue
            try:
                with spans("bench.materialize", stmt=reqs[j].stmt):
                    out = fetch(t.result())
                calls[j].ok = True
            except Exception as e:
                calls[j].error = f"{type(e).__name__}: {e}"
                out = None
            calls[j].end = time.perf_counter()
            if out is not None:
                keep(j, reqs[j], out)
        if still and len(still) == len(pending):
            with spans("bench.wait"):
                time.sleep(0.0002)
        pending = still
    for j, _ in pending:
        calls[j].error = "no answer within the grace period"
        calls[j].end = time.perf_counter()


# ---------------------------------------------------------------- the run
@dataclasses.dataclass
class State:
    """A cell set up for its window: the engine's session and statements,
    the generated data and the plan of calls."""

    cell: Cell
    seed: int
    dev: object
    data: object
    db: object
    mods: dict
    stmts: dict
    plan: generator.Plan
    spans: Spans
    run: Run
    sched: object = None
    kept: dict = dataclasses.field(default_factory=dict)
    peak_bytes: int | None = None
    by_stmt: dict = dataclasses.field(default_factory=dict)


def setup(c: Cell, seed: int, seconds: float, trace: bool, t0: float, *,
          require_chip: bool = True) -> State:
    """Make the data from the seed, prepare the cell's statements, lay out
    the calls and run every shape the window uses once."""
    import jax

    devices = check_device(c.chips) if require_chip else jax.devices()
    if require_chip:
        enable_compile_cache()
    from repro.core import Session, resolve_policy

    spans = Spans(trace)
    data_ss, traffic_ss = np.random.SeedSequence(seed).spawn(2)
    data = generate(c.config, data_ss)
    db = Session()
    load(db, data)
    for f in c.config["functions"]:
        load_named("functions", f).register(db)

    run = Run(c, device_kind=devices[0].device_kind)
    mods = {n: load_named("statements", n) for n in c.config["statements"]}
    policy = resolve_policy(c.config["policy"])
    stmts = {}
    for name, mod in mods.items():
        t = time.perf_counter()
        with spans("bench.prepare", stmt=name):
            stmts[name] = db.prepare(mod.build(), policy)
        run.prepare_s.append(time.perf_counter() - t)
    plan = generator.plan(c.mix, mods, data,
                          np.random.default_rng(traffic_ss), seconds)
    st = State(c, seed, devices[0], data, db, mods, stmts, plan, spans, run)

    t = time.perf_counter()
    warm(st)
    if c.mix.get("path") == "scheduler":
        from repro.resilience.ladder import ResilienceConfig
        from repro.serve.scheduler import CoalescingScheduler

        opts = c.mix["scheduler"]
        st.sched = CoalescingScheduler(fuse=opts["fuse"], resilience=(
            ResilienceConfig(interp_fallback=opts["interp_fallback"])))
    run.warmup_s = time.perf_counter() - t
    run.setup_s = time.perf_counter() - t0
    return st


def warm(st: State) -> None:
    """Every program the window can run, once: one call of each statement,
    or for the scheduler's path each ``execute_many`` bucket up to
    ``warm_batches_up_to``."""
    first: dict = {}
    for r in st.plan.requests:
        first.setdefault(r.stmt, []).append(r.params)
    for name, binds in first.items():
        with st.spans("bench.warmup", stmt=name):
            if st.cell.mix.get("path") != "scheduler":
                fetch(st.stmts[name].execute(params=binds[0]))
                continue
            for b in warm_batches(st.cell.mix["warm_batches_up_to"]):
                for r in st.stmts[name].execute_many((binds * b)[:b]):
                    fetch(r)


def window(st: State, seconds: float, trace: bool) -> Run:
    """The measured window, traced when ``trace``."""
    import jax

    run = st.run
    run.calls, run.lateness_s = [], []
    st.kept = {}

    def keep(i, req, out):
        if st.plan.checked is None or i in st.plan.checked:
            st.kept[i] = (req, out)

    misses0 = {k: v for k, v in st.db.cache_stats.items()
               if k.endswith("_misses")}
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    stats0 = dict(st.sched.stats) if st.sched is not None else None
    run.window_start = time.perf_counter()
    with st.spans(tracereduce.WINDOW_SPAN):
        if st.cell.mix["loop"] == "open":
            drive_open(run, st.stmts, st.plan, st.sched, st.spans, keep)
        else:
            whole = (len(st.plan.requests) if st.cell.mix["calls"] == "passes"
                     else 1)
            drive_closed(run, st.stmts, st.plan, seconds, st.spans, keep,
                         whole)
    run.window_end = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    if stats0 is not None:
        run.sched_stats = (stats0, dict(st.sched.stats))
    run.window_misses = {k: st.db.cache_stats[k] - v
                         for k, v in misses0.items()}
    run.least_bytes = sum(least_bytes(st.mods[x.stmt], st.data)
                          for x in run.calls if x.ok)
    st.peak_bytes = (st.dev.memory_stats() or {}).get("peak_bytes_in_use")
    if trace:
        run.trace = tracereduce.summarize(*tracereduce.read(str(TRACE_DIR)))
    return run


def check(st: State) -> tuple[dict, int]:
    """Each kept answer against the plain reference: the numbers compared,
    each beside its limit, and how many answers were compared."""
    tally = reference.Tally()
    cache: dict = {}
    refs: dict = {}
    for i in sorted(st.kept):
        req, (mask, cols, vocabs) = st.kept[i]
        key = (req.stmt, tuple(sorted((req.params or {}).items())))
        if key not in refs:
            refs[key] = st.mods[req.stmt].reference(st.data, req.params,
                                                    reference.F64, cache)
        tally.add(reference.from_output(mask, cols, vocabs), refs[key],
                  req.stmt)
    st.by_stmt = tally.by_label
    checks = tally.checks(st.cell.config["limits"])
    checks["unanswered"] = {"value": sum(not x.ok for x in st.run.calls),
                            "limit": 0}
    return checks, tally.compared


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t0: float | None = None, require_chip: bool = True,
             config: dict | None = None, mix: dict | None = None,
             log=print) -> dict:
    """One run of a cell; returns the result line as a dict.  ``config``
    and ``mix`` update the cell's configuration and traffic (tests run
    the cells at tiny sizes on the CPU so)."""
    t0 = time.perf_counter() if t0 is None else t0
    c = cell(workload)
    c.config.update(config or {})
    c.mix.update(mix or {})
    st = setup(c, seed, seconds, trace, t0, require_chip=require_chip)
    run = window(st, seconds, trace)
    # the engine's state goes before the reference runs
    st.db = st.stmts = st.sched = None
    checks, compared = check(st)

    metrics = {}
    for m in (c.per_layer if trace else c.end_to_end):
        v = load_named("metrics", m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = st.dev
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": c.chips, "memory_peak_bytes": st.peak_bytes}
    failed = checks["unanswered"]["value"]
    correct = compared > 0 and reference.within(checks)
    result = {"correct": correct, "attempted": len(run.calls),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()

    # earlier lines: what a reader needs to trust the window
    log(f"setup_s={run.setup_s} prepare_s={sum(run.prepare_s)} "
        f"warmup_s={run.warmup_s} window_s={run.window_end - run.window_start}"
        f" calls={len(run.calls)} answers_compared={compared}",
        file=sys.stderr)
    log("cache misses inside the window (0 = nothing compiled there): "
        + json.dumps(run.window_misses), file=sys.stderr)
    if run.sched_stats:
        s0, s1 = run.sched_stats
        log("scheduler over the window: " + json.dumps(
            {k: s1[k] - s0.get(k, 0) for k in s1 if s1[k] != s0.get(k, 0)}),
            file=sys.stderr)
    if run.calls:
        lat = latencies_ms(run)
        log(f"latency ms: p50={np.percentile(lat, 50)} p99="
            f"{np.percentile(lat, 99)} max={lat.max()} over_1s="
            f"{int((lat > 1e3).sum())}", file=sys.stderr)
        stmt = np.array([x.stmt for x in run.calls])
        log("latency ms by statement [calls, mean, p50, p95, max]: "
            + json.dumps({s: [int((stmt == s).sum())] + [
                round(float(f(lat[stmt == s])), 3) for f in (
                    np.mean, np.median, lambda a: np.percentile(a, 95),
                    np.max)] for s in sorted(set(stmt))}), file=sys.stderr)
    if run.lateness_s:
        late = np.asarray(run.lateness_s) * 1e3
        log(f"generator lateness ms: p50={np.percentile(late, 50)} "
            f"p95={np.percentile(late, 95)} max={late.max()}", file=sys.stderr)
    log("widest gap and mismatches by statement: " + json.dumps(st.by_stmt),
        file=sys.stderr)
    for e in sorted({x.error for x in run.calls if x.error})[:5]:
        log(f"failed call: {e}", file=sys.stderr)
    for name, ch in checks.items():
        log(f"check {name} {ch['value']} limit {ch['limit']}",
            file=sys.stderr)
    result["checks"] = checks
    return result
