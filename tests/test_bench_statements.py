"""The benchmark's statements on every execution path the engine serves
them by, each answer against the statement's own float64 reference.

Paths: ``execute``, ``execute_async``, ``Session.execute_fused`` with the
next statement of the same configuration as partner, and, for the
parametric statements, ``execute_many`` waves of distinct bindings.  The
data is the benchmark's TPC-H generator at scale factor 0.001 from a fixed
seed, one ``Session`` per one-chip configuration, loaded and registered
the way ``bench.harness`` sets up a run; the comparison is
``bench.reference.Tally`` under each configuration's ``limits``, the check
that decides a benchmark run's ``correct``.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:   # the benchmark's statements and references
    sys.path.insert(0, str(ROOT))

from bench import harness, reference  # noqa: E402
from repro.core import Session, resolve_policy  # noqa: E402

SEED = 7
SCALE_FACTOR = 0.001
#: the one-chip configurations; the four-chip one serves the same
#: statements as ``tpch_sf1_udf_calls``
CONFIGS = {name: json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                            .read_text())
           for name in ("tpch_sf1_udf_queries", "tpch_sf1_udf_calls")}
#: statement name -> its configuration's name
STATEMENTS = {s: name for name, c in CONFIGS.items() for s in c["statements"]}
PARAMETRIC = [s for s in STATEMENTS
              if hasattr(harness.load_named("statements", s), "bindings")]


def _partner(name: str) -> str:
    """The statement after ``name`` in its configuration, cyclically."""
    order = CONFIGS[STATEMENTS[name]]["statements"]
    return order[(order.index(name) + 1) % len(order)]


@pytest.fixture(scope="module")
def tpch():
    """One dataset, and per configuration a session holding it with the
    configuration's functions and its statements prepared."""
    datasets = {json.dumps(c["datasets"], sort_keys=True)
                for c in CONFIGS.values()}
    assert len(datasets) == 1, "the configurations share one dataset"
    config = {**next(iter(CONFIGS.values())), "scale_factor": SCALE_FACTOR}
    data = harness.generate(config, np.random.SeedSequence(SEED))
    engines = {}
    for name, c in CONFIGS.items():
        db = Session()
        harness.load(db, data)
        for f in c["functions"]:
            harness.load_named("functions", f).register(db)
        policy = resolve_policy(c["policy"])
        engines[name] = {
            s: db.prepare(harness.load_named("statements", s).build(), policy)
            for s in c["statements"]}
    return data, engines


def _bindings(name: str, data, n: int) -> list:
    """``n`` distinct bindings drawn by the statement's own rule; ``n``
    times ``None`` for a statement without parameters."""
    mod = harness.load_named("statements", name)
    if not hasattr(mod, "bindings"):
        return [None] * n
    out: list = []
    for b in mod.bindings(np.random.default_rng(SEED), 64 * n, data):
        if b not in out:
            out.append(b)
    assert len(out) >= n, f"{name}: fewer than {n} distinct bindings"
    return out[:n]


def _check(data, answered) -> None:
    """Each ``(statement, params, QueryResult)`` against its reference,
    under its configuration's limits."""
    tally = reference.Tally()
    cache: dict = {}
    configs = set()
    for name, params, result in answered:
        mod = harness.load_named("statements", name)
        ref = mod.reference(data, params, reference.F64, cache)
        tally.add(reference.from_output(*harness.fetch(result)), ref, name)
        configs.add(STATEMENTS[name])
    (config,) = configs
    checks = tally.checks(CONFIGS[config]["limits"])
    assert tally.compared == len(answered)
    assert reference.within(checks), (checks, tally.by_label)


@pytest.mark.parametrize("path", ("execute", "execute_async",
                                  "execute_fused"))
@pytest.mark.parametrize("name", list(STATEMENTS))
def test_statement_answers_its_reference(tpch, name, path):
    data, engines = tpch
    stmts = engines[STATEMENTS[name]]
    (params,) = _bindings(name, data, 1)
    if path == "execute":
        answered = [(name, params, stmts[name].execute(params=params))]
    elif path == "execute_async":
        answered = [(name, params,
                     stmts[name].execute_async(params=params).result())]
    else:
        other = _partner(name)
        (other_params,) = _bindings(other, data, 1)
        results = stmts[name].session.execute_fused(
            [(stmts[name], params), (stmts[other], other_params)])
        assert all(r.stats.get("fused") for r in results), \
            [r.stats for r in results]
        answered = [(name, params, results[0]),
                    (other, other_params, results[1])]
    _check(data, answered)


@pytest.mark.parametrize("wave", (1, 3, 8))
@pytest.mark.parametrize("name", PARAMETRIC)
def test_wave_of_distinct_bindings_answers_each_reference(tpch, name, wave):
    data, engines = tpch
    binds = _bindings(name, data, wave)
    results = engines[STATEMENTS[name]][name].execute_many(binds)
    assert len(results) == wave
    _check(data, [(name, p, r) for p, r in zip(binds, results)])
