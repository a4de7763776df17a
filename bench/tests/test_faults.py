"""Whole runs of each cell on the CPU at a tiny scale, the look for a chip
skipped: ``correct`` holds on the engine as it is, and comes out false
when the engine alters its answers where it produces them."""
import jax.numpy as jnp
import pytest

from bench import harness

TINY = {"scale_factor": 0.001}
#: the open loop at a rate and warm-up a CPU run holds
MIX = {"udf_calls.open": {"rate_per_s": 100, "warm_batches_up_to": 4}}
CELLS = ["udf_queries.power", "udf_calls.open", "udf_calls.serial"]


def run(workload):
    return harness.run_cell(workload, 2**31 + 77, 1.0, False,
                            require_chip=False, config=TINY,
                            mix=MIX.get(workload), log=lambda *a, **k: None)


def altered(result):
    """The answer with its first float column 1 % off (the first column
    one off where it has no float column)."""
    from repro.core import MaskedTable
    from repro.core.session import QueryResult
    from repro.tables.table import Column, Table

    m = result.masked
    cols = dict(m.table.columns)
    name = next((n for n, c in cols.items()
                 if jnp.issubdtype(c.data.dtype, jnp.floating)), None)
    if name is None:
        name = next(iter(cols))
        data = cols[name].data + 1
    else:
        data = cols[name].data * 1.01
    cols[name] = Column(data, cols[name].valid, cols[name].dictionary)
    return QueryResult(MaskedTable(Table(cols), m.mask), result.plan,
                       result.elapsed_s, result.stats, policy=result.policy,
                       cache_hit=result.cache_hit)


@pytest.mark.parametrize("workload", CELLS)
def test_correct_on_the_engine_as_it_is(workload):
    r = run(workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", CELLS)
def test_an_answer_altered_where_produced_fails(workload, monkeypatch):
    from repro.core.session import PreparedStatement

    execute, many = PreparedStatement.execute, PreparedStatement.execute_many
    monkeypatch.setattr(PreparedStatement, "execute",
                        lambda self, params=None: altered(
                            execute(self, params=params)))
    monkeypatch.setattr(PreparedStatement, "execute_many",
                        lambda self, plist: [altered(r)
                                             for r in many(self, plist)])
    r = run(workload)
    assert not r["correct"]
    assert r["checks"]["gap"]["value"] > r["checks"]["gap"]["limit"] \
        or r["checks"]["mismatches"]["value"] > 0


def test_answers_handed_to_the_wrong_tickets_fail(monkeypatch):
    """Each wave's answers rotated by one ticket: every answer is right for
    some call, but not for the call it is handed to.  The rate is above
    what the CPU serves, so waves of several distinct bindings form."""
    from repro.core.session import PreparedStatement

    many = PreparedStatement.execute_many
    monkeypatch.setattr(PreparedStatement, "execute_many",
                        lambda self, plist: (lambda rs: rs[1:] + rs[:1])(
                            list(many(self, plist))))
    r = harness.run_cell("udf_calls.open", 2**31 + 77, 1.0, False,
                         require_chip=False, config=TINY,
                         mix={"rate_per_s": 400, "warm_batches_up_to": 4},
                         log=lambda *a, **k: None)
    assert not r["correct"]
    assert r["checks"]["mismatches"]["value"] > 0


def test_no_chip_fails_the_run():
    with pytest.raises(harness.NoChip):
        harness.check_device(1)
