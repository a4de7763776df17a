"""Decorrelation pass: fixed conformance runs of the shared oracle
(``conformance_util.check_decorrelation_oracle``), rewrite-shape and
explain assertions, shared-build dedup, content-derived naming stability,
cost-model pricing, and the hypothesis layer over the same spec space
(skipped where hypothesis is absent — the fixed grid below is the
deterministic floor).

The oracle's contract: the decorrelated plan (keyed GroupAgg build +
left/semi/anti join) equals the per-row apply element-wise — masks,
validity (NULL for a binding with no matching group; COUNT coalesces to
0), and values — across FROID/INTERPRETED/HEKATON, serial and
``execute_many`` (sharded and unsharded), empty inner relations, and DDL
invalidation.  Non-rewritable bodies (non-equi correlation) keep the
per-row apply and still answer identically.
"""
from __future__ import annotations

import numpy as np
import pytest

from conformance_util import (
    DECORR_AGGS,
    DECORR_KEYSHAPES,
    DECORR_KINDS,
    _plan_has_correlated_subquery,
    check_decorrelation_oracle,
    decorr_query,
    make_session,
    populate_session,
)
from repro.core import FROID, Session
from repro.core import relalg as R

# ---------------------------------------------------------------------------
# fixed oracle grid: every kind and keyshape, the full agg set on the
# canonical shape, plus the empty-inner / missing-group / DDL axes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", DECORR_KINDS)
@pytest.mark.parametrize("keyshape", DECORR_KEYSHAPES)
def test_decorr_oracle_kinds_by_keyshapes(kind, keyshape):
    check_decorrelation_oracle(kind, keyshape, "sum", seed=3, n_rows=23)


@pytest.mark.parametrize("agg", DECORR_AGGS)
def test_decorr_oracle_all_aggs(agg):
    check_decorrelation_oracle("agg", "direct", agg, seed=5, n_rows=23)


@pytest.mark.parametrize("agg", ("sum", "count", "min"))
def test_decorr_oracle_empty_inner(agg):
    """Zero fact rows: every binding is an empty group — scalar aggs go
    NULL (COUNT goes 0), EXISTS goes false, semi joins empty out."""
    check_decorrelation_oracle("agg", "direct", agg, seed=2, n_rows=0)
    check_decorrelation_oracle("exists", "direct", agg, seed=2, n_rows=0)
    check_decorrelation_oracle("semi", "direct", agg, seed=2, n_rows=0)


def test_decorr_oracle_missing_groups_null_semantics():
    """The "expr" keyshape shifts bindings past the fact domain: those
    outer rows must see NULL (scalar) / FALSE (exists) exactly like the
    per-row apply over an empty filtered relation."""
    for agg in ("sum", "avg", "count"):
        check_decorrelation_oracle("agg", "expr", agg, seed=11, n_rows=23)
    check_decorrelation_oracle("not_exists", "expr", "sum", seed=11, n_rows=23)


def test_decorr_oracle_ddl_invalidation():
    check_decorrelation_oracle("agg", "direct", "sum", seed=7, n_rows=23,
                               ddl=True)
    check_decorrelation_oracle("semi", "multi", "sum", seed=7, n_rows=23,
                               ddl=True)


# ---------------------------------------------------------------------------
# rewrite shape: explain surfacing, shared-build dedup, stable naming
# ---------------------------------------------------------------------------


def test_explain_shows_decorrelated_shape():
    db = make_session(3, 23)
    stmt = db.prepare(decorr_query("agg", "direct", "sum"), FROID)
    txt = stmt.explain()
    assert "GroupAgg keys=" in txt and "Join[left]" in txt, txt
    assert not _plan_has_correlated_subquery(stmt.plan)
    # the non-rewritable shape keeps (and shows) the per-row apply
    stmt2 = db.prepare(decorr_query("agg", "nonequi", "sum"), FROID)
    assert "Join[left]" not in stmt2.explain()
    assert _plan_has_correlated_subquery(stmt2.plan)


def test_semi_anti_join_shapes():
    db = make_session(3, 23)
    kinds = {
        "semi": "Join[semi]",
        "anti": "Join[anti]",
    }
    for kind, marker in kinds.items():
        txt = db.prepare(decorr_query(kind, "direct", "sum"), FROID).explain()
        assert marker in txt, f"{kind}:\n{txt}"


def test_shared_build_dedup():
    """Three subqueries over the same correlated body collapse into ONE
    keyed GroupAgg build and ONE join — the shared-scan materialization
    half of the pass."""
    from repro.core.frontend import col, lit, scan, scalar_subquery, sum_
    from repro.core import scalar as S

    db = make_session(3, 23)

    def body():
        return (scan("facts").filter(col("fk") == S.Outer("k"))
                .agg(s=sum_(col("val"))))

    q = (scan("keys")
         .compute(a=scalar_subquery(body(), "s"),
                  b=scalar_subquery(body(), "s") * lit(2.0),
                  c=scalar_subquery(body(), "s") + lit(1.0))
         .project("k", "a", "b", "c"))
    stmt = db.prepare(q, FROID)
    assert not _plan_has_correlated_subquery(stmt.plan)
    joins = [n for n in R.walk_plan(stmt.plan) if isinstance(n, R.Join)]
    builds = [n for n in R.walk_plan(stmt.plan)
              if isinstance(n, R.GroupAgg) and n.keys]
    assert len(joins) == 1, stmt.explain()
    assert len(builds) == 1, stmt.explain()


def test_decorrelated_naming_is_content_derived():
    """Two independently-built sessions produce fingerprint-identical
    decorrelated plans: the rewrite's generated column names come from
    content digests, never from process-local counters — the property
    every cache tier (and the persistent store) keys on."""
    from repro.core.fingerprint import plan_fingerprint

    fps = []
    for _ in range(2):
        db = make_session(3, 23)
        stmt = db.prepare(decorr_query("agg", "multi", "sum"), FROID)
        fps.append(plan_fingerprint(stmt.plan))
    assert fps[0] == fps[1]


# ---------------------------------------------------------------------------
# cost model: decorrelated priced by distinct-binding cardinality, per-row
# priced by outer cardinality — the ratio the router consumes
# ---------------------------------------------------------------------------


def test_cost_model_prefers_decorrelated_at_scale():
    from repro.core import optimizer as O
    from repro.cost.model import estimate_plan

    db = Session()
    rng = np.random.default_rng(0)
    n = 1024
    db.create_table("facts",
                    fk=rng.integers(0, 7, n),
                    val=rng.normal(size=n).astype(np.float32),
                    qty=rng.integers(0, 9, n))
    db.create_table("keys", k=np.arange(1024) % 7)
    node = decorr_query("agg", "direct", "sum").node
    wanted = set(R.output_columns(node, db.catalog))
    dec = O.optimize(node, db.catalog, required=wanted)
    rules = tuple(r for r in O.DEFAULT_RULES
                  if r not in (O.decorrelate_in_computes,
                               O.decorrelate_filters))
    perrow = O.optimize(node, db.catalog, required=wanted, rules=rules)
    assert _plan_has_correlated_subquery(perrow)
    assert not _plan_has_correlated_subquery(dec)
    e_dec = estimate_plan(dec, db.catalog)
    e_row = estimate_plan(perrow, db.catalog)
    # per-row re-runs the body once per outer row; the decorrelated build
    # runs it once — at N=1024 outer rows the work profiles must separate
    # by a wide, algorithmic margin.  (seconds() adds the same fixed
    # dispatch overhead to both, so the roofline terms carry the signal
    # the router's comparison consumes.)
    assert e_row.flops > 50 * e_dec.flops, (
        f"per-row {e_row.flops:.3e} flops vs decorrelated "
        f"{e_dec.flops:.3e}")
    assert e_row.bytes > 50 * e_dec.bytes, (
        f"per-row {e_row.bytes:.3e} bytes vs decorrelated "
        f"{e_dec.bytes:.3e}")


# ---------------------------------------------------------------------------
# hypothesis layer: the same oracle over the generated spec space
# ---------------------------------------------------------------------------

try:  # no pip install in this environment: skip where absent
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    decorr_specs = st.tuples(
        st.sampled_from(DECORR_KINDS),
        st.sampled_from(DECORR_KEYSHAPES),
        st.sampled_from(DECORR_AGGS),
        st.integers(min_value=0, max_value=2**16),
        st.sampled_from((0, 1, 23, 64)),
    )

    @settings(max_examples=25, deadline=None,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.data_too_large])
    @given(decorr_specs,
           st.lists(st.integers(min_value=0, max_value=10), min_size=1,
                    max_size=4))
    def test_decorr_oracle_generative(spec, minqs):
        kind, keyshape, agg, seed, n_rows = spec
        check_decorrelation_oracle(
            kind, keyshape, agg, seed=seed, n_rows=n_rows,
            params_list=[{"minq": m} for m in minqs])

else:  # deterministic stand-in so the axis is never silently dark

    def test_decorr_oracle_generative_fallback():
        for spec in [("agg", "expr", "avg", 17, 1),
                     ("anti", "multi", "count", 23, 64),
                     ("exists", "nonequi", "max", 29, 23)]:
            kind, keyshape, agg, seed, n_rows = spec
            check_decorrelation_oracle(kind, keyshape, agg,
                                       seed=seed, n_rows=n_rows)
