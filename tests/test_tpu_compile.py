"""Compiles for a described TPU v5e chip (none attached): the relagg kernel
at TPC-H SF 1 shapes and a whole TPC-H plan, through the TPU compiler.

Interpret mode runs a kernel's body op by op and cannot show what Mosaic
refuses (layouts, VMEM); these compiles can.  Nothing runs, so they say
nothing about results or times.  The topology is described inside a
fixture, never at import: only the worker that runs this file loads the
TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.relagg.relagg import MAX_GROUPS, relagg_pallas

#: lineitem rows at TPC-H SF 1
SF1_ROWS = 6_000_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU compiler otherwise writes its logs under the temp directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU library / unknown topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    (where ``JAX_COMPILATION_CACHE_DIR`` names one) but cannot be read back
    without a chip; keep the cache out of it."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


# (groups, value columns): Q1-like dictionary keys (l_returnflag: 3,
# l_shipmode: 7) and a decorrelated-build key at the kernel's bound
@pytest.mark.parametrize("groups,n_aggs", [(3, 4), (7, 2), (MAX_GROUPS, 4)])
def test_relagg_compiles_for_v5e(one_chip, no_compile_cache, groups, n_aggs):
    gid = jax.ShapeDtypeStruct((SF1_ROWS,), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((SF1_ROWS,), jnp.bool_, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((SF1_ROWS, n_aggs), jnp.float32,
                                sharding=one_chip)
    compiled = jax.jit(
        lambda g, m, v: relagg_pallas(g, m, v, groups, interpret=False)
    ).lower(gid, mask, vals).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_q6_plan_compiles_for_v5e(one_chip, no_compile_cache):
    """The jitted closure a ``Session`` builds for the inlined Q6 UDF
    statement, lowered on described-device shapes of its catalog."""
    from benchmarks.tpch_udfs import q6_udf, register_udfs
    from repro.core import FROID, Session
    from repro.data.tpch import generate_tpch

    db = Session()
    generate_tpch(db, sf=0.1)
    register_udfs(db)
    stmt = db.prepare(q6_udf(), FROID)
    # no store attached: this builds the closure without compiling it
    entry, _, _ = db._executable(stmt.node, stmt._query_fp, stmt.policy, None)
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        db._catalog_args())
    compiled = jax.jit(entry.raw).lower(args, {}).compile()
    mem = compiled.memory_analysis()
    li = db.catalog["lineitem"]
    assert mem.argument_size_in_bytes >= li.columns["l_extendedprice"].data.nbytes
    assert "revenue" in entry.out_dicts


def test_q12_plan_compiles_for_v5e_without_a_search_loop(one_chip,
                                                          no_compile_cache):
    """Q12's lineitem → orders join builds on a dense order key: its
    compiled program probes by direct address, so no binary-search
    ``while`` loop (``searchsorted``'s lowering) is left in it."""
    from benchmarks.tpch_udfs import q12_udf, register_udfs
    from repro.core import FROID, Session
    from repro.data.tpch import generate_tpch

    db = Session()
    generate_tpch(db, sf=0.1)
    register_udfs(db)
    stmt = db.prepare(q12_udf(), FROID)
    assert db.timing_stats["dense_joins"] == 1, stmt.explain()
    entry, _, _ = db._executable(stmt.node, stmt._query_fp, stmt.policy, None)
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        db._catalog_args())
    hlo = jax.jit(entry.raw).lower(args, {}).compile().as_text()
    assert " while(" not in hlo
