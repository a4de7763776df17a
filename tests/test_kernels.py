"""The relagg kernel's shape/dtype sweeps: Pallas (interpret=True) vs its
ref.py oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.relagg.ref import grouped_aggregate_ref
from repro.kernels.relagg.relagg import MAX_GROUPS, relagg_pallas


# ---------------------------------------------------------------- relagg
@pytest.mark.parametrize("n", [64, 257, 1000, 4096])
# block_rows=None sizes the tile from the group count (pick_block_rows);
# 3 and 7 are the TPC-H dictionary-key widths, MAX_GROUPS the kernel's bound
@pytest.mark.parametrize("groups,block_rows", [
    (1, 256), (8, 256), (130, 256), (3, None), (7, None), (MAX_GROUPS, None),
])
@pytest.mark.parametrize("n_aggs", [1, 4])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_relagg_sweep(rng, n, groups, block_rows, n_aggs, dtype):
    gid = jnp.asarray(rng.integers(0, groups, n), jnp.int32)
    mask = jnp.asarray(rng.random(n) > 0.4)
    vals = jnp.asarray(rng.normal(size=(n, n_aggs)), dtype)
    s1, c1 = relagg_pallas(gid, mask, vals, groups, block_rows=block_rows,
                           interpret=True)
    s2, c2 = grouped_aggregate_ref(gid, mask, vals, groups)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


@pytest.mark.parametrize("groups", [0, MAX_GROUPS + 1])
def test_relagg_rejects_groups_outside_bound(groups):
    gid = jnp.zeros(128, jnp.int32)
    with pytest.raises(ValueError, match="groups"):
        relagg_pallas(gid, gid >= 0, jnp.ones((128, 1), jnp.float32), groups,
                      interpret=True)


def test_relagg_empty_mask(rng):
    gid = jnp.zeros(128, jnp.int32)
    mask = jnp.zeros(128, bool)
    vals = jnp.ones((128, 2), jnp.float32)
    s, c = relagg_pallas(gid, mask, vals, 4, block_rows=128, interpret=True)
    assert float(jnp.abs(s).sum()) == 0.0 and float(c.sum()) == 0.0
