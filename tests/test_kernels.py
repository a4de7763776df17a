"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.relagg.ref import grouped_aggregate_ref
from repro.kernels.relagg.relagg import MAX_GROUPS, relagg_pallas
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas


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


# ---------------------------------------------------------------- flash attention
@pytest.mark.parametrize(
    "B,Hq,Hk,Sq,Sk,D",
    [
        (1, 4, 2, 256, 256, 64),
        (2, 4, 4, 128, 128, 32),
        (1, 8, 2, 96, 160, 64),   # non-multiple-of-block sizes
        (1, 2, 1, 64, 320, 128),
    ],
)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(rng, B, Hq, Hk, Sq, Sk, D, causal, dtype):
    q = jnp.asarray(rng.normal(size=(B, Hq, Sq, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hk, Sk, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hk, Sk, D)), dtype)
    a = flash_attention_pallas(q, k, v, causal=causal, interpret=True, bq=64, bk=64)
    b = flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(rng, window):
    q = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    a = flash_attention_pallas(q, k, v, causal=True, window=window,
                               interpret=True, bq=64, bk=64)
    b = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


def test_flash_attention_decode_offset(rng):
    """Sq=1 with q_offset == cache position (serving decode path)."""
    q = jnp.asarray(rng.normal(size=(2, 4, 1, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 2, 512, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 2, 512, 64)), jnp.float32)
    a = flash_attention_pallas(q, k, v, causal=True, q_offset=511, interpret=True)
    b = flash_attention_ref(q, k, v, causal=True, q_offset=511)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------- ssd scan
@pytest.mark.parametrize(
    "BH,BG,L,P,N,chunk",
    [
        (4, 2, 256, 32, 64, 64),
        (2, 2, 100, 16, 32, 32),  # unpadded length
        (6, 3, 64, 64, 128, 64),
        (2, 1, 512, 64, 128, 128),
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(rng, BH, BG, L, P, N, chunk, dtype):
    n_rep = BH // BG
    xdt = jnp.asarray(rng.normal(size=(BH, L, P)) * 0.5, dtype)
    dtA = -jnp.asarray(rng.uniform(0.01, 0.5, size=(BH, L)), dtype)
    B = jnp.asarray(rng.normal(size=(BG, L, N)) * 0.3, dtype)
    C = jnp.asarray(rng.normal(size=(BG, L, N)) * 0.3, dtype)
    a = ssd_scan_pallas(xdt, dtA, B, C, n_rep, chunk=chunk, interpret=True)
    b = ssd_scan_ref(xdt, dtA, B, C, n_rep)
    scale = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) + 1e-9
    err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) / scale
    assert err < (3e-4 if dtype == jnp.float32 else 3e-2), err


def test_ssd_matches_decode_steps(rng):
    from repro.kernels.ssd_scan.ops import ssd_decode_step, ssd_scan

    Bb, L, H, P, G, N = 2, 16, 4, 8, 2, 16
    x = jnp.asarray(rng.normal(size=(Bb, L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 0.3, size=(Bb, L, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.1, 1.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(Bb, L, G, N)) * 0.3, jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(Bb, L, G, N)) * 0.3, jnp.float32)
    y_full = ssd_scan(x, dt, A, Bm, Cm, use_kernel=False)
    state = jnp.zeros((Bb, H, N, P), jnp.float32)
    ys = []
    for t in range(L):
        state, y_t = ssd_decode_step(state, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t])
        ys.append(y_t)
    np.testing.assert_allclose(
        np.asarray(jnp.stack(ys, 1)), np.asarray(y_full), atol=1e-4
    )
