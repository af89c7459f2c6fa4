"""Interpret-mode correctness for the Pallas sorted-unique scatter-add RMW
kernel (ops/pallas_scatter.py) vs the XLA .at[].add reference.

The compiled path is held to the chip's compiler in
tests/test_chip_compile.py and to XLA on the chip in chip_smoke.py. The
kernel exists because XLA's scatter cost 100-280 ns/row on TPU when last
measured, and dedup_sum's sorted-unique output makes a conflict-free DMA
stream legal.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_embeddings_tpu.ops import pallas_scatter as ps


def make_sorted_unique(rng, n_real, v, n_total):
    ids = np.sort(rng.choice(v, size=n_real, replace=False)).astype(np.int32)
    fill = (v + 1 + np.arange(n_total - n_real)).astype(np.int32)
    return np.concatenate([ids, fill])


@pytest.mark.parametrize("v,w,n_real,n_total", [
    (500, 8, 100, 128),       # padded tail of OOB fillers
    (1000, 16, 512, 512),     # no fillers, multiple tiles
    (300, 128, 77, 100),      # wide rows, odd counts
])
def test_scatter_add_sorted_unique_matches_xla(v, w, n_real, n_total):
    rng = np.random.default_rng(v + w)
    ids = make_sorted_unique(rng, n_real, v, n_total)
    delta = rng.standard_normal((n_total, w)).astype(np.float32)
    delta[n_real:] = 0.0                    # filler deltas are zero (contract)
    table = rng.standard_normal((v, w)).astype(np.float32)

    got = ps.scatter_add_sorted_unique(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(delta))
    want = jnp.asarray(table).at[jnp.asarray(ids)].add(
        jnp.asarray(delta), mode="drop")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_scatter_add_sorted_unique_bf16_table():
    rng = np.random.default_rng(9)
    v, w, n = 400, 16, 96
    ids = make_sorted_unique(rng, n, v, 128)
    delta = np.zeros((128, w), np.float32)
    delta[:n] = rng.standard_normal((n, w)).astype(np.float32)
    table = (rng.standard_normal((v, w)) * 0.1).astype(jnp.bfloat16)

    got = ps.scatter_add_sorted_unique(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(delta))
    want = jnp.asarray(table).at[jnp.asarray(ids)].add(
        jnp.asarray(delta).astype(jnp.bfloat16), mode="drop")
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2)


def test_scatter_add_under_jit():
    rng = np.random.default_rng(2)
    v, w = 600, 8
    ids = make_sorted_unique(rng, 200, v, 256)
    delta = np.zeros((256, w), np.float32)
    delta[:200] = rng.standard_normal((200, w))
    table = rng.standard_normal((v, w)).astype(np.float32)

    f = jax.jit(lambda t, i, d: ps.scatter_add_sorted_unique(t, i, d))
    got = f(jnp.asarray(table), jnp.asarray(ids), jnp.asarray(delta))
    want = jnp.asarray(table).at[jnp.asarray(ids)].add(
        jnp.asarray(delta), mode="drop")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("v,w,n_real,n_total", [
    (500, 8, 100, 128), (800, 16, 512, 512),
])
def test_adagrad_rows_fused_matches_formula(v, w, n_real, n_total):
    """Fused adagrad RMW kernel == the row-wise adagrad formula on unique
    rows, with untouched rows (and OOB fillers) left intact."""
    rng = np.random.default_rng(v)
    ids = make_sorted_unique(rng, n_real, v, n_total)
    sums = np.zeros((n_total, w), np.float32)
    sums[:n_real] = rng.standard_normal((n_real, w))
    table = rng.standard_normal((v, w)).astype(np.float32)
    acc = np.full((v, w), 0.1, np.float32)
    lr, eps = 0.05, 1e-10

    t2, a2 = ps.adagrad_rows_sorted_unique(
        jnp.asarray(table), jnp.asarray(acc), jnp.asarray(ids),
        jnp.asarray(sums), lr, eps)

    want_t, want_a = table.copy(), acc.copy()
    for k in range(n_real):
        r = ids[k]
        want_a[r] = acc[r] + sums[k] * sums[k]
        want_t[r] = table[r] - lr * sums[k] / np.sqrt(want_a[r] + eps)
    np.testing.assert_allclose(np.asarray(a2), want_a, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(t2), want_t, rtol=1e-5, atol=1e-5)
