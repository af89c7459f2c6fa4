"""host_apply_rows_inplace: the XLA-free offload apply kernels.

C++ (native/host_apply.cpp) vs numpy reference parity, agreement with the
jax HOST_SPARSE_APPLY rules they mirror, and the f32-only guard."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_embeddings_tpu.ops import sparse_update


def _rows(seed, v=64, w=8, n=32):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, v, n).astype(np.int32)
    contribs = rng.randn(n, w).astype(np.float32)
    rep, sums, valid = jax.device_get(
        sparse_update.prepare_safe_grad(jnp.asarray(ids),
                                        jnp.asarray(contribs), v))
    table = rng.randn(v, w).astype(np.float32)
    return table, rep, sums, valid


def _state(kind, table, seed=3):
    rng = np.random.RandomState(seed)
    if kind == "sgd":
        return ()
    if kind == "adagrad":
        return (np.abs(rng.randn(*table.shape)).astype(np.float32) + 0.1,)
    return (rng.randn(*table.shape).astype(np.float32) * 0.01,
            np.abs(rng.randn(*table.shape)).astype(np.float32) * 0.01,
            np.float32(3.0))        # count AFTER increment (caller contract)


@pytest.mark.parametrize("kind", ["sgd", "adagrad", "adam"])
def test_cpp_matches_numpy_reference(kind):
    table, rep, sums, valid = _rows(0)
    st = _state(kind, table)

    t_cpp = table.copy()
    s_cpp = tuple(x.copy() if getattr(x, "ndim", 0) else x for x in st)
    sparse_update.host_apply_rows_inplace(kind, t_cpp, s_cpp, rep, sums,
                                          valid, 0.05)

    t_np = table.copy()
    s_np = tuple(x.copy() if getattr(x, "ndim", 0) else x for x in st)
    sparse_update.host_apply_rows_inplace(kind, t_np, s_np, rep, sums,
                                          valid, 0.05, reference=True)

    np.testing.assert_allclose(t_cpp, t_np, rtol=1e-6, atol=1e-6)
    for a, b in zip(s_cpp, s_np):
        if getattr(a, "ndim", 0):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kind", ["sgd", "adagrad", "adam"])
def test_matches_jax_host_rule(kind):
    """In-place kernels == the compute_on host rules they mirror
    (HOST_SPARSE_APPLY), row for row."""
    table, rep, sums, valid = _rows(1)
    st = _state(kind, table)

    jt = jnp.asarray(table)
    if kind == "adam":
        # jax rule increments count itself: pass the PRE-increment count
        js = (jnp.asarray(st[0]), jnp.asarray(st[1]),
              jnp.asarray(st[2] - 1.0))
    else:
        js = tuple(jnp.asarray(x) for x in st)
    want_t, want_s = sparse_update.HOST_SPARSE_APPLY[kind](
        jt, js, jnp.asarray(rep), jnp.asarray(sums), jnp.asarray(valid),
        jnp.float32(0.05))

    got_t = table.copy()
    got_s = tuple(x.copy() if getattr(x, "ndim", 0) else x for x in st)
    sparse_update.host_apply_rows_inplace(kind, got_t, got_s, rep, sums,
                                          valid, 0.05)

    np.testing.assert_allclose(got_t, np.asarray(want_t), rtol=2e-5,
                               atol=2e-6)
    for a, b in zip(got_s, want_s):
        if getattr(a, "ndim", 0):
            np.testing.assert_allclose(a, np.asarray(b), rtol=2e-5,
                                       atol=2e-6)
        else:
            assert float(a) == float(b)


def test_set_kind_replaces_rows():
    """kind='set' (ISSUE 6, the weight-streaming delta apply seam):
    valid reps get their rows REPLACED by the payload, invalid (padded)
    slots — which alias row 0 — leave the table untouched."""
    table, rep, sums, valid = _rows(7)
    before = table.copy()
    payload = np.random.RandomState(9).randn(*sums.shape) \
        .astype(np.float32)
    sparse_update.host_apply_rows_inplace("set", table, (), rep, payload,
                                          valid, 0.0)
    ok = valid > 0.0
    np.testing.assert_array_equal(table[rep[ok]], payload[ok])
    untouched = np.ones(len(table), bool)
    untouched[rep[ok]] = False
    np.testing.assert_array_equal(table[untouched], before[untouched])
    # zero-valid call (all slots padded): a pure no-op
    t2 = before.copy()
    sparse_update.host_apply_rows_inplace(
        "set", t2, (), np.zeros_like(rep), payload,
        np.zeros_like(valid), 0.0)
    np.testing.assert_array_equal(t2, before)


def test_non_f32_rejected():
    table, rep, sums, valid = _rows(2)
    with pytest.raises(TypeError, match="float32-only"):
        sparse_update.host_apply_rows_inplace(
            "sgd", table.astype(np.float16), (), rep, sums, valid, 0.05)


def test_unknown_kind_rejected():
    table, rep, sums, valid = _rows(4)
    with pytest.raises(NotImplementedError):
        sparse_update.host_apply_rows_inplace("rmsprop", table, (), rep,
                                              sums, valid, 0.05)


def test_rejects_noncontiguous_buffers():
    """ADVICE r5: the in-place apply consumes raw pointers with dense
    row-major stride assumptions — non-contiguous views must be refused,
    not silently corrupted."""
    table, rep, sums, valid = _rows(11)
    bad_table = np.asfortranarray(table)
    assert not bad_table.flags["C_CONTIGUOUS"]
    with pytest.raises(ValueError, match="C-contiguous"):
        sparse_update.host_apply_rows_inplace(
            "sgd", bad_table, (), rep, sums, valid, 0.1)
    acc = np.zeros_like(table)
    bad_acc = acc[:, ::2]                       # strided state view
    with pytest.raises(ValueError, match="C-contiguous"):
        sparse_update.host_apply_rows_inplace(
            "adagrad", table, (bad_acc,), rep, sums[:, ::2].copy(),
            valid, 0.1)
