"""Tiled one-hot-matmul sparse kernels (ops/pallas_tiled.py) vs XLA
reference semantics, interpret mode.

These kernels answer the scatter bottleneck (ops/pallas_tiled.py): every
memory access is a regular BlockSpec block
stream, duplicates aggregate inside an MXU matmul. The tests pin:
  * gather == jnp.take for valid ids, zero rows for invalid ids
  * sgd/adagrad == the sparse_update XLA paths (duplicates, invalid ids,
    all-filler and empty corners, non-divisible vocab/tile shapes)
"""

import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from distributed_embeddings_tpu.ops import pallas_tiled as pt
from distributed_embeddings_tpu.ops import sparse_update as su

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import tpu_sort_payload_sweep as sweep  # noqa: E402


def one_program(fn, *arrays, **options):
    """`fn(*arrays, **options)` compiled as one program, the arrays its
    arguments and the options constants of it: not one program for each
    operation, as an eager call compiles."""
    return jax.jit(lambda *a: fn(*a, **options))(*arrays)


def _mk(v, w, n, seed=0, frac_invalid=0.0, hot_skew=True):
    rng = np.random.RandomState(seed)
    if hot_skew:
        # power-law-ish: many duplicates at low ids plus a uniform tail
        ids = np.minimum(
            rng.zipf(1.3, n) - 1, v - 1).astype(np.int32)
    else:
        ids = rng.randint(0, v, n).astype(np.int32)
    if frac_invalid:
        k = int(n * frac_invalid)
        pos = rng.choice(n, k, replace=False)
        ids[pos[: k // 2]] = -1 - rng.randint(0, 5, k // 2)
        ids[pos[k // 2:]] = v + rng.randint(0, 5, k - k // 2)
    table = rng.randn(v, w).astype(np.float32)
    contribs = rng.randn(n, w).astype(np.float32)
    return jnp.asarray(table), jnp.asarray(ids), jnp.asarray(contribs)


@pytest.mark.parametrize("v,w,n,tile,chunk", [
    (1000, 16, 700, 128, 128),      # non-divisible vocab/tile
    (513, 8, 1300, 256, 128),       # odd vocab, heavy dup
    (4096, 128, 512, 1024, 128),    # wide rows
    (64, 16, 2000, 1024, 512),      # tile > vocab, chunk > n/4
])
def test_tiled_gather_matches_take(v, w, n, tile, chunk):
    table, ids, _ = _mk(v, w, n, seed=v + n)
    got = one_program(pt.tiled_gather, table, ids, chunk=chunk, tile=tile,
                      interpret=True)
    want = jnp.take(table, jnp.clip(ids, 0, v - 1), axis=0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_tiled_gather_invalid_ids_zero_rows():
    table, ids, _ = _mk(500, 16, 400, seed=3, frac_invalid=0.25)
    got = np.asarray(one_program(pt.tiled_gather, table, ids, interpret=True))
    idn = np.asarray(ids)
    bad = (idn < 0) | (idn >= 500)
    assert bad.any()
    np.testing.assert_allclose(got[bad], 0.0)
    np.testing.assert_allclose(
        got[~bad], np.asarray(table)[idn[~bad]], rtol=1e-5, atol=1e-5)


def test_tiled_gather_sorted_direct():
    table, ids, _ = _mk(2000, 32, 900, seed=11)
    sid = jnp.sort(ids)
    got = one_program(pt.tiled_gather_sorted, table, sid, interpret=True)
    want = jnp.take(table, sid, axis=0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("v,w,n,frac_invalid", [
    (1000, 16, 900, 0.0),
    (777, 8, 1500, 0.2),       # invalid ids must be dropped
    (4096, 128, 600, 0.0),
    (50, 16, 3000, 0.0),       # extreme duplication, tiny vocab
])
def test_tiled_sgd_matches_xla(v, w, n, frac_invalid):
    table, ids, contribs = _mk(v, w, n, seed=v, frac_invalid=frac_invalid)
    lr = 0.07
    got = one_program(pt.tiled_sgd, table, ids, contribs, lr=lr,
                      interpret=True)
    want = one_program(lambda table, ids, contribs: table.at[
        jnp.clip(ids, 0, v)].add(-lr * jnp.where(
            ((ids >= 0) & (ids < v))[:, None], contribs, 0.0)),
        table, ids, contribs)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("v,w,n,frac_invalid,tile,chunk", [
    (1000, 16, 900, 0.0, 1024, 512),
    (777, 8, 1500, 0.2, 128, 128),
    (4096, 128, 600, 0.0, 512, 256),
    (50, 16, 3000, 0.1, 1024, 512),
])
def test_tiled_adagrad_matches_sparse_update(v, w, n, frac_invalid, tile,
                                             chunk):
    table, ids, contribs = _mk(v, w, n, seed=7 * v, frac_invalid=frac_invalid)
    accum = jnp.full((v, w), 0.1, jnp.float32)
    lr = 0.05
    got_t, got_a = one_program(pt.tiled_adagrad, table, accum, ids, contribs,
                               lr=lr, tile=tile, chunk=chunk, interpret=True)
    want_t, want_a = one_program(
        su.sparse_adagrad, table, accum, su.SparseRowGrad(ids, contribs),
        lr=lr, strategy="sort")
    np.testing.assert_allclose(got_a, want_a, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got_t, want_t, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("v,w,n,frac_invalid", [
    (1000, 16, 900, 0.0),
    (777, 8, 1500, 0.2),
])
def test_tiled_adam_matches_sparse_update(v, w, n, frac_invalid):
    table, ids, contribs = _mk(v, w, n, seed=3 * v, frac_invalid=frac_invalid)
    mu = jnp.zeros((v, w), jnp.float32)
    nu = jnp.zeros((v, w), jnp.float32)
    cnt = jnp.zeros((), jnp.int32)
    lr = 0.02
    got = one_program(pt.tiled_adam, table, mu, nu, cnt, ids, contribs,
                      lr=lr, interpret=True)
    want = one_program(su.sparse_adam, table, mu, nu, cnt,
                       su.SparseRowGrad(ids, contribs), lr=lr,
                       strategy="sort")
    for g, wv, name in zip(got, want, ("table", "mu", "nu", "count")):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(wv, np.float32),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


def test_tiled_adam_two_steps_touched_only_decay():
    """Second step with DIFFERENT ids: rows touched only in step 1 must not
    decay in step 2 (lazy adam contract)."""
    v, w = 200, 8
    rng = np.random.RandomState(0)
    table = jnp.asarray(rng.randn(v, w).astype(np.float32))
    mu = jnp.zeros((v, w), jnp.float32)
    nu = jnp.zeros((v, w), jnp.float32)
    cnt = jnp.zeros((), jnp.int32)
    ids1 = jnp.asarray(np.arange(0, 50, dtype=np.int32))
    ids2 = jnp.asarray(np.arange(100, 150, dtype=np.int32))
    g1 = jnp.asarray(rng.randn(50, w).astype(np.float32))
    g2 = jnp.asarray(rng.randn(50, w).astype(np.float32))
    s_t, s_mu, s_nu, s_c = table, mu, nu, cnt
    w_t, w_mu, w_nu, w_c = table, mu, nu, cnt
    for ids, g in ((ids1, g1), (ids2, g2)):
        s_t, s_mu, s_nu, s_c = one_program(
            pt.tiled_adam, s_t, s_mu, s_nu, s_c, ids, g, lr=0.05,
            interpret=True)
        w_t, w_mu, w_nu, w_c = one_program(
            su.sparse_adam, w_t, w_mu, w_nu, w_c, su.SparseRowGrad(ids, g),
            lr=0.05, strategy="sort")
    np.testing.assert_allclose(s_t, w_t, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s_mu, w_mu, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s_nu, w_nu, rtol=1e-4, atol=1e-5)
    assert int(s_c) == int(w_c) == 2


def test_tiled_adagrad_traced_lr_and_jit():
    v, w, n = 600, 16, 800
    table, ids, contribs = _mk(v, w, n, seed=42)
    accum = jnp.full((v, w), 0.1, jnp.float32)

    @jax.jit
    def step(t, a, i, c, lr):
        return pt.tiled_adagrad(t, a, i, c, lr, interpret=True)

    got_t, got_a = step(table, accum, ids, contribs, jnp.float32(0.03))
    want_t, want_a = one_program(
        su.sparse_adagrad, table, accum, su.SparseRowGrad(ids, contribs),
        lr=0.03, strategy="sort")
    np.testing.assert_allclose(got_a, want_a, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got_t, want_t, rtol=1e-4, atol=1e-5)


def test_tiled_all_invalid_and_empty():
    v, w = 300, 8
    table = jnp.asarray(np.random.RandomState(0).randn(v, w), jnp.float32)
    accum = jnp.full((v, w), 0.1, jnp.float32)
    ids = jnp.full((256,), v + 3, jnp.int32)          # all invalid
    contribs = jnp.ones((256, w), jnp.float32)
    got_t, got_a = pt.tiled_adagrad(table, accum, ids, contribs, 0.1,
                                    interpret=True)
    np.testing.assert_allclose(got_t, table, rtol=1e-6)
    np.testing.assert_allclose(got_a, accum, rtol=1e-6)
    # empty
    t2 = pt.tiled_sgd(table, jnp.zeros((0,), jnp.int32),
                      jnp.zeros((0, w), jnp.float32), 0.1, interpret=True)
    assert t2 is table
    g2 = pt.tiled_gather(table, jnp.zeros((0,), jnp.int32), interpret=True)
    assert g2.shape == (0, w)


def test_tiled_strategy_full_train_equivalence():
    """strategy='tiled' through make_sparse_train_step: distributed sparse
    training with the tiled kernels (interpret mode on the 8-CPU mesh) must
    match the dense optax reference — the same contract the sort/dense
    strategies are held to."""
    from test_sparse_train import run_equivalence
    run_equivalence([(40, 16), (200, 16), (64, 8)], "adagrad",
                    strategy="tiled", rtol=1e-4, atol=1e-4)


def test_tiled_strategy_multihot_train_equivalence():
    from test_sparse_train import run_equivalence
    run_equivalence([(60, 16, "sum"), (500, 8, "sum")], "adagrad",
                    strategy="tiled", rtol=1e-4, atol=1e-4)


def test_tiled_embedding_lookup_matches_fused_contract():
    """tiled_embedding_lookup == the XLA gather+einsum formulation, incl.
    mean normalization, padded zero-weight slots and OOB clamping — and its
    custom VJP matches the dense-path gradients."""
    rng = np.random.RandomState(5)
    v, w, b, k = 400, 16, 64, 4
    table = jnp.asarray(rng.randn(v, w).astype(np.float32))
    ids = jnp.asarray(rng.randint(-3, v + 3, (b, k)).astype(np.int32))
    wts = jnp.asarray((rng.rand(b, k) * (rng.rand(b, k) > 0.3))
                      .astype(np.float32))
    from distributed_embeddings_tpu.ops import pallas_tiled as pt2

    for comb in ("sum", "mean"):
        def ref(tbl, wv):
            ww = wv
            if comb == "mean":
                ww = wv / jnp.maximum(jnp.sum(wv, 1, keepdims=True), 1.0)
            rows = jnp.take(tbl, jnp.clip(ids, 0, v - 1), axis=0)
            return jnp.einsum("bk,bkw->bw", ww, rows)

        got = one_program(pt2.tiled_embedding_lookup, table, ids, wts,
                          combiner=comb, interpret=True)
        np.testing.assert_allclose(got, jax.jit(ref)(table, wts), rtol=1e-5,
                                   atol=1e-5)
        # gradient parity (dense path)
        g = jnp.asarray(rng.randn(b, w).astype(np.float32))
        f_tiled = lambda t, wv: jnp.vdot(
            pt2.tiled_embedding_lookup(t, ids, wv, comb, interpret=True), g)
        f_ref = lambda t, wv: jnp.vdot(ref(t, wv), g)
        gt_t, gt_w = jax.jit(jax.grad(f_tiled, argnums=(0, 1)))(table, wts)
        gr_t, gr_w = jax.jit(jax.grad(f_ref, argnums=(0, 1)))(table, wts)
        np.testing.assert_allclose(gt_t, gr_t, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(gt_w, gr_w, rtol=1e-4, atol=1e-5)


def test_tiled_lookup_dense_grad_scatter_free():
    """Round 5 (ADVICE r4): differentiating the tiled lookup on the DENSE
    path must not materialize a zeros.at[ids].add table-gradient scatter —
    the backward aggregates via the sgd kernel reusing the forward's
    sort, so grad-of-lookup lowers with zero stablehlo.scatter ops."""
    import re
    from distributed_embeddings_tpu.ops import pallas_tiled as pt2

    v, w, b, k = 4096, 16, 32, 4
    table = jax.ShapeDtypeStruct((v, w), jnp.float32)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, v, (b, k))
                      .astype(np.int32))

    def loss(t):
        return jnp.sum(pt2.tiled_embedding_lookup(t, ids, None, "sum",
                                                  interpret=True))

    txt = jax.jit(jax.grad(loss)).lower(table).as_text()
    scatters = re.findall(r'"stablehlo\.scatter"', txt)
    assert not scatters, f"{len(scatters)} scatter ops in tiled-lookup grad"


def test_presorted_matches_fresh_sort():
    """tiled_sgd/adagrad/adam/gather with a caller-provided (sid, perm)
    must equal the fresh-sort path bit for bit."""
    from distributed_embeddings_tpu.ops import pallas_tiled as pt2

    rng = np.random.RandomState(9)
    v, w, n = 600, 16, 256
    ids = jnp.asarray(rng.randint(-5, v + 5, n).astype(np.int32))
    contribs = jnp.asarray(rng.randn(n, w).astype(np.float32))
    table = jnp.asarray(rng.randn(v, w).astype(np.float32))
    acc = jnp.abs(jnp.asarray(rng.randn(v, w).astype(np.float32))) + 0.1
    pre = pt2._sort_ids(ids, None, v)
    presorted = (pre[0], pre[2])

    a = pt2.tiled_sgd(table, ids, contribs, 0.05, interpret=True)
    b = pt2.tiled_sgd(table, ids, contribs, 0.05, interpret=True,
                      presorted=presorted)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    a = pt2.tiled_adagrad(table, acc, ids, contribs, 0.05, interpret=True)
    b = pt2.tiled_adagrad(table, acc, ids, contribs, 0.05, interpret=True,
                          presorted=presorted)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    mu = jnp.zeros((v, w), jnp.float32)
    nu = jnp.zeros((v, w), jnp.float32)
    cnt = jnp.zeros((), jnp.int32)
    a = pt2.tiled_adam(table, mu, nu, cnt, ids, contribs, 0.01,
                       interpret=True)
    b = pt2.tiled_adam(table, mu, nu, cnt, ids, contribs, 0.01,
                       interpret=True, presorted=presorted)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    a = pt2.tiled_gather(table, ids, interpret=True)
    b = pt2.tiled_gather(table, ids, interpret=True, presorted=presorted)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _payload_stream(case, w, seed):
    """(vocab, ids, contribs) of a fresh sort's edge: runs of duplicate ids
    (stability), ids < 0 and >= vocab keyed to the end, and a stream that
    fills no whole chunk of the walk."""
    rng = np.random.RandomState(seed)
    v, n = 700, {"runs": 1024, "invalid": 1024, "ragged": 1000}[case]
    if case == "runs":
        ids = np.repeat(rng.randint(0, v, n // 16), 16)
        rng.shuffle(ids[: n // 2])
    else:
        ids = rng.randint(-8, v + 8, n) if case == "invalid" \
            else rng.randint(0, 40, n)
    contribs = rng.randn(n, w).astype(np.float32)
    contribs[::7] = -0.0                 # signed zeros keep their bits
    return v, jnp.asarray(ids.astype(np.int32)), jnp.asarray(contribs)


def _bits(a):
    return np.asarray(a).view(np.uint32)


@pytest.mark.parametrize("form", ["stream", "gather", "carry1", "carry4"])
@pytest.mark.parametrize("width", [8, 16])
@pytest.mark.parametrize("case", ["runs", "invalid", "ragged"])
def test_carried_sort_is_the_gathers_bit_for_bit(case, width, form,
                                                 monkeypatch):
    """The fresh sort that carries the contribution columns hands the walk
    the sorted ids and the `[w, n]` rows that `sort_key_val` + the
    permutation gather give, bit for bit: it is stable on the same key.
    `stream` is `_sorted_stream`'s fresh branch with the width carried
    (`_sort_carrying`); the others are the forms
    `tools/tpu_sort_payload_sweep.py` times on the chip, each also held to
    numpy's stable argsort."""
    v, ids, contribs = _payload_stream(case, width, seed=width)
    if form == "stream":
        monkeypatch.setattr(pt, "SORT_PAYLOAD_WIDTHS", frozenset({width}))
        sid, cols = pt._sorted_stream(ids, contribs, v, None)
    else:
        sid, cols = jax.jit(sweep.variant(form, v))(ids, contribs)
        ref_sid, ref_cols = sweep.reference(np.asarray(ids), contribs, v)
        np.testing.assert_array_equal(_bits(sid), ref_sid)
        np.testing.assert_array_equal(_bits(cols), ref_cols)
    want_sid, want_rows, _ = pt._sort_ids(ids, contribs, v)
    assert cols.shape == (width, ids.shape[0])
    np.testing.assert_array_equal(np.asarray(sid), np.asarray(want_sid))
    np.testing.assert_array_equal(_bits(cols), _bits(want_rows).T)


@pytest.mark.parametrize("width", [8, 16, 24])
def test_the_sort_carries_only_the_width_read(width):
    """`sort_payload` carries the rows at the width read on the chip (16)
    and at no other: at 8 and 24 the fresh sort orders the ids alone and
    the rows are gathered by its permutation, as a caller's sort's always
    are."""
    v, ids, contribs = _payload_stream("invalid", width, seed=3)
    assert pt.sort_payload(width) == (width if width == 16 else 0)
    sid, cols = pt._sorted_stream(ids, contribs, v, None)
    want_sid, want_rows, perm = pt._sort_ids(ids, contribs, v)
    np.testing.assert_array_equal(np.asarray(sid), np.asarray(want_sid))
    np.testing.assert_array_equal(np.asarray(cols), np.asarray(want_rows).T)
    folded = pt._sorted_stream(ids, contribs, v, (want_sid, perm))
    np.testing.assert_array_equal(np.asarray(folded[1]),
                                  np.asarray(want_rows).T)
    text = jax.jit(lambda i, c: pt._sorted_stream(i, c, v, None)).lower(
        ids, contribs).as_text()
    assert ("stablehlo.gather" in text) is (width != 16)


@pytest.mark.parametrize("width", [8, 16])
@pytest.mark.parametrize("case", ["runs", "invalid", "ragged"])
def test_tiled_adagrad_with_the_carried_sort_is_the_gathers(case, width,
                                                            monkeypatch):
    """`tiled_adagrad` end to end over a fresh sort: the carried columns
    give the table and accumulator that the permutation gather gives, bit
    for bit: the kernel sees the same block."""
    v, ids, contribs = _payload_stream(case, width, seed=10 + width)
    rng = np.random.RandomState(width)
    table = jnp.asarray(rng.randn(v, width).astype(np.float32))
    acc = jnp.asarray(rng.rand(v, width).astype(np.float32)) + 0.1
    monkeypatch.setattr(pt, "SORT_PAYLOAD_WIDTHS", frozenset({width}))
    got = pt.tiled_adagrad(table, acc, ids, contribs, 0.05, interpret=True)
    monkeypatch.setattr(pt, "SORT_PAYLOAD_WIDTHS", frozenset())
    want = pt.tiled_adagrad(table, acc, ids, contribs, 0.05, interpret=True)
    for x, y in zip(got, want):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_tiled_lookup_path_forward_equivalence(monkeypatch):
    """DET_LOOKUP_PATH=tiled through DistributedEmbedding matches the
    default XLA forward on the 8-CPU mesh (interpret mode)."""
    from distributed_embeddings_tpu.layers.embedding import Embedding
    from distributed_embeddings_tpu.layers.dist_model_parallel import (
        DistributedEmbedding)
    from distributed_embeddings_tpu.parallel.mesh import create_mesh
    rng = np.random.RandomState(17)
    mesh = create_mesh(jax.devices()[:8])
    specs = [(60, 16, "sum"), (300, 8, "sum"), (40, 16, None)]

    def build():
        return DistributedEmbedding(
            [Embedding(vv, ww, combiner=cc) for vv, ww, cc in specs],
            mesh=mesh)

    weights = [rng.randn(vv, ww).astype(np.float32) for vv, ww, _ in specs]
    cats = [jnp.asarray(rng.randint(0, specs[i][0], (16, 3) if specs[i][2]
                                    else (16,))) for i in range(3)]
    emb = build()
    params = emb.set_weights(weights)
    want = jax.jit(emb)(params, list(cats))
    monkeypatch.setenv("DET_LOOKUP_PATH", "tiled")
    emb2 = build()
    params2 = emb2.set_weights(weights)
    got = jax.jit(emb2)(params2, list(cats))
    for a, b2 in zip(want, got):
        np.testing.assert_allclose(
            np.asarray(b2).reshape(np.asarray(a).shape), np.asarray(a),
            rtol=1e-5, atol=1e-5)


def test_tiled_step_hlo_scatter_free(monkeypatch):
    """The fully-tiled train step (tiled updates + tiled forward) must
    lower with NO stablehlo.scatter ops at all — removing the 100-280
    ns/row scatter lowering is the entire point of the round-4 kernels.
    (Lowered on CPU: the pallas interpreter emulates kernels with
    while/dynamic-update-slice, not scatter, so any scatter in the text is
    a real framework scatter.)"""
    import re
    from distributed_embeddings_tpu.layers.dist_model_parallel import (
        DistributedEmbedding)
    from distributed_embeddings_tpu.layers.embedding import Embedding
    from distributed_embeddings_tpu.training import make_sparse_train_step

    class _Tiny:
        def __init__(self, emb):
            self.embedding = emb

        def loss_fn(self, p, numerical, cats, labels, taps=None,
                    return_residuals=False):
            out = self.embedding(p["embedding"], list(cats), taps=taps,
                                 return_residuals=return_residuals)
            outs, res = out if return_residuals else (out, None)
            x = jnp.concatenate([o.reshape(o.shape[0], -1) for o in outs],
                                axis=1)
            loss = jnp.mean((jnp.sum(x, axis=1) - labels.reshape(-1)) ** 2)
            return (loss, res) if return_residuals else loss

    monkeypatch.setenv("DET_LOOKUP_PATH", "tiled")
    emb = DistributedEmbedding([Embedding(30_000_000, 8, combiner="sum")],
                               mesh=None)
    model = _Tiny(emb)
    init_fn, step_fn = make_sparse_train_step(model, "adagrad", lr=0.01,
                                              strategy="tiled")
    params = jax.eval_shape(
        lambda: {"embedding": emb.init(jax.random.PRNGKey(0))})
    state = jax.eval_shape(init_fn, params)
    num = jax.ShapeDtypeStruct((8, 1), jnp.float32)
    cats = [jax.ShapeDtypeStruct((8, 4), jnp.int32)]
    lab = jax.ShapeDtypeStruct((8,), jnp.float32)
    txt = jax.jit(step_fn).lower(params, state, num, cats, lab).as_text()
    scatters = re.findall(r'"stablehlo.scatter"', txt)
    assert not scatters, (
        f"tiled step still lowers {len(scatters)} scatter ops")


def test_tiled_bf16_table():
    v, w, n = 512, 16, 700
    table, ids, contribs = _mk(v, w, n, seed=9)
    table16 = table.astype(jnp.bfloat16)
    got = pt.tiled_sgd(table16, ids, contribs, 0.05, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = su.sparse_sgd(table16, su.SparseRowGrad(ids, contribs), 0.05)
    # XLA scatter rounds to bf16 per contribution; the kernel aggregates in
    # f32 and rounds once — heavily-duplicated rows accumulate visible
    # (one-sided, kernel-favoring) rounding differences
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=1e-1)


# ---- the dense aggregate's resident kernel (ISSUE 41) vs XLA's scatter-add
def _dense_stream(kind, rows, n, rng):
    """An id stream of n slots into a target of `rows` rows."""
    if kind == "feature_major":
        # runs of 512 slots, each inside one small table's rows of the
        # bucket, nearly every slot a duplicate: what a narrow bucket's
        # feature-major stream looks like
        sizes = np.array([10, 10, 1000, 10, 1000, 10000])
        starts = np.minimum(np.concatenate([[0], np.cumsum(sizes)[:-1]]),
                            rows - sizes.clip(max=rows))
        which = np.repeat(rng.randint(0, len(sizes), -(-n // 512)), 512)[:n]
        return starts[which] + rng.zipf(1.3, n) % np.minimum(sizes[which],
                                                             rows)
    if kind == "every_tile":
        # every chunk names the first and the last row and some between
        ids = rng.randint(0, rows, n)
        ids[::128], ids[1::128] = 0, rows - 1
        return ids
    assert kind == "out_of_range"
    ids = rng.randint(0, rows, n)
    ids[::5] = -1 - rng.randint(0, 9, len(ids[::5]))
    ids[3::7] = rows + rng.randint(0, 9, len(ids[3::7]))
    return ids


@pytest.mark.parametrize("width", [8, 16])
@pytest.mark.parametrize("kind,rows,n,tile", [
    ("feature_major", 12160, 4096, 1024),
    ("every_tile", 2048, 1536, 256),
    ("out_of_range", 1000, 1111, 256),      # a length that is no whole chunk
    ("feature_major", 12043, 2048, 512),    # rows that are no whole tile
    ("every_tile", 300, 100, 2048),         # one tile, one short chunk
])
def test_dense_sum_matches_the_scatter(kind, rows, n, tile, width):
    """`pallas_tiled.dense_sum`, the whole target resident, against the
    XLA lines it stands in for (`sparse_update._scatter_sum`): counts
    exactly, sums to an f32 sum's order, invalid ids dropped."""
    rng = np.random.RandomState(rows + n + width)
    ids = jnp.asarray(_dense_stream(kind, rows, n, rng).astype(np.int32))
    contribs = jnp.asarray(rng.randn(n, width).astype(np.float32))
    tile = min(tile, -(-rows // 128) * 128)
    kids, lo, hi, pairs = one_program(pt.dense_sum_walk, ids, rows=rows,
                                      chunk=256, tile=tile)
    g, counts = one_program(pt.dense_sum, kids, lo, hi, contribs, rows=rows,
                            tile=tile, interpret=True)
    g_want, counts_want = one_program(su._scatter_sum, ids, contribs,
                                      rows=rows)
    assert g.shape == (rows, width) and counts.shape == (rows,)
    np.testing.assert_array_equal(counts, counts_want)
    np.testing.assert_allclose(g, g_want, rtol=1e-5, atol=1e-5)
    # the walk pairs a chunk with the tiles between its least and its
    # greatest valid id, and with none where it has no valid id
    chunks = np.full(kids.size, -1)         # padded to whole grid steps
    chunks[:n] = np.asarray(ids)
    for c, l, h in zip(chunks.reshape(-1, 256), np.asarray(lo),
                       np.asarray(hi)):
        ok = c[(c >= 0) & (c < rows)]
        assert (l, h) == ((ok.min() // tile, ok.max() // tile)
                          if len(ok) else (0, -1))
    assert int(pairs) == int(np.sum(np.asarray(hi) - np.asarray(lo) + 1))


@pytest.mark.parametrize("width", [24, 32, 64, 96, 104])
def test_dense_sum_at_the_wider_lane_widths(width):
    """The kernel at the lane widths no cell has, with the tile that
    `dense_sum_blocks` cuts to a pair's operands (3 w + 8 rows a block):
    the scatter's counts and sums, over runs inside one small table and a
    run across the whole target."""
    rng = np.random.RandomState(width)
    rows, n = 3000, 2500
    chunk, tile = pt.dense_sum_blocks(rows, width)
    ids = np.concatenate([
        _dense_stream("feature_major", rows, 2048, rng),
        _dense_stream("out_of_range", rows, n - 2048, rng)]).astype(np.int32)
    contribs = jnp.asarray(rng.randn(n, width).astype(np.float32))
    kids, lo, hi, _ = one_program(pt.dense_sum_walk, jnp.asarray(ids),
                                  rows=rows, chunk=chunk, tile=tile)
    g, counts = one_program(pt.dense_sum, kids, lo, hi, contribs, rows=rows,
                            tile=tile, interpret=True)
    g_want, counts_want = one_program(su._scatter_sum, jnp.asarray(ids),
                                      contribs, rows=rows)
    np.testing.assert_array_equal(counts, counts_want)
    np.testing.assert_allclose(g, g_want, rtol=1e-5, atol=1e-5)


def test_dense_sum_blocks_fit_fast_memory():
    """The target with its count rows, and a pair's operands, each stay
    under their share of fast memory, or no stream reaches the kernel;
    Tiny V3's width-8 bucket fits, and the tile is cut where the width
    makes a pair's operands grow."""
    chunk, tile = pt.dense_sum_blocks(60160, 8)
    assert (chunk, tile) == (1024, 1024)
    assert -(-60160 // tile) * 16 * tile * 4 <= pt._DENSE_SUM_BYTES_MAX
    assert pt.dense_sum_blocks(100, 8) == (chunk, 128)
    assert pt.dense_sum_blocks(2_000_000, 8) is None
    assert pt.dense_sum_blocks(60160, 64) is None
    tiles = {w: pt.dense_sum_blocks(9_000, w) for w in range(8, 128, 8)}
    assert [tiles[w] and tiles[w][1] for w in (8, 16, 24, 32, 64, 96, 104)] \
        == [1024, 1024, 512, 512, 256, 128, 128]
    assert tiles[112] is None and tiles[120] is None
    for w, blocks in tiles.items():
        if blocks:
            assert pt._dense_pair_bytes(w, blocks[1]) \
                <= pt._DENSE_PAIR_BYTES_MAX < pt._dense_pair_bytes(
                    w, 2 * blocks[1]) or blocks[1] == 1024
