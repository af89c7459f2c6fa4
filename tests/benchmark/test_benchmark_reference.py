"""The comparison that decides `correct` must fail when the system is
wrong: rows rounded to bf16, a duplicate id's gradient lost, a row the
batches never touched moved. And it must pass the system as it is."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import synthetic
from benchmark.generators import power_law
from benchmark.harness import check

CONFIG = {
    "name": "scratch", "builder": "synthetic",
    "embedding_configs": [
        {"num_tables": 1, "nnz": [1, 4], "num_rows": 300, "width": 8,
         "shared": True},
        {"num_tables": 2, "nnz": [1], "num_rows": 2000, "width": 16,
         "shared": False}],
    "mlp_sizes": [32, 16], "num_numerical_features": 4,
    "interact_stride": None, "global_batch": 64,
    "optimizer": {"kind": "adagrad", "lr": 0.01,
                  "initial_accumulator_value": 0.1, "eps": 1e-07},
    "placement": "memory_balanced", "numerical_scale": 1.0,
    "matmul_precision": "default"}


@pytest.fixture(scope="module")
def stepped():
    """A small model, its check taken before two steps, and its state
    after them."""
    built = synthetic.build(CONFIG, None, False)
    host = power_law.generate(
        {"alpha": 1.05, "num_batches": 2},
        [(built.tables[t][0], h)
         for t, h in zip(built.table_map, built.hotness)],
        built.global_batch, built.num_numerical, built.numerical_scale, 5)
    params = built.model.init(jax.random.PRNGKey(5))
    batches = [(jnp.asarray(n), [jnp.asarray(c) for c in cats],
                jnp.asarray(lab)) for n, cats, lab in host]
    chk = check.Check(built, params, host, batches[0][1], "default")
    init_fn, step_fn = built.make_step()
    opt_state = init_fn(params)
    losses = []
    for numerical, cats, labels in batches:
        params, opt_state, loss = step_fn(params, opt_state, numerical, cats,
                                          labels)
        losses.append(float(loss))
    return chk, losses, chk.read_rows(params), host


def _compare(chk, losses, rows_after, sys_embs=None):
    return check.compare(chk.built, chk.ref, chk.probes,
                         chk.sys_embs if sys_embs is None else sys_embs,
                         losses, chk.rows_before, rows_after)


def test_the_system_as_it_is_passes(stepped):
    chk, losses, rows_after, _ = stepped
    summary = _compare(chk, losses, rows_after)
    assert summary["probed_tables"] == 3
    assert summary["touched_rows_moved"] > 0
    assert summary["row_err_over_tolerance_max"] <= 1.0


def test_rows_rounded_to_bf16_fail(stepped):
    chk, losses, rows_after, _ = stepped
    rounded = [np.asarray(jnp.asarray(e).astype(jnp.bfloat16)
                          .astype(jnp.float32)) for e in chk.sys_embs]
    with pytest.raises(check.CheckFailed, match=r"\(a\)"):
        _compare(chk, losses, rows_after, sys_embs=rounded)


def test_a_dropped_id_fails(stepped):
    """The multi-hot input's sum without one of its four rows."""
    chk, losses, rows_after, host = stepped
    embs = copy.deepcopy(chk.sys_embs)
    inp = chk.built.hotness.index(4)
    table = chk.built.table_map[inp]
    at = np.searchsorted(chk.ref["kept"][table], host[0][1][inp][0, 0])
    embs[inp][0] -= chk.ref["before"][table][at]
    with pytest.raises(check.CheckFailed, match=r"\(a\)"):
        _compare(chk, losses, rows_after, sys_embs=embs)


def test_a_lost_duplicate_fails(stepped):
    """A row hit by two ids that moves by half of what both gradients give:
    what losing one of two like contributions looks like."""
    chk, losses, rows_after, _ = stepped
    tampered = copy.deepcopy(rows_after)
    t, r = next((t, int(np.flatnonzero((kind == 1) & (hits == 2))[0]))
                for t, (_, _, kind, hits) in chk.probes.items()
                if np.any((kind == 1) & (hits == 2)))
    before = chk.rows_before[t][r]
    assert np.any(rows_after[t][r] != before)
    tampered[t][r] = before + 0.5 * (rows_after[t][r] - before)
    with pytest.raises(check.CheckFailed, match=r"\(c\)"):
        _compare(chk, losses, tampered)


def test_an_untouched_row_that_moves_fails(stepped):
    chk, losses, rows_after, _ = stepped
    tampered = copy.deepcopy(rows_after)
    t, (_, _, kind, _) = next(iter(chk.probes.items()))
    r = int(np.flatnonzero(kind == 2)[0])
    tampered[t][r, 0] = np.nextafter(tampered[t][r, 0], np.float32(1))
    with pytest.raises(check.CheckFailed, match=r"\(d\)"):
        _compare(chk, losses, tampered)


def test_a_loss_beyond_f32_rounding_fails(stepped):
    chk, losses, rows_after, _ = stepped
    with pytest.raises(check.CheckFailed, match=r"\(b\)"):
        _compare(chk, [losses[0] * (1 + 1e-4)] + losses[1:], rows_after)


def test_the_precision_band_widens_the_loss_tolerance(stepped):
    """A loss may sit as far from the reference's as four times the distance
    between the reference at the model's precision and under `highest`."""
    chk, losses, rows_after, _ = stepped
    off = [losses[0] * (1 + 1e-3)] + losses[1:]
    ref = dict(chk.ref, losses_high=[chk.ref["losses"][0] * (1 + 3e-4)]
               + chk.ref["losses_high"][1:])
    summary = check.compare(chk.built, ref, chk.probes, chk.sys_embs, off,
                            chk.rows_before, rows_after)
    assert summary["loss"][0]["tolerance"] == pytest.approx(
        1.21e-3 * losses[0], rel=1e-2)
    with pytest.raises(check.CheckFailed, match=r"\(b\)"):
        _compare(chk, off, rows_after)


def test_the_precision_share_widens_a_rows_tolerance(stepped):
    chk, losses, rows_after, _ = stepped
    tampered = copy.deepcopy(rows_after)
    t, r = next((t, int(np.flatnonzero(kind == 1)[0]))
                for t, (_, _, kind, _) in chk.probes.items())
    before = chk.rows_before[t][r]
    tampered[t][r] = before + 1.01 * (rows_after[t][r] - before)
    with pytest.raises(check.CheckFailed, match=r"\(c\)"):
        _compare(chk, losses, tampered)
    share = list(chk.ref["precision_share"])
    share[t] = 0.01
    check.compare(chk.built, dict(chk.ref, precision_share=share), chk.probes,
                  chk.sys_embs, losses, chk.rows_before, tampered)


def test_the_reference_has_one_shape_under_every_seed():
    """The cut tables are padded to what the batches could touch at most, so
    the reference's programs are compiled once per cell, not per seed."""
    built = synthetic.build(CONFIG, None, False)
    shapes = []
    for seed in (5, 6):
        host = power_law.generate(
            {"alpha": 1.05, "num_batches": 2},
            [(built.tables[t][0], h)
             for t, h in zip(built.table_map, built.hotness)],
            built.global_batch, built.num_numerical, built.numerical_scale,
            seed)
        touched = check.touched_rows(built, host)
        weights = [np.ones(shape, np.float32) for shape in built.tables]
        tables, renumbered = check.compact(built, weights, host, touched)
        shapes.append([t.shape for t in tables])
        for t, table in enumerate(tables):
            kept = len(touched[t][0])
            assert np.all(table[:kept] == 1) and np.all(table[kept:] == 0)
        assert all(ids.max() < len(tables[t]) for _, cats, _ in renumbered
                   for t, ids in zip(built.table_map, cats))
    # 300 rows hit through 1 + 4 ids a sample, 2,000 rows through 1
    assert shapes[0] == shapes[1] == [(300, 8), (128, 16), (128, 16)]
