"""The comparison that decides `correct` must fail when the system is
wrong: rows rounded to bf16, a duplicate id's gradient lost, a row the
batches never touched moved. And it must pass the system as it is: under
adagrad, under sgd with a schedule, and under adam."""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import dlrm, synthetic
from benchmark.generators import power_law
from benchmark.harness import check, spec

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
DLRM_CONFIG = {
    "name": "scratch-dlrm", "builder": "dlrm",
    "table_rows": [300, 2000, 50], "embedding_dim": 16,
    "bottom_mlp_dims": [32, 16], "top_mlp_dims": [32, 1],
    "num_numerical_features": 4, "global_batch": 64,
    "optimizer": {"kind": "sgd", "lr_schedule": {
        "base_lr": 0.5, "warmup_steps": 4, "decay_start_step": 48,
        "decay_steps": 24}},
    "placement": "memory_balanced", "numerical_scale": 1.0,
    "matmul_precision": "default"}
ADAM_CONFIG = dict(
    CONFIG, matmul_precision="highest",
    optimizer={"kind": "adam", "lr": 0.01, "b1": 0.9, "b2": 0.999,
               "eps": 1e-08})
SEED = 5


def _drive(built, batches, seed=SEED):
    """Fresh parameters from `seed` through one step per batch (a step
    donates its state) -> (parameters, losses)."""
    params = built.model.init(jax.random.PRNGKey(seed))
    init_fn, step_fn = built.make_step()
    opt_state = init_fn(params)
    losses = []
    for inputs, cats, labels in batches:
        params, opt_state, loss = step_fn(params, opt_state, inputs, cats,
                                          labels)
        losses.append(float(loss))
    return params, losses


def _stepped(builder, config, num_batches=2, seed=SEED):
    """A small model, its check taken before one step per batch, and its
    state after them."""
    built = builder.build(config, None, False)
    host = power_law.generate(
        {"alpha": 1.05, "num_batches": num_batches},
        [(built.tables[t][0], h)
         for t, h in zip(built.table_map, built.hotness)],
        built.global_batch, built.num_numerical, built.numerical_scale, seed)
    batches = [(jnp.asarray(n), [jnp.asarray(c) for c in built.shape_ids(cats)],
                jnp.asarray(lab)) for n, cats, lab in host]
    chk = check.Check(built, built.model.init(jax.random.PRNGKey(seed)), host,
                      batches[0][1], config["matmul_precision"])
    params, losses = _drive(built, batches, seed)
    return chk, losses, chk.read_rows(params), host, batches


@pytest.fixture(scope="module")
def stepped():
    return _stepped(synthetic, CONFIG)[:4]


@pytest.fixture(scope="module")
def stepped_adam():
    """Three steps, so that a row can be hit, left alone and hit again."""
    return _stepped(synthetic, ADAM_CONFIG, num_batches=3)


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


# ---- the references as modules: moved, not rewritten
# (float.hex() of what the parent's `LOGITS[...]` + `bce_with_logits` inside
# `train_steps` gave on these fixtures: my CPU run on the parent, PR 28)
RECORDED = {
    "synthetic": (synthetic, CONFIG, {
        "loss": [("0x1.89b6c00000000p-1", "0x1.89b6c00000000p-1",
                  "0x1.89b6c00000000p-1"),
                 ("0x1.6b9b380000000p-1", "0x1.6b9b380000000p-1",
                  "0x1.6b9b380000000p-1")],
        "emb_max_err_over_largest_value": "0x0.0p+0",
        "row_err_over_tolerance_max": "0x1.884aa46fb8e62p-3",
        "touched_rows": 298, "touched_rows_moved": 298}),
    "dlrm": (dlrm, DLRM_CONFIG, {
        "loss": [("0x1.ab23800000000p-1", "0x1.ab23800000000p-1",
                  "0x1.ab23820000000p-1"),
                 ("0x1.9e05ee0000000p-1", "0x1.9e05ee0000000p-1",
                  "0x1.9e05f00000000p-1")],
        "emb_max_err_over_largest_value": "0x0.0p+0",
        "row_err_over_tolerance_max": "0x1.b1b848ae34cc3p-2",
        "touched_rows": 182, "touched_rows_moved": 111}),
}
CANARY = "0x1.9608000000000p-1"     # the same run's arithmetic, see below


def _this_machine_rounds_as_the_recording_one():
    """The recorded numbers are bit patterns of one XLA:CPU build on one kind
    of processor (its matmul kernels follow the vector width). A product and
    a `tanh` that no file of the benchmark touches say whether this is such
    a machine."""
    x = np.linspace(-2, 2, 64 * 48, dtype=np.float32).reshape(64, 48)
    got = jnp.sum(jnp.tanh(jnp.asarray(x) @ jnp.asarray(x.T[:, :32])))
    return float(got).hex() == CANARY


@pytest.fixture(scope="module", params=sorted(RECORDED))
def moved(request):
    """-> (what the check reads today, what it read on the parent)."""
    builder, config, want = RECORDED[request.param]
    chk, losses, rows_after = _stepped(builder, config)[:3]
    summary = _compare(chk, losses, rows_after)
    got = {"loss": [(s["system"], s["reference"], s["reference_at_highest"])
                    for s in summary["loss"]],
           **{k: summary[k] for k in want if k != "loss"}}
    want = {"loss": [tuple(float.fromhex(x) for x in step)
                     for step in want["loss"]],
            **{k: float.fromhex(v) if isinstance(v, str) else v
               for k, v in want.items() if k != "loss"}}
    assert summary["compared"]["row_err_over_tolerance"] == [
        summary["row_err_over_tolerance_max"], 1.0]
    return got, want


def test_a_moved_reference_reads_the_parents_bits(moved):
    """To the bit, where the machine rounds as the recording one did; a
    machine that does not says so and skips (the next test holds there)."""
    if not _this_machine_rounds_as_the_recording_one():
        pytest.skip("the canary product rounds otherwise here than on the "
                    "machine the parent's summaries were recorded on")
    got, want = moved
    assert got == want


def test_a_moved_reference_reads_the_parents_numbers(moved):
    """On any machine: every number of the summary to 1e-6 (an embedding
    error of nought stays under f32 rounding), the counts of rows equal."""
    got, want = moved
    assert got["loss"] == pytest.approx(want["loss"], rel=1e-6)
    assert got["row_err_over_tolerance_max"] == pytest.approx(
        want["row_err_over_tolerance_max"], rel=1e-6)
    assert got["emb_max_err_over_largest_value"] == pytest.approx(
        want["emb_max_err_over_largest_value"], abs=2.0 ** -23)
    assert (got["touched_rows"], got["touched_rows_moved"]) == (
        want["touched_rows"], want["touched_rows_moved"])


def test_a_reference_that_names_no_module_names_the_missing_file():
    built = dataclasses.replace(synthetic.build(CONFIG, None, False),
                                reference="no_such_model")
    with pytest.raises(spec.SpecError,
                       match=r"benchmark/references/no_such_model\.py"):
        check.Check(built, None, [], None, "default")


def test_an_optimizer_without_a_rule_is_named():
    from benchmark import reference
    with pytest.raises(ValueError, match="lion"):
        reference.init_state({"kind": "lion"}, [np.zeros((2, 2), np.float32)])


# ---- adam: the dense tree whole, a table lazily and row-wise. Three steps,
# so that a row can be hit, left alone and hit again. PERF.md section 6
# (PR 28) has each number below over a dozen seeds

def test_adam_is_held_over_three_steps_whatever_the_configuration_says():
    assert check.check_steps(ADAM_CONFIG["optimizer"]) == 3
    assert check.check_steps(CONFIG["optimizer"]) == 2
    assert check.check_steps(DLRM_CONFIG["optimizer"]) == 2


def row_reading(chk, losses, rows):
    """-> (`row_err_over_tolerance`, the first of (a)-(d) to fail or None)."""
    try:
        summary, failed = _compare(chk, losses, rows), None
    except check.CheckFailed as e:
        summary, failed = e.summary, str(e)[:3]
    return summary["compared"].get("row_err_over_tolerance", [None])[0], failed


def test_the_system_under_adam_passes(stepped_adam):
    chk, losses, rows_after, host, _ = stepped_adam
    summary = _compare(chk, losses, rows_after)
    assert len(summary["loss"]) == 3 and summary["probed_tables"] == 3
    assert summary["touched_rows_moved"] > 0
    assert summary["row_err_over_tolerance_max"] <= 0.5


def test_under_adam_an_element_with_next_to_no_gradient_is_held_by_its_own():
    """Seed 9 has a probed row with an element whose gradient is 2e-8 beside
    eps 1e-8: adam moves it by lr's size all the same, and the program and
    the reference round it 1.2e-6 apart on a step of 3.6e-3, beyond a
    tolerance made from the row's other elements (1.05 of it). Held element
    by element the run reads what the other seeds read."""
    chk, losses, rows_after = _stepped(synthetic, ADAM_CONFIG, 3, seed=9)[:3]
    number, failed = row_reading(chk, losses, rows_after)
    assert failed is None and number <= 0.05


def first_only(real):
    """Of the contributions to one row in one step only the first counts."""
    def sparse_adam(table, mu, nu, count, grad, lr, **kw):
        order = jnp.argsort(grad.ids, stable=True)
        sid = grad.ids[order]
        first = jnp.zeros(sid.shape, bool).at[order].set(
            jnp.concatenate([jnp.ones(1, bool), sid[1:] != sid[:-1]]))
        kw["presorted"] = None
        return real(table, mu, nu, count, grad._replace(
            contribs=grad.contribs * first[:, None]), lr, **kw)
    return sparse_adam


def decay_everywhere(real):
    """Dense adam's moments on a lazily updated table: every row's moments
    decay in every step."""
    def sparse_adam(table, mu, nu, count, grad, lr, b1=0.9, b2=0.999, **kw):
        new, mu2, nu2, count = real(table, mu, nu, count, grad, lr, b1=b1,
                                    b2=b2, **kw)
        hit = jnp.zeros(table.shape[0], bool).at[grad.ids].set(
            True, mode="drop")[:, None]
        return (new, jnp.where(hit, mu2, b1 * mu),
                jnp.where(hit, nu2, b2 * nu), count)
    return sparse_adam


def rows_with_sparse_adam(wrap, stepped_adam, seed=SEED):
    """The system's rows after the same steps with `ops.sparse_update.
    sparse_adam` wrapped: a fault planted in the program."""
    from distributed_embeddings_tpu.ops import sparse_update

    chk, _, _, _, batches = stepped_adam
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sparse_update, "sparse_adam",
                      wrap(sparse_update.sparse_adam))
        params, _ = _drive(chk.built, batches, seed)
    return chk.read_rows(params)


def test_under_adam_a_lost_duplicate_fails(stepped_adam):
    """The forward and the loss are the sound run's; the rows are not."""
    chk, losses, rows_after, _, _ = stepped_adam
    tampered = rows_with_sparse_adam(first_only, stepped_adam)
    assert any(np.any(tampered[t] != rows_after[t]) for t in tampered)
    number, failed = row_reading(chk, losses, tampered)
    assert failed == "(c)" and number > 10


def hit_left_alone_hit_again(chk, host):
    seen = [{t: set(np.concatenate(
        [cats[i].reshape(-1) for i, table in enumerate(chk.built.table_map)
         if table == t]).tolist()) for t in chk.probes}
        for _, cats, _ in host]
    return [(t, r) for t, probe in chk.probes.items()
            for r in probe.ids[probe.kind == 1].tolist()
            if r in seen[0][t] and r not in seen[1][t] and r in seen[2][t]]


def test_under_adam_moments_decayed_on_an_untouched_row_fail(stepped_adam):
    """It shows on a row hit, left alone and hit again."""
    chk, losses, rows_after, host, _ = stepped_adam
    assert hit_left_alone_hit_again(chk, host), "the fixture has no such row"
    tampered = rows_with_sparse_adam(decay_everywhere, stepped_adam)
    number, failed = row_reading(chk, losses, tampered)
    assert failed == "(c)" and number > 10


def reference_in_bfloat16(chk, host, seed=SEED):
    """The control: the plain reference put in the system's place with its
    model computed one precision down, the dense tree, the embedding outputs
    and the inputs cast to bfloat16 in front of the forward and the loss
    -> (its losses, its rows at the probes after the steps)."""
    from benchmark import reference

    built = chk.built
    tables, renumbered = check.compact(
        built, ref_tables(chk), host, check.touched_rows(built, host))
    dense = jax.tree.map(np.asarray, built.dense_params(
        built.model.init(jax.random.PRNGKey(seed))))
    model_loss = spec.plugin("references", built.reference).loss

    def low(tree):
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16), tree)

    _, losses, _, change = reference.train_steps(
        lambda dense, embs, inputs, labels: model_loss(
            low(dense), low(embs), low(inputs), labels).astype(jnp.float32),
        built.optimizer, tables, built.table_map, dense, renumbered)[:4]
    rows = {}
    for t, probe in chk.probes.items():
        hit = probe.kind == 1
        rows[t] = chk.rows_before[t].copy()
        rows[t][hit] += np.asarray(change[t])[
            np.searchsorted(chk.ref["kept"][t], probe.ids[hit])]
    return [float(x) for x in losses], rows


def test_under_adam_the_reference_in_bfloat16_fails(stepped_adam):
    """The control comes out not correct: by its losses (b), and, handed
    the sound run's losses, by its rows alone (c): the row tolerance under
    adam lies below what one precision down does to a row."""
    chk, losses, rows_after, host, _ = stepped_adam
    low_losses, low_rows = reference_in_bfloat16(chk, host)
    assert row_reading(chk, low_losses, low_rows)[1] == "(b)"
    number, failed = row_reading(chk, losses, low_rows)
    assert failed == "(c)" and number > 3


def test_adams_row_tolerance_is_the_first_order_bound(stepped_adam):
    """What the reference calls `moved` under adam bounds, to first order,
    what an error of a share e of every contribution does to a row. Scale
    the loss, and with it every contribution, by 1, 1 + e and 1 - e in the
    three steps (alike in all three adam would not notice): no row's change
    differs by more than e * moved, and some row's by a tenth of that."""
    from benchmark import reference

    chk, _, _, host, _ = stepped_adam
    built, ref = chk.built, chk.ref
    tables, renumbered = check.compact(
        built, ref_tables(chk), host, check.touched_rows(built, host))
    dense = jax.tree.map(np.asarray, built.dense_params(
        built.model.init(jax.random.PRNGKey(SEED))))
    model_loss = spec.plugin("references", built.reference).loss
    e = 1e-3
    scaled = [((inputs, np.float32(1 + k * e)), cats, labels)
              for k, (inputs, cats, labels) in zip((0, 1, -1), renumbered)]
    with jax.default_matmul_precision("highest"):
        change = reference.train_steps(
            lambda dense, embs, inputs, labels: inputs[1] * model_loss(
                dense, embs, inputs[0], labels),
            built.optimizer, tables, built.table_map, dense, scaled)[3]
    closest = 0.0
    for t in range(len(tables)):
        off = np.abs(np.asarray(change[t]) - ref["change"][t])
        bound = e * ref["moved"][t] * 1.05 + 1e-9
        assert np.all(off <= bound), (t, float(np.max(off / bound)))
        closest = max(closest, float(np.max(off / bound)))
    assert closest > 0.1


def ref_tables(chk):
    """Full-size tables that hold the check's compact rows where the
    batches touch them (`compact` cuts them out again)."""
    full = [np.zeros(shape, np.float32) for shape in chk.built.tables]
    for t, kept in chk.ref["kept"].items():
        full[t][kept] = chk.ref["before"][t][:len(kept)]
    return full
