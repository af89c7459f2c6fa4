"""The comparison that decides ``correct``: the system's first steps against
the plain reference, during set-up. The reference is the configuration's
module ``benchmark/references/<Built.reference>.py``, whose ``loss(dense,
embs, inputs, labels)`` is the model's forward and loss, under the steps,
the embedding stage and the optimizer rules (sgd, adagrad, adam) of
``benchmark/reference.py``. A batch is ``(inputs, cats, labels)``: the check
reads the ids, and hands the first and the last array to the program's
``loss_fn`` and to the reference's ``loss`` as the cell's generator made
them.

The reference reads the same initial arrays as the system: the tables as
``DistributedEmbedding.get_weights`` exports them to the host (the library's
own export, not its lookup path), cut on the host to the rows the check's
batches touch, with the ids renumbered to match. The arithmetic is the
same, and a 24 GB table set never needs a second copy on a device. A cut
table is padded with zero rows to the most rows those batches could touch,
so the reference's programs have the same shapes under every seed and are
compiled once per cell.

The reference takes its steps once, at the configuration's own matmul
precision (the system's), and evaluates every step's loss and embedding
gradients a second time under ``highest`` on the same state. The distance
between the two is what the model's precision explains: measured on this
device, not assumed, and next to nothing on a CPU and where the
configuration says ``highest``.

Held, on the first `check_steps` steps (which are also the warm-up): two,
and three under adam, whose rule needs a hit, a miss and a hit to show:

(a) the embedding stage's outputs for batch 0 against take-and-sum, at
    rtol 1e-5 of the value plus 1e-6 of the input's largest value: f32
    summation order over at most a few tens of rows explains 1e-7; a row
    stored in bf16 is off by 4e-3 and a dropped id by a whole row;
(b) each step's loss, at 1e-5 relative (f32 summation order over the batch)
    plus four times that step's distance between the two precisions. The
    loss may sit that close to the reference's loss at either;
(c) probed rows after the steps against the reference's rule with the
    gradient summed over every duplicate. Per row, the change may differ by
    1e-4 of itself; by one half-ulp of the row for every id that hit it and
    one more (a scatter-add rounds the stored row once per duplicate, a
    deduplicated update once; the reference's change is summed beside its
    rows and not rounded into them); and by a share of what the row would
    have moved had no two contributions cancelled: 4e-5 (f32 summation
    order over up to 1e5 duplicates) plus four times the share by which the
    precision moves the gradients of the table's inputs. A duplicate lost
    from a row hit twice is half the change.
    That last term is an error in the gradient's sum, carried to the row by
    `size`, d change / d gradient. Under sgd and adagrad a step is linear in
    the summed gradient (adagrad's accumulator starts at 0.1, far above a
    squared gradient, and is held), `size` is the step size and "what the
    row would have moved by" is `size` times the sum of the contributions'
    absolute values, the largest of the row's elements: the matrix product
    that made a contribution rounds each element by a share of the row's
    scale. Adam's step is not linear: the change is ``-size * mu`` with
    ``size = lr / ((1 - b1**t) * (sqrt(nu / (1 - b2**t)) + eps))``, the
    gradient is in both moments, and on a row's first hit the change is
    ``-lr * g / (|g| + eps)`` times the count's correction: lr in size
    whatever the gradient's. So under adam `size` is what multiplies the
    first moment, the reference sums ``-size * mu`` as the row's change, and
    "what it would have moved by" is, **element by element**, the
    first-order bound of what an error of a share of the row's largest
    absolute sum does to that product: ``size * (mu_abs + |mu| * nu_x /
    ((1 - b2**t) * root * (root + eps)))``, with ``mu_abs`` and ``nu_x``
    the same two moments taken of that largest sum
    (``benchmark.reference._adam``): the first term is the error in ``mu``,
    the second the error in the root under it. A row is held by its worst
    element, each against its own tolerance. On a first hit that is ``2 *
    lr * (the row's largest sum) / |g|`` for an element: one whose own
    gradient is a ten-thousandth of the row's scale, or whose contributions all
    but cancel, has a sign that rounding decides and a tolerance as wide as
    its step, while the row's other elements are held to ~1e-4 of lr (one
    tolerance for the row, as under the other rules, would fail a sound run
    at such an element or hold no element tighter than that one; PERF.md
    section 6, PR 28, has the readings). A duplicate lost from a row flips the sign of every element in which the
    lost contribution outweighed the rest (2 lr off) and changes the ratio
    of two steps' gradients in the moments; where all contributions of a
    first hit agree in sign, adam itself moves the row alike with and
    without it, and no reading of rows can tell until the row's next hit.
    Moments decayed on a row that a step did not hit show at the row's next
    hit, a tenth of the earlier gradient's part of that step: three steps,
    hit, not hit, hit, which is why `check_steps` gives adam three;
(d) probed rows that no batch touched: bit-identical.

Rows are read back through the layer's own forward on one-hot probe inputs,
which (a) has just held to the reference.
"""

from typing import NamedTuple

import numpy as np

CHECK_STEPS = 2          # DLRM's schedule gives lr 0 on step 0: rows move on 1
ADAM_CHECK_STEPS = 3     # moments decayed on a row not hit show at its next hit
PROBE_ROWS = 256         # per probe-able table: half touched, half untouched


def check_steps(optimizer):
    """How many first steps the system is held to under this optimizer."""
    return ADAM_CHECK_STEPS if optimizer["kind"] == "adam" else CHECK_STEPS


class CheckFailed(Exception):
    """`summary`: what `compare` had read when it failed."""

    def __init__(self, message, summary=None):
        super().__init__(message)
        self.summary = summary or {}


class Probe(NamedTuple):
    """Rows of one table to read back through its one-hot input."""
    input: int
    ids: np.ndarray       # [PROBE_ROWS]
    kind: np.ndarray      # 1 touched by the batches, 2 by none, 0 padding
    hits: np.ndarray      # how many ids of the batches hit the row


def touched_rows(built, batches):
    """{table: (sorted ids the batches touch through any of its inputs,
    how often each)}."""
    by_table = {}
    for inp, t in enumerate(built.table_map):
        by_table.setdefault(t, []).append(inp)
    return {t: np.unique(np.concatenate(
        [cats[i].reshape(-1) for _, cats, _ in batches for i in inputs]),
        return_counts=True) for t, inputs in by_table.items()}


def _evenly(sorted_ids, n):
    """`n` of `sorted_ids`, evenly spaced."""
    at = np.linspace(0, len(sorted_ids) - 1, min(n, len(sorted_ids)))
    return sorted_ids[at.astype(np.int64)]


def select_probes(built, touched):
    """{table: Probe} for every probe-able table (one with a one-hot input):
    up to half of PROBE_ROWS touched rows (half of those hit more than once,
    where there are any) and as many that nothing touched."""
    probes = {}
    half = PROBE_ROWS // 2
    for t, (seen, counts) in touched.items():
        onehot = [i for i, table in enumerate(built.table_map)
                  if table == t and built.hotness[i] == 1]
        if not onehot:
            continue
        rows = built.tables[t][0]
        free = np.setdiff1d(np.arange(max(0, rows - 65536), rows), seen)[:half]
        hit_twice = _evenly(seen[counts > 1], half // 2)
        pick = np.concatenate([hit_twice, _evenly(
            np.setdiff1d(seen, hit_twice), half - len(hit_twice))])
        ids = np.full(PROBE_ROWS, seen[0], np.int32)
        kind = np.zeros(PROBE_ROWS, np.int8)
        hits = np.zeros(PROBE_ROWS, np.int64)
        ids[:len(pick)], kind[:len(pick)] = pick, 1
        hits[:len(pick)] = counts[np.searchsorted(seen, pick)]
        ids[len(pick):len(pick) + len(free)] = free
        kind[len(pick):len(pick) + len(free)] = 2
        probes[t] = Probe(onehot[0], ids, kind, hits)
    if not probes:
        raise CheckFailed("no table has a one-hot input to read rows through")
    return probes


def probe_cats(built, probes):
    cats = [np.zeros((PROBE_ROWS, h), np.int32) for h in built.hotness]
    for probe in probes.values():
        cats[probe.input][:, 0] = probe.ids
    return cats


def compact(built, weights, batches, touched):
    """Cut each exported table to the rows `batches` touch, padded with zero
    rows to the most they could touch: as many rows as they carry ids for
    the table, or the table if that is smaller.

    Returns (compact tables, batches with renumbered ids)."""
    ids_for = [0] * len(built.tables)
    for _, cats, _ in batches:
        for t, ids in zip(built.table_map, cats):
            ids_for[t] += ids.size
    tables = []
    for t, (rows, width) in enumerate(built.tables):
        kept = np.asarray(weights[t])[touched[t][0]]
        table = np.zeros((min(rows, ids_for[t]), width), np.float32)
        table[:len(kept)] = kept
        tables.append(table)
    renumbered = [(num, [np.searchsorted(touched[t][0], ids).astype(np.int32)
                         for t, ids in zip(built.table_map, cats)], lab)
                  for num, cats, lab in batches]
    return tables, renumbered


def reference_results(built, model_loss, weights, dense, batches, touched,
                      precision):
    """Everything the comparison needs from the reference, computed before
    the system takes a step: one step per batch at the configuration's
    matmul `precision`, results on the host. `model_loss` is the
    configuration's ``loss(dense, embs, inputs, labels)``, `dense` the
    reference's dense tree as host arrays."""
    import jax

    from benchmark import reference

    tables, renumbered = compact(built, weights, batches, touched)
    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:          # JAX was told of no CPU backend
        host = None
    with jax.default_matmul_precision(precision):
        embs, losses, losses_high, change, moved, share = (
            reference.train_steps(
                model_loss, built.optimizer, tables,
                built.table_map, dense, renumbered, sparse_device=host))
    return {"kept": {t: seen for t, (seen, _) in touched.items()},
            "before": tables,
            "embs": [np.asarray(e) for e in embs],
            "losses": [float(x) for x in losses],
            "losses_high": [float(x) for x in losses_high],
            "change": [np.asarray(c) for c in change],
            "moved": [np.asarray(m) for m in moved],
            "precision_share": [float(x) for x in share]}


class Check:
    """The check around the system's first steps: everything the reference
    and the probes need is taken before the first step (the step donates its
    state), the comparison is made after the last."""

    def __init__(self, built, params, host_batches, staged_cats0, precision,
                 phase=lambda name: None):
        """`params`: the initial state. `host_batches`: the batches the
        steps will run, in order, as the generator made them.
        `staged_cats0`: batch 0's ids as staged for the system.
        `phase(name)` is told when a part of the work ends."""
        import jax

        from benchmark.harness import spec

        self.built = built
        # a configuration whose reference is not there fails before the export
        model_loss = spec.plugin("references", built.reference).loss
        touched = touched_rows(built, host_batches)
        self.probes = select_probes(built, touched)
        weights = built.model.embedding.get_weights(params["embedding"])
        dense = jax.tree.map(np.asarray, built.dense_params(params))
        phase("export")
        self.ref = reference_results(built, model_loss, weights, dense,
                                     host_batches, touched, precision)
        del weights
        # the fullest chip when the reference has taken its steps beside the
        # system's arguments (None where the backend keeps no count)
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()]
        self.reference_peak_bytes = max(
            (b for b in peaks if b is not None), default=None)
        phase("reference")
        self._forward = jax.jit(
            lambda p, cats: built.model.embedding(p, cats))
        self.sys_embs = [np.asarray(e) for e in self._forward(
            params["embedding"], staged_cats0)]
        self._probe_in = [jax.numpy.asarray(c) for c in built.shape_ids(
            probe_cats(built, self.probes))]
        self.rows_before = self.read_rows(params)
        phase("check")

    def read_rows(self, params):
        """{table: the probed rows}, through the layer's forward."""
        outs = self._forward(params["embedding"], self._probe_in)
        return {t: np.asarray(outs[probe.input])
                for t, probe in self.probes.items()}

    def finish(self, params, sys_losses):
        """Compare; raises CheckFailed, or returns the summary."""
        return compare(self.built, self.ref, self.probes, self.sys_embs,
                       sys_losses, self.rows_before, self.read_rows(params))


def compare(built, ref, probes, sys_embs, sys_losses, rows_before, rows_after):
    """Raise CheckFailed on the first of (a)-(d) that does not hold, with
    what was read until then as its `summary`; return a summary of how close
    the system came otherwise. ``summary["compared"]`` holds every number
    compared beside its limit, ``{name: [number, limit]}``.

    `sys_embs`: the system's embedding outputs for batch 0. `rows_before` /
    `rows_after`: {table: [PROBE_ROWS, width]} read through the forward."""
    summary = {"compared": {}}
    try:
        _compare(summary, built, ref, probes, sys_embs, sys_losses,
                 rows_before, rows_after)
    except CheckFailed as e:
        e.summary = summary
        raise
    return summary


def _compare(summary, built, ref, probes, sys_embs, sys_losses, rows_before,
             rows_after):
    ref_embs, ref_change, ref_moved = ref["embs"], ref["change"], ref["moved"]
    compared = summary["compared"]

    worst = excess = 0.0
    for inp, (got, want) in enumerate(zip(sys_embs, ref_embs)):
        got = np.asarray(got, np.float32).reshape(want.shape)
        scale = float(np.max(np.abs(want))) or 1.0
        err = np.abs(got - want) - 1e-5 * np.abs(want)
        worst = max(worst, float(np.max(np.abs(got - want))) / scale)
        excess = max(excess, float(np.max(err)) / scale)
        compared["emb_err_beyond_rtol_over_largest"] = [excess, 1e-6]
        if np.max(err) > 1e-6 * scale:
            raise CheckFailed(
                f"(a) embedding output of input {inp} is off by "
                f"{float(np.max(np.abs(got - want))):.3e} (largest value "
                f"{scale:.3e}): beyond f32 summation order")
    summary["emb_max_err_over_largest_value"] = worst

    summary["loss"] = []
    for i, (got, want, high) in enumerate(zip(sys_losses, ref["losses"],
                                              ref["losses_high"])):
        band = abs(high - want)       # what the model's precision explains
        tol = 1e-5 * abs(want) + 4 * band
        summary["loss"].append({"step": i, "system": got, "reference": want,
                                "reference_at_highest": high,
                                "tolerance": tol})
        off = min(abs(got - want), abs(got - high))
        compared[f"loss{i}_off"] = [off, tol]
        if not off <= tol:
            raise CheckFailed(
                f"(b) loss of step {i} is {got!r}, the reference's {want!r} "
                f"({high!r} under `highest`; tolerance {tol:.3e}, of which "
                f"the model's matmul precision explains {4 * band:.3e})")

    moved = touched = untouched = 0
    worst_rel = 0.0
    compared["untouched_rows_changed"] = [0, 0]
    for t, (_, ids, kind, hits) in probes.items():
        before, after = rows_before[t], rows_after[t]
        same = kind == 2
        if not np.array_equal(before[same], after[same]):
            compared["untouched_rows_changed"] = [
                int(np.any(before[same] != after[same], axis=1).sum()), 0]
            raise CheckFailed(f"(d) rows of table {t} that no batch touched "
                              "changed")
        untouched += int(same.sum())
        hit = kind == 1
        at = np.searchsorted(ref["kept"][t], ids[hit])
        if not np.array_equal(ref["before"][t][at], before[hit]):
            raise CheckFailed(
                f"(c) table {t}: the forward reads other rows than "
                "get_weights exported")
        want = ref_change[t][at]
        could = ref_moved[t][at]                  # had nothing cancelled
        if built.optimizer["kind"] != "adam":     # the row's: see (c)
            could = could.max(axis=1, keepdims=True)
        got = after[hit] - before[hit]
        tol = ((1e-4 * np.abs(want).max(axis=1)
                + (hits[hit] + 1) * 2.0 ** -24
                * np.abs(after[hit]).max(axis=1))[:, None]
               + (4e-5 + 4 * ref["precision_share"][t]) * could)
        err = np.abs(got - want)
        tol = np.broadcast_to(tol, err.shape)
        rel = (err / tol).max(axis=1)             # a row by its worst element
        if len(rel):
            worst_rel = max(worst_rel, float(np.max(rel)))
        compared["row_err_over_tolerance"] = [worst_rel, 1.0]
        bad = np.flatnonzero(np.any(err > tol, axis=1))
        if len(bad):
            r = bad[0]
            e = int(np.argmax(err[r] / tol[r]))
            raise CheckFailed(
                f"(c) table {t} row {int(ids[hit][r])}: changed by "
                f"{got[r][:4]}..., the reference's rule gives "
                f"{want[r][:4]}... (off by {err[r][e]:.3e}, tolerance "
                f"{tol[r][e]:.3e}; {len(bad)} of {int(hit.sum())} probed "
                "rows)")
        touched += int(hit.sum())
        moved += int(np.any(got != 0, axis=1).sum())
    summary.update(probed_tables=len(probes), touched_rows=touched,
                   touched_rows_moved=moved, untouched_rows=untouched,
                   row_err_over_tolerance_max=worst_rel,
                   gradient_share_explained_by_precision=max(
                       ref["precision_share"][t] for t in probes))
