"""What every plain reference shares: the embedding stage, the optimizer
rules and the training steps, in straightforward ``jax.numpy``.

A configuration's model is a module of its own, ``benchmark/references/
<name>.py`` (`Built.reference` names it): it exposes ``loss(dense, embs,
inputs, labels) -> f32 scalar``, the model's forward pass from the embedding
stage's outputs on and its loss. ``dense`` is the reference's dense tree
(`Built.dense_params`), ``embs`` one ``[B, width]`` array per input, and
``inputs`` and ``labels`` are the first and the last array of a batch
``(inputs, cats, labels)`` as the cell's generator made them and the
program's ``loss_fn`` takes them: numerical features and ``[B, 1]`` f32
clicks for a click model, document boundaries and ``[T]`` int32 next-token
ids for a language model. Nothing here reads, reshapes or casts them.

Nothing here or there imports ``distributed_embeddings_tpu``. Every function
is f32. The callers run the steps under the configuration's own
``jax.default_matmul_precision`` (on a TPU an f32 matmul otherwise runs in
bf16 passes, and the system's does), and every step's loss and embedding
gradients are evaluated once more under ``"highest"`` on the same state:
the distance between the two is what the configuration's precision
explains, measured and not assumed. There is no kernel,
no fusion of tables into buckets, no exchange, no dedup: a table is one
``[rows, width]`` array, a lookup is ``jnp.take``, a multi-hot input is
summed, and the gradient of a row that several ids hit is written out as
the sum over every one of them.

Optimizer rules, as the repo applies them (``training._sparse_optimizer_setup``,
``ops/sparse_update``; optax for the dense part):

* sgd:      ``p -= lr(step) * g``
* adagrad:  ``acc += g**2; p -= lr * g * rsqrt(acc + eps)`` with
  ``acc0 = initial_accumulator_value`` (0.1) and ``eps = 1e-7``; for an
  embedding row ``g`` is the row's summed gradient.
* adam:     ``t += 1; mu = b1 * mu + (1 - b1) * g; nu = b2 * nu + (1 - b2) *
  g**2; p -= lr * (mu / (1 - b1**t)) / (sqrt(nu / (1 - b2**t)) + eps)`` with
  ``mu0 = nu0 = 0``, ``t0 = 0`` and the configuration's ``b1``, ``b2``,
  ``eps`` (optax's and ``sparse_adam``'s defaults: 0.9, 0.999, 1e-8). The
  dense tree takes it whole (optax). A table takes it lazily and row-wise
  (``ops/sparse_update.sparse_adam``): ``t`` is one count for the table,
  advances every step and corrects the bias of every row alike; a row's
  moments decay, take ``g`` (the row's summed gradient) and move the row
  only in a step in which an id hit it, and stay as they are otherwise.
"""

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def embed(tables, table_map, cats):
    """One ``[B, width]`` array per input: rows taken and summed over the
    input's hotness. ``cats[i]`` is ``[B, hotness]`` int32."""
    return [jnp.take(tables[t], ids, axis=0).sum(axis=1)
            for t, ids in zip(table_map, cats)]


def mlp(layers, x, final_activation=False):
    for i, layer in enumerate(layers):
        x = x @ layer["w"] + layer["b"]
        if i < len(layers) - 1 or final_activation:
            x = jnp.maximum(x, 0.0)
    return x


def bce_with_logits(logits, labels):
    labels = labels.reshape(-1)
    return jnp.mean(jnp.maximum(logits, 0.0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def learning_rate(optimizer, step):
    """The lr of `step` (0-based). A schedule is MLPerf DLRM's: linear
    warm-up from 0, constant, then polynomial decay to 0."""
    sched = optimizer.get("lr_schedule")
    if sched is None:
        return optimizer["lr"]
    warm, start, steps = (sched["warmup_steps"], sched["decay_start_step"],
                          sched["decay_steps"])
    if step < warm:
        factor = 1.0 - (warm - step) / warm
    elif step < start:
        factor = 1.0
    else:
        factor = min(max((start + steps - step) / steps, 0.0), 1.0) ** 2
    return sched["base_lr"] * factor


def init_state(optimizer, tree, sums_abs=False):
    """The optimizer's initial state for `tree`, as host arrays. `sums_abs`:
    `tree` is the tables, and `apply_rule` will be given the sums of the
    contributions' absolute values (adam keeps two moments of them)."""
    kind = optimizer["kind"]
    if kind == "sgd":
        return None

    def full(value):
        return jax.tree.map(lambda p: np.full(np.shape(p), value, np.float32),
                            tree)
    if kind == "adagrad":
        return full(optimizer["initial_accumulator_value"])
    if kind != "adam":
        raise ValueError(f"the reference has no rule for optimizer {kind!r} "
                         "(it has sgd, adagrad and adam)")
    state = {"count": np.zeros((), np.int32), "mu": full(0.0), "nu": full(0.0)}
    if sums_abs:
        state.update(mu_abs=full(0.0), nu_x=full(0.0))
    return state


class Applied(NamedTuple):
    """One optimizer step over a pytree. The step's change of a parameter is
    ``-size * step``; how far an error of a share `e` of every single
    contribution to a gradient (adam: of the largest sum of contributions in
    the parameter's row) could move it is ``e * size * step_abs``, to first
    order."""
    params: Any
    state: Any
    size: Any        # sgd: lr; adagrad: lr * rsqrt(acc + eps); adam:
    #                  lr / ((1 - b1**t) * (sqrt(nu / (1 - b2**t)) + eps))
    step: Any        # what `size` multiplies: the gradient; adam: `mu`
    step_abs: Any    # None without `grads_abs`


def _adam(optimizer, lr, params, grads, state, grads_abs):
    b1, b2, eps = optimizer["b1"], optimizer["b2"], optimizer["eps"]
    tmap = jax.tree.map
    count = state["count"] + 1
    c1 = 1.0 - b1 ** count.astype(jnp.float32)
    c2 = 1.0 - b2 ** count.astype(jnp.float32)
    mu = tmap(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = tmap(lambda v, g: b2 * v + (1 - b2) * (g * g), state["nu"], grads)
    root = tmap(lambda v: jnp.sqrt(v / c2), nu)
    size = tmap(lambda r: lr / (c1 * (r + eps)), root)
    new = tmap(lambda p, s, m: p - s * m, params, size, mu)
    state = dict(state, count=count, mu=mu, nu=nu)
    if grads_abs is None:
        return Applied(new, state, size, mu, None)
    # the same two moments of what the contributions could have summed to
    # had none cancelled: `mu_abs` of the sums of their absolute values,
    # `nu_x` of |gradient| times that sum (the derivative of g**2). An error
    # of a share e of every contribution moves `mu` by at most e * mu_abs
    # and `nu` by at most 2 e * nu_x, so the root by e * nu_x / (c2 * root)
    # and `size` by that over (root + eps) of itself. The sum is the largest
    # of the row: the matrix product that made a contribution rounds every
    # element of it by a share of the row's scale, not of the element's, and
    # adam's step, unlike the other two, carries that to an element whose
    # own gradient is next to nothing at lr's size
    grads_abs = tmap(lambda a: jnp.max(a, axis=-1, keepdims=True), grads_abs)
    mu_abs = tmap(lambda m, a: b1 * m + (1 - b1) * a, state["mu_abs"],
                  grads_abs)
    nu_x = tmap(lambda x, g, a: b2 * x + (1 - b2) * (jnp.abs(g) * a),
                state["nu_x"], grads, grads_abs)
    state.update(mu_abs=mu_abs, nu_x=nu_x)
    step_abs = tmap(
        lambda ma, m, x, r: ma + jnp.abs(m) * jnp.where(
            r > 0, x / (c2 * jnp.maximum(r, 1e-30) * (r + eps)), 0.0),
        mu_abs, mu, nu_x, root)
    return Applied(new, state, size, mu, step_abs)


def apply_rule(optimizer, lr, params, grads, state, grads_abs=None, hit=None):
    """One optimizer step over a pytree -> `Applied`.

    `grads_abs`, shaped like `grads`: per gradient the sum of its
    contributions' absolute values. `hit`: `params` is a list of tables,
    and per table these are the rows some id hit: the sparse update never
    visits another row, so its row and its state stay as they are (adam's
    count is the table's, and advances)."""
    kind, old = optimizer["kind"], state
    if kind == "sgd":
        new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        size = jax.tree.map(lambda p: jnp.full_like(p, lr), params)
        applied = Applied(new, None, size, grads, grads_abs)
    elif kind == "adagrad":
        eps = optimizer["eps"]
        state = jax.tree.map(lambda a, g: a + g * g, state, grads)
        size = jax.tree.map(lambda a: lr * jax.lax.rsqrt(a + eps), state)
        new = jax.tree.map(lambda p, g, s: p - s * g, params, grads, size)
        applied = Applied(new, state, size, grads, grads_abs)
    else:
        applied = _adam(optimizer, lr, params, grads, state, grads_abs)
    if hit is None:
        return applied

    def rows(new, old):       # lists of tables, or of arrays shaped like them
        return [jnp.where(h[:, None], n, o) for n, o, h in zip(new, old, hit)]

    new_state = applied.state
    if kind == "adagrad":
        new_state = rows(new_state, old)
    elif kind == "adam":
        new_state = {k: v if k == "count" else rows(v, old[k])
                     for k, v in new_state.items()}
        # a moment of earlier steps moves no row that no id hit in this one
        nothing = [jnp.zeros_like(p) for p in params]
        applied = applied._replace(
            step=rows(applied.step, nothing),
            step_abs=rows(applied.step_abs, nothing))
    return applied._replace(params=rows(applied.params, params),
                            state=new_state)


def train_steps(model_loss, optimizer, tables, table_map, dense, batches,
                sparse_device=None):
    """Run one step per batch ``(inputs, cats, labels)`` from the given
    initial host arrays, under the matmul precision the caller has set.
    `model_loss` is the ``loss(dense, embs, inputs, labels)`` of the
    configuration's module under ``benchmark/references/``.

    Returns (embedding outputs of the first batch; per step the loss and the
    loss of the same state under ``"highest"``; per table two arrays shaped
    like it: by how much the steps changed each row, summed as such and not
    through the stored row, whose rounding would hide a change of a few
    ulps, and the summed size of every single id's contribution to the row:
    what it would have moved by had no two contributions cancelled; and per
    table the largest share, over the steps, by which its inputs' gradients
    under ``"highest"`` differ from those the steps used). `tables` may hold
    only the rows the batches touch, with the ids renumbered to match: the
    arithmetic is the same.

    The takes, the duplicate sums and the tables' optimizer run on
    `sparse_device` (default: where the dense model runs). A plain
    scatter-add of millions of rows takes minutes on a TPU and seconds on
    its host's CPU; the matrix products stay on the default device, whose
    matmul precision is the one in question."""
    dense_device = jax.devices()[0]
    sparse_device = sparse_device or dense_device
    inputs_of = [[i for i, table in enumerate(table_map) if table == t]
                 for t in range(len(tables))]

    @jax.jit
    def lookup(tables, cats):
        return embed(tables, table_map, cats)

    @jax.jit
    def dense_step(embs, dense, d_state, lr, inputs, labels):
        def loss_fn(embs, dense):
            return model_loss(dense, embs, inputs, labels)

        loss, (g_embs, g_dense) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            embs, dense)
        with jax.default_matmul_precision("highest"):
            loss_high, g_high = jax.value_and_grad(loss_fn)(embs, dense)
        # per table: how far the precision moves its inputs' gradients
        off = [jnp.sum(jnp.abs(g - h)) for g, h in zip(g_embs, g_high)]
        size = [jnp.sum(jnp.abs(h)) for h in g_high]
        share = jnp.stack([sum(off[i] for i in inputs)
                           / jnp.maximum(sum(size[i] for i in inputs), 1e-30)
                           for inputs in inputs_of])
        dense, d_state = apply_rule(optimizer, lr, dense, g_dense, d_state)[:2]
        return loss, loss_high, share, g_embs, dense, d_state

    @jax.jit
    def sparse_step(tables, t_state, change, moved, lr, cats, g_embs):
        # a row's gradient: the sum, over every id that hit it, of the
        # gradient of that id's sample (the combiner is a plain sum). One
        # scatter-add per input carries three things side by side: the
        # gradient, its absolute value, and a one that counts the hit
        sums = [jnp.zeros((t.shape[0], 2 * t.shape[1] + 1), t.dtype)
                for t in tables]
        for t, ids, g in zip(table_map, cats, g_embs):
            per_id = jnp.repeat(g, ids.shape[1], axis=0)
            sums[t] = sums[t].at[ids.reshape(-1)].add(jnp.concatenate(
                [per_id, jnp.abs(per_id), jnp.ones_like(per_id[:, :1])],
                axis=1))
        widths = [t.shape[1] for t in tables]
        g_tables = [s[:, :w] for s, w in zip(sums, widths)]
        g_abs = [s[:, w:2 * w] for s, w in zip(sums, widths)]
        hit = [s[:, -1] > 0 for s in sums]
        # the sparse update never visits a row that no id hit
        new_tables, new_state, size, step, step_abs = apply_rule(
            optimizer, lr, tables, g_tables, t_state, g_abs, hit)
        change = [c - s * g for c, s, g in zip(change, size, step)]
        moved = [m + s * a for m, s, a in zip(moved, size, step_abs)]
        return new_tables, new_state, change, moved

    # state starts as host arrays and is placed, never computed: an eager
    # `zeros_like` per table is a program of its own to compile
    zeros = [np.zeros(np.shape(t), np.float32) for t in tables]
    t_state, change, moved = jax.device_put(
        (init_state(optimizer, tables, sums_abs=True), zeros, zeros),
        sparse_device)
    d_state = jax.device_put(init_state(optimizer, dense), dense_device)
    tables = jax.device_put(list(tables), sparse_device)
    dense = jax.device_put(dense, dense_device)
    losses, losses_high, shares, first_embs = [], [], [], None
    for i, (inputs, cats, labels) in enumerate(batches):
        lr = np.float32(learning_rate(optimizer, i))
        cats = jax.device_put(list(cats), sparse_device)
        embs = lookup(tables, cats)
        loss, loss_high, share, g_embs, dense, d_state = dense_step(
            jax.device_put(embs, dense_device), dense, d_state, lr,
            jax.device_put(inputs, dense_device),
            jax.device_put(labels, dense_device))
        tables, t_state, change, moved = sparse_step(
            tables, t_state, change, moved, lr, cats,
            jax.device_put(g_embs, sparse_device))
        if first_embs is None:
            first_embs = embs
        losses.append(loss)
        losses_high.append(loss_high)
        shares.append(share)
    share = np.max(np.stack([np.asarray(s) for s in shares]), axis=0)
    return first_embs, losses, losses_high, change, moved, share
