"""Plain references: the two models' forward, loss, gradients and optimizer
rules in straightforward ``jax.numpy``.

Nothing here imports ``distributed_embeddings_tpu``. Every function is f32.
The callers run the steps under the configuration's own
``jax.default_matmul_precision`` (on a TPU an f32 matmul otherwise runs in
bf16 passes, and the system's does), and every step's loss and embedding
gradients are evaluated once more under ``"highest"`` on the same state:
the distance between the two is what the configuration's precision
explains, measured and not assumed. There is no kernel,
no fusion of tables into buckets, no exchange, no dedup: a table is one
``[rows, width]`` array, a lookup is ``jnp.take``, a multi-hot input is
summed, and the gradient of a row that several ids hit is written out as
the sum over every one of them.

Published descriptions followed:

* Synthetic models (NVIDIA-Merlin/distributed-embeddings,
  ``examples/benchmarks/synthetic_models/synthetic_models.py``): embeddings
  with the ``sum`` combiner, concatenated in input order with the numerical
  features appended, an MLP with ReLU between layers, one logit, sigmoid
  binary cross-entropy averaged over the global batch.
* DLRM (Naumov et al., arXiv:1906.00091, as MLPerf and the reference's
  ``examples/dlrm`` run it): bottom MLP with ReLU after every layer, the
  pairwise dot products of the bottom output and the 26 embeddings (strictly
  lower triangle, row-major) concatenated in front of the bottom output, top
  MLP with ReLU between layers, one logit, the same loss.

Optimizer rules, as the repo applies them (``training._sparse_optimizer_setup``,
``ops/sparse_update``; optax for the dense part):

* sgd:      ``p -= lr(step) * g``
* adagrad:  ``acc += g**2; p -= lr * g * rsqrt(acc + eps)`` with
  ``acc0 = initial_accumulator_value`` (0.1) and ``eps = 1e-7``; for an
  embedding row ``g`` is the row's summed gradient.
"""

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


def synthetic_logits(dense, embs, numerical):
    x = jnp.concatenate(list(embs) + [numerical], axis=1)
    return mlp(dense["mlp"], x)[:, 0]


def dlrm_logits(dense, embs, numerical):
    bottom = mlp(dense["bottom"], numerical, final_activation=True)
    feats = jnp.stack([bottom] + list(embs), axis=1)          # [B, F+1, d]
    gram = jnp.einsum("bfd,bgd->bfg", feats, feats)
    rows, cols = np.tril_indices(feats.shape[1], k=-1)
    pairwise = gram[:, rows, cols]
    return mlp(dense["top"], jnp.concatenate([pairwise, bottom], axis=1))[:, 0]


LOGITS = {"synthetic": synthetic_logits, "dlrm": dlrm_logits}


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


def init_state(optimizer, tree):
    """The optimizer's initial state for `tree`, as host arrays."""
    if optimizer["kind"] == "sgd":
        return None
    acc0 = optimizer["initial_accumulator_value"]
    return jax.tree.map(lambda p: np.full(np.shape(p), acc0, np.float32), tree)


def apply_rule(optimizer, lr, params, grads, state):
    """One optimizer step over a pytree -> (params, state, step size): the
    last is d(change)/d(gradient), by which an error in a gradient sum
    shows in the parameter."""
    if optimizer["kind"] == "sgd":
        new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return new, None, jax.tree.map(lambda p: jnp.full_like(p, lr), params)
    eps = optimizer["eps"]
    state = jax.tree.map(lambda a, g: a + g * g, state, grads)
    size = jax.tree.map(lambda a: lr * jax.lax.rsqrt(a + eps), state)
    new = jax.tree.map(lambda p, g, s: p - s * g, params, grads, size)
    return new, state, size


def train_steps(logits_fn, optimizer, tables, table_map, dense, batches,
                sparse_device=None):
    """Run one step per batch from the given initial host arrays, under the
    matmul precision the caller has set.

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
    def dense_step(embs, dense, d_state, lr, numerical, labels):
        def loss_fn(embs, dense):
            return bce_with_logits(logits_fn(dense, embs, numerical), labels)

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
        dense, d_state, _ = apply_rule(optimizer, lr, dense, g_dense, d_state)
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
        new_tables, new_state, size = apply_rule(optimizer, lr, tables,
                                                 g_tables, t_state)
        # the sparse update never visits a row that no id hit
        keep = lambda new, old, h: jnp.where(h[:, None], new, old)  # noqa: E731
        new_tables = [keep(n, o, h) for n, o, h in zip(new_tables, tables, hit)]
        if new_state is not None:
            new_state = [keep(n, o, h)
                         for n, o, h in zip(new_state, t_state, hit)]
        change = [c - s * g for c, s, g in zip(change, size, g_tables)]
        moved = [m + s * a for m, s, a in zip(moved, size, g_abs)]
        return new_tables, new_state, change, moved

    # state starts as host arrays and is placed, never computed: an eager
    # `zeros_like` per table is a program of its own to compile
    zeros = [np.zeros(np.shape(t), np.float32) for t in tables]
    t_state, change, moved = jax.device_put(
        (init_state(optimizer, tables), zeros, zeros), sparse_device)
    d_state = jax.device_put(init_state(optimizer, dense), dense_device)
    tables = jax.device_put(list(tables), sparse_device)
    dense = jax.device_put(dense, dense_device)
    losses, losses_high, shares, first_embs = [], [], [], None
    for i, (numerical, cats, labels) in enumerate(batches):
        lr = np.float32(learning_rate(optimizer, i))
        cats = jax.device_put(list(cats), sparse_device)
        embs = lookup(tables, cats)
        loss, loss_high, share, g_embs, dense, d_state = dense_step(
            jax.device_put(embs, dense_device), dense, d_state, lr,
            jax.device_put(numerical, dense_device),
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
