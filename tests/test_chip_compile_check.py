"""The program that `glm47-flash.packed-4k`'s CHECK runs, compiled for a
DESCRIBED TPU v5e at the cell's real size: no chip attached, nothing runs.

It is what caps a token cell's size: the plain reference's `dense_step`
runs beside the program's parameters and table (a transcription of
`benchmark/reference.py::train_steps`' closure: the loss and gradient
twice, adam over the whole dense tree, nothing donated). The cell's step
is `tests/test_chip_compile_cells.py`'s; each is a whole program's compile,
and each has a file of its own so that the suite's workers, which take a
file each, compile them side by side.
"""

import jax
import jax.numpy as jnp
import numpy as np

from test_chip_compile import F32, I32, one_chip, topo  # noqa: F401
from test_chip_compile_cells import CHIP_GIB, glm47_cell, live_gib, placed


def test_compiles_for_described_v5e(one_chip, monkeypatch):
    """The check's program must fit a chip beside the program's
    parameters; 14.82 GiB ran and 15.83 died for `lfm2.packed-4k`
    (PERF.md section 6)."""
    from benchmark import reference
    from benchmark.harness import spec

    cell, built, params, positions, next_ids = glm47_cell(one_chip,
                                                          monkeypatch)
    model_loss = spec.plugin("references", built.reference).loss
    dense = built.dense_params(params)

    def dense_step(embs, dense, d_state, lr, inputs, labels):
        def loss_fn(embs, dense):
            return model_loss(dense, embs, inputs, labels)

        loss, (g_embs, g_dense) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(embs, dense)
        with jax.default_matmul_precision("highest"):
            loss_high, g_high = jax.value_and_grad(loss_fn)(embs, dense)
        share = (jnp.sum(jnp.abs(g_embs[0] - g_high[0]))
                 / jnp.maximum(jnp.sum(jnp.abs(g_high[0])), 1e-30))
        dense, d_state = reference.apply_rule(built.optimizer, lr, dense,
                                              g_dense, d_state)[:2]
        return loss, loss_high, share, g_embs, dense, d_state

    embs, count, lr = placed((
        [jax.ShapeDtypeStruct((next_ids.shape[0],
                               cell.config["hidden_size"]), F32)],
        jax.ShapeDtypeStruct((), I32), jax.ShapeDtypeStruct((), F32)),
        one_chip)
    d_state = {"count": count, "mu": dense, "nu": dense}
    check = jax.jit(dense_step).lower(embs, dense, d_state, lr, positions,
                                      next_ids).compile()
    held = 4 * sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert live_gib(check) + held / 2 ** 30 < CHIP_GIB - 0.5, (
        f"the check needs {live_gib(check):.2f} GiB beside the program's "
        f"{held / 2 ** 30:.2f}")
