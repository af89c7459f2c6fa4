"""The token cells' programs compiled for a DESCRIBED TPU v5e at their real
sizes: no chip attached, nothing runs.

`tests/test_chip_compile.py` holds the kernels and Tiny V3's step to the
chip's compiler; these cases hold what a token cell gives the chip to it:
the expert layers of `mellum2.packed-4k` and `lfm2.packed-4k`, and
`glm47-flash.packed-4k`'s step, each of which must fit the chip. They are
files of their own so that the suite's workers, which take a file each,
can run them beside the kernels' cases; the topology is described by the
same module-scoped fixtures, and only in the worker handed the file.
"""

import jax
import jax.numpy as jnp
import pytest

from test_chip_compile import F32, I32, one_chip, topo  # noqa: F401

CHIP_GIB = 15.75                # what one v5e chip's allocator hands out


def placed(tree, one_chip):
    """Each leaf's shape and type, on the described chip."""
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), tree)


def live_gib(compiled):
    """What a compiled program holds on the chip while it runs."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) / 2 ** 30


def _cell_experts(one_chip, monkeypatch, cell):
    """The expert layer at a cell's size (`mellum2.packed-4k`: 16,384
    tokens of width 2,304, 8 of 64 experts of width 896 held, top 8, a
    softmax router; `lfm2.packed-4k`: width 2,048, experts of 1,536, top 4,
    a sigmoid router with its selection bias), forward and gradient: the
    library's grouped-product kernels at this repo's tilings, and both
    branches of the bounded rows."""
    from distributed_embeddings_tpu.layers.experts import ExpertLayer

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer = {"mellum_experts": ExpertLayer(2304, 896, 64, range(8), 8),
             "lfm2_experts": ExpertLayer(2048, 1536, 64, range(8), 4,
                                         router="sigmoid")}[cell]

    params = placed(jax.eval_shape(layer.init, jax.random.PRNGKey(0)),
                    one_chip)
    x = jax.ShapeDtypeStruct((16384, layer.hidden), F32, sharding=one_chip)
    compiled = jax.jit(jax.grad(
        lambda p, x: jnp.sum(layer(p, x)), argnums=(0, 1))).lower(
            params, x).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2 * 9    # 3 products, 3 passes
    assert "conditional(" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 30


def glm47_cell(one_chip, monkeypatch):
    """`glm47-flash.packed-4k` at its real size, built as the
    benchmark builds it and steered to the chip's branches: the cell, the
    built model, and its parameters, positions and next ids on the chip."""
    from benchmark.harness import spec

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = spec.load_cell("glm47-flash.packed-4k")
    built = spec.plugin("builders", cell.config["builder"]).build(
        cell.config, None, False)
    tokens, length = built.global_batch, built.num_numerical
    params = placed(jax.eval_shape(built.model.init, jax.random.PRNGKey(0)),
                    one_chip)
    positions, next_ids = placed((
        jax.ShapeDtypeStruct((tokens // length, length), I32),
        jax.ShapeDtypeStruct((tokens,), I32)), one_chip)
    return cell, built, params, positions, next_ids


def _glm47_step_fit(one_chip, monkeypatch):
    """The cell's training step as the cell dispatches it must fit a chip
    with its window's batches; 14.82 GiB ran and 15.83 died for
    `lfm2.packed-4k` (PERF.md section 6). The program the check
    runs beside the step's parameters is `test_chip_compile_check.py`'s."""
    _, built, params, positions, next_ids = glm47_cell(one_chip, monkeypatch)
    init_fn, step_fn = built.make_step()
    step = step_fn.lower(params,
                         placed(jax.eval_shape(init_fn, params), one_chip),
                         positions, [next_ids], next_ids).compile()
    text = step.as_text()
    assert "det.latent" in text and "det.shared" in text
    assert text.count("tpu_custom_call") >= 4 * 9    # the experts' products
    assert live_gib(step) < CHIP_GIB - 2, "the step and its window's batches"


# the GLM step's compile takes 160-190 s on an 8-core host, and a file
# of few cases runs at the end of the suite's run, beside the check's
# compile (`test_chip_compile_check.py`, 180 s alone, 280 beside it): the
# step is the looser of the two fits, so it is the one in the `-m slow`
# tier, and `benchmark.tools.describe_chip --workload glm47-flash.packed-4k`
# compiles the same step for the described chip
@pytest.mark.parametrize(
    "kernel,width",
    [("mellum_experts", 2304), ("lfm2_experts", 2048),
     pytest.param("glm47_step", 2048, marks=pytest.mark.slow)],
    ids=lambda v: str(v))
def test_compiles_for_described_v5e(kernel, width, one_chip, monkeypatch):
    if kernel == "glm47_step":
        _glm47_step_fit(one_chip, monkeypatch)
    else:
        _cell_experts(one_chip, monkeypatch, kernel)
