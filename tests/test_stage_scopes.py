"""Stage scopes inside the jitted train step, its name and its handle
(ISSUE 25, `obs/stages.py`).

What is held: the compiled steps of a Tiny-like model (Adagrad, the sort
path), a DLRM (sgd under a schedule) and a two-device mesh step carry every
stage their path runs in their operations' ``op_name``; no operation with a
path of the jitted program lies outside a stage (no exception is needed);
gathers and scatter-adds sit in the stage that asked for them; the step is
named, and its handle gives the compiled program of the very function a
call dispatches to; and the scopes change no number: a step traced with the
scopes taken out (the parent's program) returns the same bits.

No existing test holds the step to the parent's recorded values. The
bit-exact parities that exist compare two paths of one tree
(`test_sort_folding.py::test_fold_parity_adagrad`, folded against unfolded;
`test_schedule.py`, lookahead against the monolithic step), and
`test_sparse_train.py::test_sparse_train_basic` holds the step to plain
optax within 5e-5: `test_scopes_change_no_bit` below is the one that pins
scoped against unscoped.
"""

import collections
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import config as jax_config
from jax.sharding import NamedSharding

from distributed_embeddings_tpu import training
from distributed_embeddings_tpu.layers import dist_model_parallel
from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.models.dlrm import DLRM, make_lr_schedule
from distributed_embeddings_tpu.models.synthetic import (
    EmbeddingConfig, ModelConfig, SyntheticModel, expand_embedding_configs)
from distributed_embeddings_tpu.obs import stages
from distributed_embeddings_tpu.parallel.mesh import create_mesh
from distributed_embeddings_tpu.training import (make_sparse_train_step,
                                                 make_train_step)

BATCH = 32
TINY = ModelConfig(
    "tiny-like",
    [EmbeddingConfig(2, [1, 10], 500, 8, True),
     EmbeddingConfig(3, [1], 300, 16, False)],
    [32, 16], 5, None)
DLRM_ROWS = [400, 50, 300, 7]
EVERY = set(stages.STAGES)


def _tiny(mesh=None):
    model = SyntheticModel(TINY, mesh=mesh, distributed=True,
                           strategy="memory_balanced")
    tables, table_map, hotness = expand_embedding_configs(TINY)
    # `sort`: what `auto` picks for tables of the benchmark's size
    step = make_sparse_train_step(model, "adagrad", lr=0.01, strategy="sort")
    shapes = [(BATCH, h) for h in hotness]
    rows = [tables[t][0] for t in table_map]
    return model, step, shapes, rows, TINY.num_numerical_features


def _dlrm():
    model = DLRM(table_sizes=DLRM_ROWS, embedding_dim=16,
                 bottom_mlp_dims=[32, 16], top_mlp_dims=[32, 1],
                 num_numerical_features=13, mesh=None,
                 dist_strategy="memory_balanced")
    step = make_sparse_train_step(
        model, "sgd", lr=make_lr_schedule(24.0, 10, 20, 30))
    return model, step, [(BATCH,)] * len(DLRM_ROWS), DLRM_ROWS, 13


def _batch(shapes, rows, num_numerical, seed=0):
    rng = np.random.default_rng(seed)
    cats = [jnp.asarray(rng.integers(0, r, s), jnp.int32)
            for s, r in zip(shapes, rows)]
    return (jnp.asarray(rng.normal(size=(BATCH, num_numerical)), jnp.float32),
            cats, jnp.asarray(rng.integers(0, 2, (BATCH, 1)), jnp.float32))


PROGRAMS = {
    # name: (builder, the stages its path runs)
    "tiny": (_tiny, EVERY),
    # sgd `auto` scatters duplicates as they are: no dedup. XLA folds the
    # contributions' broadcast into the scatter, which is `apply`'s
    "dlrm": (_dlrm, EVERY - {"dedup", "contrib"}),
    "mesh": (lambda: _tiny(create_mesh(jax.devices()[:2])), EVERY),
}
_TEXTS = {}


def _compiled_text(name):
    """The program's step, lowered through its handle from abstract
    arguments and compiled: (text, compiled, step_fn)."""
    if name not in _TEXTS:
        model, (init_fn, step_fn), shapes, rows, nn = PROGRAMS[name][0]()
        mesh = model.embedding.mesh
        with mesh or contextlib.nullcontext():
            params = model.init(jax.random.PRNGKey(0))
            opt_state = init_fn(params)
            # what the mesh placed keeps its place; the rest is free
            args = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=x.sharding if isinstance(
                        x.sharding, NamedSharding) else None),
                (params, opt_state) + _batch(shapes, rows, nn))
            compiled = step_fn.lower(*args).compile()
        _TEXTS[name] = (compiled.as_text(), compiled, step_fn)
    return _TEXTS[name]


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def _stage(op_name):
    found = re.findall(r"det\.([a-z_]+)", op_name)
    return found[-1] if found else None


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_compiled_step_holds_the_stages_its_path_runs(name):
    text, _, _ = _compiled_text(name)
    held = {_stage(n) for n in _op_names(text)} - {None}
    assert held <= EVERY
    assert held >= PROGRAMS[name][1], sorted(PROGRAMS[name][1] - held)
    if name == "dlrm":
        assert "dedup" not in held


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_every_operation_with_a_path_lies_in_a_stage(name):
    """An `op_name` that names the jitted program (`jit(det_train_step)/..`)
    is under some `det.*` scope. What has no path is not the program's: the
    parameters (named for the argument), the bodies of reducers (a bare
    primitive) and the compiler's own broadcasts."""
    text, _, _ = _compiled_text(name)
    paths = [n for n in _op_names(text) if "/" in n]
    assert len(paths) > 100
    assert [n for n in paths if _stage(n) is None] == []
    # a path is the program's from its root, or relative inside a called
    # computation (a sort's comparator: `det.dedup/sort`)
    rooted = [n for n in paths if n.startswith("jit(")]
    assert len(rooted) > 100
    assert all(n.startswith(f"jit({stages.STEP_NAME})/") for n in rooted)


def test_gathers_and_scatter_adds_sit_in_the_stage_that_asked():
    text, _, _ = _compiled_text("tiny")
    names = _op_names(text)
    lookups = [n for n in names if n.endswith("/gather")
               and _stage(n) == "lookup"]
    assert lookups and not any("transpose(" in n for n in lookups)
    # the update's re-read of the accumulator is a gather too, and apply's
    assert any(n.endswith("/gather") and _stage(n) == "apply" for n in names)
    # and dedup's gather of the contributions by the sort's permutation
    assert any(n.endswith("/gather") and _stage(n) == "dedup" for n in names)
    # the scatter instructions themselves, by primitive and stage: per
    # bucket _row_scatter_add's two (accumulator, table), which are apply's.
    # dedup_sum scatters nothing: it sums sorted runs with a scan
    scatters = collections.Counter(
        (n.rsplit("/", 1)[1], _stage(n)) for n in re.findall(
            r'= \S+ scatter\(.*?op_name="([^"]*)"', text))
    assert scatters["scatter-add", "apply"] >= 4
    assert set(scatters) == {("scatter-add", "apply")}
    # the sorts are dedup's, in dedup_sum or folded into the forward
    sorts = [n for n in names if n.endswith("/sort")]
    assert sorts and {_stage(n) for n in sorts} == {"dedup"}


def test_backward_of_the_activation_exchange_is_under_transpose():
    text, _, _ = _compiled_text("mesh")
    acts = [n for n in _op_names(text) if _stage(n) == "acts"]
    assert any("transpose(" in n for n in acts)
    assert any("transpose(" not in n for n in acts)


def test_an_unknown_stage_is_refused():
    with pytest.raises(ValueError, match="nope"):
        stages.stage("nope")
    with pytest.raises(ValueError, match="nope"):
        stages.staged("nope")
    assert all(re.fullmatch(r"[a-z_]+", s) for s in stages.STAGES)
    # not the name of a primitive a trace reader classes operations by
    assert not EVERY & {"gather", "scatter", "sort", "all_to_all", "psum"}


def test_staged_opens_a_new_scope_per_call():
    """A scope object keeps what it restores on itself: shared between
    calls it would not survive re-entry."""
    @stages.staged("lookup")
    def down(n, x):
        return down(n - 1, x) + 1.0 if n else x * 2.0

    text = jax.jit(down, static_argnums=0).lower(2, 1.0).compile().as_text()
    assert "det.lookup/det.lookup/det.lookup/mul" in text

    @jax.jit
    def after(x):
        return x + 1.0                   # the name stack is back where it was
    assert "det." not in after.lower(1.0).compile().as_text()


@pytest.mark.parametrize("name", ["tiny", "dlrm"])
def test_the_handle_gives_the_compiled_program_of_the_named_step(name):
    text, compiled, step_fn = _compiled_text(name)
    assert step_fn.name == stages.STEP_NAME == "det_train_step"
    assert text.startswith(f"HloModule jit_{stages.STEP_NAME},")
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes > 0
    assert memory.argument_size_in_bytes > 0
    # the handle's donation is the step's own: parameters and state alias
    assert memory.alias_size_in_bytes > 0
    assert "input_output_alias" in text.split("\n", 1)[0]


def test_wrapping_the_step_in_a_second_jit_still_works():
    """`benchmark/tools/describe_chip.py` does, unedited."""
    model, (init_fn, step_fn), shapes, rows, nn = _tiny()
    params = model.init(jax.random.PRNGKey(0))
    out = jax.jit(step_fn, donate_argnums=(0, 1))(
        params, init_fn(params), *_batch(shapes, rows, nn))
    assert np.isfinite(float(out[2]))


def test_the_offloaded_step_carries_the_handle_too():
    mesh = create_mesh(jax.devices()[:2])
    specs = [(4000, 16), (50, 16), (60, 16), (70, 16)]
    dist = dist_model_parallel.DistributedEmbedding(
        [Embedding(r, w, combiner="sum") for r, w in specs], mesh=mesh,
        gpu_embedding_size=4000 * 16 + 1)
    assert dist._offload_enabled
    assert any(b.offload for b in dist.plan.tp_buckets)

    class Model:
        embedding = dist

    _, step_fn = make_sparse_train_step(Model(), "sgd", lr=0.1)
    assert step_fn.name == stages.STEP_NAME and callable(step_fn.lower)


def test_make_train_step_is_named_and_scoped():
    import optax

    def loss_fn(params, x):
        return jnp.sum((x @ params["w"]) ** 2)

    step = make_train_step(loss_fn, optax.sgd(0.1), donate=False)
    params = {"w": jnp.ones((4, 2))}
    text = step.lower(params, optax.sgd(0.1).init(params),
                      jnp.ones((3, 4))).compile().as_text()
    assert text.startswith(f"HloModule jit_{stages.STEP_NAME},")
    assert {_stage(n) for n in _op_names(text) if "/" in n} == {
        "model", "dense_opt"}


@pytest.mark.parametrize("name", ["tiny", "dlrm"])
def test_scopes_change_no_bit(name, monkeypatch):
    """The same steps from the same state, traced once with the scopes and
    once with every scope taken out, which is the parent's program."""
    def run():
        model, (init_fn, step_fn), shapes, rows, nn = PROGRAMS[name][0]()
        params = model.init(jax.random.PRNGKey(1))
        state = init_fn(params)
        losses = []
        for seed in (3, 4):
            params, state, loss = step_fn(params, state,
                                          *_batch(shapes, rows, nn, seed))
            losses.append(np.asarray(loss))
        return losses, jax.tree.map(np.asarray, (params, state)), step_fn

    scoped_losses, scoped, _ = run()
    none = lambda name: contextlib.nullcontext()   # noqa: E731
    for module in (stages, training, dist_model_parallel):
        monkeypatch.setattr(module, "stage", none)
    # the compile cache's key leaves names out, so the bare step would be
    # kept under the scoped step's key where it alone compiled long enough
    # to be written, and the tests above would load a step without a stage
    with jax_config.persistent_cache_min_compile_time_secs(float("inf")):
        bare_losses, bare, bare_step = run()
    batch = _batch(*PROGRAMS[name][0]()[2:])
    assert "det." not in bare_step.lower(
        *jax.tree.map(jnp.asarray, bare), *batch).as_text()
    np.testing.assert_array_equal(scoped_losses, bare_losses)
    jax.tree.map(np.testing.assert_array_equal, scoped, bare)
