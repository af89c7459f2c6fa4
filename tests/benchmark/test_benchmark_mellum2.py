"""The cell `mellum2.packed-4k`'s own files: the plain reference against the
program's model at a small size (and a bfloat16 mutation of the model that
must fail the same comparison), the packed-documents generator, the
configuration against the numbers its source publishes, and the arithmetic
of its rooflines."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import mellum2 as builder
from benchmark.generators import packed_documents
from benchmark.harness import spec, stage_flops
from benchmark.readers import stage_ms, stage_roofline
from benchmark.references import mellum2 as reference
from product_rounding import with_rounded_products
from distributed_embeddings_tpu.models.mellum import Mellum

CONFIG = spec.load_json("benchmark/configs/mellum2-12b-a2.5b.json")
TRAFFIC = spec.load_json("benchmark/traffic/packed-4k.json")
# what the catalog's row gives under `config`, numbers and groups
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "sliding_window": 1024,
    "tie_word_embeddings": False, "vocab_size": 98304,
    "use_sliding_window": True,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}}
SMALL = {   # a test's spec: what `reference.published_spec` reads from file
    "head_dim": 8, "layer_types": ["sliding_attention", "full_attention"],
    "sliding_window": 12, "num_experts_per_tok": 4, "first_expert_held": 4,
    "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000},
        "full_attention": {"rope_type": "yarn", "rope_theta": 10000,
                           "factor": 4, "beta_fast": 4, "beta_slow": 1,
                           "original_max_position_embeddings": 16,
                           "attention_factor": 1.1386294361119891}}}


# ------------------------------------------------------- the configuration
def test_the_configuration_keeps_every_published_number():
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key + "_published"] == value, key
        elif key == "layer_types":
            assert CONFIG[key] == value * 7 and len(CONFIG[key]) == 28
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["mlp_layer_types"] == ["sparse"] * 28
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    # the cut: a whole period, an eighth of the experts and of the vocabulary
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (4, 8, 12288)
    share = CONFIG["deployment"]["chips_sharing_a_layer"]
    assert CONFIG["num_experts"] * share == CONFIG["num_experts_published"]
    assert CONFIG["vocab_size"] * share == CONFIG["vocab_size_published"]
    assert CONFIG["layer_types"][:4] == PUBLISHED["layer_types"]
    assert CONFIG["tokens_per_step"] == 4 * CONFIG["sequence_length"] == 16384
    assert set(CONFIG["rehearse"]) <= {
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "moe_intermediate_size", "vocab_size", "tokens_per_step",
        "sequence_length"}, "the plain reference reads the rest from the file"


def test_the_builder_counts_the_parameters_and_flops_the_issue_counts():
    built = builder.build(CONFIG, None, False)
    shapes = jax.eval_shape(built.model.init, jax.random.PRNGKey(0))
    dense = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(
        built.dense_params(shapes)))
    assert dense == 4 * 70_930_944 + 2_304 + 28_311_552 == 312_037_632
    assert built.tables == [(12288, 2304)] and built.hotness == [1]
    assert built.global_batch == 16384 and built.num_numerical == 4096
    # 3 x 2 x (4 x 27.57M + 28.31M) a token: attention, router and the
    # expected one expert a layer, and the head
    per_layer = 21_233_664 + 147_456 + 6_193_152
    assert built.mlp_flops_per_sample == 6 * (4 * per_layer + 28_311_552)
    assert stage_flops.expert_flops_per_step(CONFIG, 16384) == (
        6 * 4 * 6_193_152 * 16384)
    from distributed_embeddings_tpu.models import mellum
    assert (CONFIG["init_std"], CONFIG["table_init_std"]) == (
        mellum.INIT_STD, mellum.TABLE_STD)
    assert built.model.residual_std == pytest.approx(
        CONFIG["residual_init_std"], rel=1e-12) == pytest.approx(
            0.02 / 56 ** 0.5, rel=1e-12)


@pytest.mark.parametrize("took_ms, want", [(24.7234, 50.0), (0.0, None)])
def test_a_stage_roofline_is_least_time_over_stage_time(took_ms, want):
    cell = types.SimpleNamespace(config=CONFIG, chips=1)
    ctx = types.SimpleNamespace(
        chips=[object()], steps=1, cell=cell, device_kind="TPU v5 lite",
        built=types.SimpleNamespace(global_batch=16384), notes=[],
        stage_partition={("experts", False): took_ms * 0.25,
                         ("experts", True): took_ms * 0.75,
                         ("attn", False): 1.0})
    got = stage_roofline.read(ctx, {"scope": "experts",
                                    "flops": "expert_flops_per_step"})
    if want is None:
        assert got is None
        return
    # 2.435e12 flops over 197 TFLOP/s = 12.3617 ms
    assert got == pytest.approx(want, rel=1e-4)
    assert "12.3617 ms" in ctx.notes[0]
    assert stage_ms.read(ctx, {"scope": "attn", "pass": "any"}) == 1.0
    rehearsal = types.SimpleNamespace(chips=[], cell=cell)
    assert stage_roofline.read(rehearsal, {"scope": "experts",
                                           "flops": "x"}) is None


# ---------------------------------------------------------- the generator
def batches(seed, batch=4096, length=1024, rows=12288, **changed):
    return packed_documents.generate(
        dict(TRAFFIC, **changed), [(rows, 1)], batch, length, 0.0, seed)


def test_documents_are_packed_end_to_end_and_ids_stay_in_the_slice():
    made = batches(2147483659)
    assert len(made) == TRAFFIC["num_batches"] == 4
    lengths = []
    for positions, (ids,), next_ids in made:
        assert positions.shape == (4, 1024) and positions.dtype == np.int32
        assert ids.shape == (4096, 1) and next_ids.shape == (4096,)
        assert ids.dtype == next_ids.dtype == np.int32
        assert 0 <= ids.min() and ids.max() < 12288
        assert 0 <= next_ids.min() and next_ids.max() < 12288
        # a token's successor is the next token of its sequence
        np.testing.assert_array_equal(
            next_ids.reshape(4, 1024)[:, :-1], ids.reshape(4, 1024)[:, 1:])
        for row in positions:
            assert row[0] == 0
            starts = np.flatnonzero(row == 0)
            # positions count up inside a document and restart with it
            steps = np.diff(row)
            assert np.all((steps == 1) | (row[1:] == 0))
            lengths += list(np.diff(np.append(starts, 1024))[:-1])
    lengths = np.array(lengths)     # whole documents: the last of a row is cut
    assert lengths.min() >= TRAFFIC["document_min"]
    assert lengths.max() <= 1024
    assert 200 < np.median(lengths) < 700
    # the power law: a few hot rows take most lookups
    ids = np.concatenate([b[1][0].reshape(-1) for b in made])
    assert np.mean(ids < 1000) > 0.45 and len(np.unique(ids)) > 1000


def test_the_seed_decides_the_batches_and_a_bad_packing_is_refused():
    first, again, other = batches(7), batches(7), batches(8)
    for a, b in zip(first, again):
        jax.tree.map(np.testing.assert_array_equal, a, b)
    assert any(not np.array_equal(a[1][0], b[1][0])
               for a, b in zip(first, other))
    assert not np.array_equal(first[0][0], first[1][0])     # rotated batches
    uniform = batches(7, alpha=0.0)[0][1][0]
    assert np.mean(uniform < 1000) < 0.15
    with pytest.raises(ValueError, match="do not pack"):
        batches(7, batch=4000)
    with pytest.raises(ValueError, match="do not pack"):
        packed_documents.generate(TRAFFIC, [(64, 2)], 1024, 256, 0.0, 0)


# ------------------------------------------ the reference against the model
def small_case(seed):
    model = Mellum(
        vocab_rows=64, hidden=32, num_heads=4, num_kv_heads=2, head_dim=8,
        layer_types=SMALL["layer_types"], window=SMALL["sliding_window"],
        rope_parameters=SMALL["rope_parameters"], num_experts_total=16,
        held_experts=range(4, 8), top_k=4, expert_width=16)
    params = model.init(jax.random.PRNGKey(seed))
    # weights large enough that every block matters to the loss
    params = jax.tree.map(lambda p: p * 8.0 if p.ndim > 1 else p, params)
    positions, cats, next_ids = packed_documents.generate(
        dict(TRAFFIC, document_median=14, document_min=3, num_batches=1),
        [(64, 1)], 96, 48, 0.0, seed)[0]
    return model, params, positions, [cats[0][:, 0]], next_ids


def both_sides(model, params, positions, cats, next_ids):
    """(loss, gradient of the dense tree and of the table) of the program's
    model and of the plain reference, under `highest`. The reference's
    gradient of the embedded tokens is summed into their rows."""
    dense = {k: v for k, v in params.items() if k != "embedding"}
    (table,) = model.embedding.get_weights(params["embedding"])
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(model.loss_fn)(
            params, positions, cats, next_ids)
        want_loss, (want_dense, want_x) = jax.value_and_grad(
            lambda dense, x: reference.model_loss(dense, [x], positions,
                                                  next_ids, SMALL),
            argnums=(0, 1))(dense, jnp.asarray(table)[cats[0]])
    (got_table,) = model.embedding.get_weights(grads.pop("embedding"))
    want_table = np.zeros_like(table)
    np.add.at(want_table, cats[0], np.asarray(want_x))
    return ((loss, (grads, jnp.asarray(got_table))),
            (want_loss, (want_dense, jnp.asarray(want_table))))


def worst_relative(got, want):
    pairs = zip(jax.tree.leaves(got), jax.tree.leaves(want))
    return max(float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
               for g, w in pairs)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_model_agrees_with_the_plain_reference(seed):
    """Loss to 1e-5 and every gradient to 2e-4 of its leaf's largest entry:
    f32 summation order over 96 tokens, 32 to 64 products deep."""
    (loss, grads), (want_loss, want_grads) = both_sides(*small_case(seed))
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert float(loss) > 2.0
    assert worst_relative(grads, want_grads) < 2e-4


def test_a_bfloat16_model_fails_the_same_comparison():
    """The mutation: the program's matrices rounded to bfloat16, as a model
    that kept its weights in the lower precision would hold them."""
    model, params, *batch = small_case(0)
    rounded = jax.tree.map(
        lambda p: p.astype(jnp.bfloat16).astype(p.dtype) if p.ndim > 1 else p,
        {k: v for k, v in params.items() if k != "embedding"})
    (loss, grads), _ = both_sides(model, dict(rounded,
                                              embedding=params["embedding"]),
                                  *batch)
    _, (want_loss, want_grads) = both_sides(model, params, *batch)
    assert (abs(float(loss) - float(want_loss)) > 1e-5 * float(want_loss)
            or worst_relative(grads, want_grads) > 2e-4)
    assert worst_relative(grads, want_grads) > 2e-3


# ------------------------- where the chip's default precision rounds a product
def blocks_under_rounded_products(model, params, positions, x):
    """The residual stream after each of the model's blocks, of the program
    and of the plain reference, every product's operands in bfloat16."""
    from distributed_embeddings_tpu.models import mellum

    def program(params, x):
        document, _ = mellum.packed_mask_terms(positions)
        stream = []
        for layer, kind in zip(params["layers"], model.layer_types):
            x = model._attend(layer, kind, x, positions, document)
            stream.append(x)
            x = model._sparse_mlp(layer, x)
            stream.append(x)
        return stream

    def plain(params, x):
        eps, stream = SMALL["rms_norm_eps"], []
        for layer, kind in zip(params["layers"], SMALL["layer_types"]):
            x = x + reference.attention(
                layer, reference.rms_norm(x, layer["attn_norm"], eps),
                positions, kind, SMALL)
            stream.append(x)
            x = x + reference.experts_held(
                layer["experts"],
                reference.rms_norm(x, layer["mlp_norm"], eps), SMALL)
            stream.append(x)
        return stream

    return (with_rounded_products(program)(params, x),
            with_rounded_products(plain)(params, x), plain(params, x))


def distance(a, b):
    return float(jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(b ** 2)))


def scores_of_scaled_queries(q, k, v, visible):
    """The mutation: the same attention, the queries scaled before the
    product (one pass over `q`, not over the scores)."""
    scores = jnp.einsum("nqhgd,nkhd->nhgqk", q / np.sqrt(q.shape[-1]), k)
    scores = jnp.where(visible[:, None, None], scores, -jnp.inf)
    return jnp.einsum("nhgqk,nkhd->nqhgd", jax.nn.softmax(scores, axis=-1), v)


@pytest.mark.parametrize("mutated", [False, True])
def test_the_models_products_round_what_the_plain_references_round(
        mutated, monkeypatch):
    """Rule (c) of the check allows the program four times the mean
    distance between the reference's two precisions and reads the worst
    element of the probed rows: it holds a program that is right only while
    its products round the values the reference's do. Under bfloat16
    operands the program's blocks stay within 1e-5 of the reference's (f32
    summation order, carried through a few roundings) where the reference
    itself is 1e-4 from its f32 values; with the queries scaled before the
    product of the scores, attention is its own draw of that rounding."""
    from distributed_embeddings_tpu.models import mellum
    if mutated:
        monkeypatch.setattr(mellum, "_scores_to_values",
                            scores_of_scaled_queries)
    model, params, positions, cats, _ = small_case(1)
    (table,) = model.embedding.get_weights(params["embedding"])
    got, want, exact = blocks_under_rounded_products(
        model, {k: v for k, v in params.items() if k != "embedding"},
        jnp.asarray(positions), jnp.asarray(table)[cats[0]])
    first_block = distance(got[0], want[0])
    rounding = distance(want[0], exact[0])
    assert rounding > 5e-5
    if mutated:
        assert first_block > 0.3 * rounding
    else:
        assert first_block < 1e-5 and distance(got[-1], want[-1]) < 1e-4
