"""`models/sdar.py` at a small size on the CPU: the block-diffusion model
against its plain reference (`benchmark/references/sdar_moe.py`: dense
``[2L, 2L]`` masked attention, a plain loop over the held experts) in loss,
dense gradients and the table's rows after two adam steps, and a bfloat16
mutation that must fail the same comparison; the mask's properties; the
eight shares of the experts against the uncut layer; the tiles the
attention visits and where a sequence's tile starts its clean keys; the
gauges on batches counted by hand; and the shared
`mellum._attend_blocks` held to the code it was before this model came.
"""

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference as plain
from benchmark.generators import block_diffusion
from benchmark.references import sdar_moe as reference
from distributed_embeddings_tpu.models import mellum, sdar
from distributed_embeddings_tpu.obs.instrument import export_diffusion_gauges
from distributed_embeddings_tpu.obs.registry import MetricRegistry
from distributed_embeddings_tpu.training import make_sparse_train_step

VOCAB, HIDDEN, HEADS, KV_HEADS, HEAD_DIM = 64, 32, 4, 2, 8
TOTAL, TOP_K, WIDTH, BLOCK = 16, 4, 16, 4
ROPE_ENTRY = {"rope_type": "default", "rope_theta": 10000}
SPEC = {   # a test's spec: what `reference.published_spec` reads from file
    "rope_theta": 10000, "block_length": BLOCK, "num_experts_per_tok": TOP_K,
    "first_expert_held": 4, "rms_norm_eps": 1e-6}
# two sequences of 1,024 whose documents put blocks across the edge of the
# attention's first tile of 512 queries (510 + [0, 4); 1 + 508 + [0, 4))
LENGTHS = ((510, 7, 300, 207), (1, 1022, 1))
# the same shape, but sequence 0's second tile of queries begins a document
# at 512, so its clean keys start there; sequence 1's still start at 0
TILE_DOCUMENT = ((300, 212, 512), (1, 1022, 1))


def small_model(layers=2, held=range(4, 8), total=TOTAL, top_k=TOP_K,
                block=BLOCK):
    return sdar.Sdar(
        vocab_rows=VOCAB, hidden=HIDDEN, num_heads=HEADS,
        num_kv_heads=KV_HEADS, head_dim=HEAD_DIM, num_layers=layers,
        rope=ROPE_ENTRY, block=block, num_experts_total=total,
        held_experts=held, top_k=top_k, expert_width=WIDTH)


def scaled(params):
    """Weights large enough that every block matters to the loss."""
    return jax.tree.map(lambda p: p * 8.0 if p.ndim > 1 else p, params)


def packed(lengths_per_sequence):
    return np.stack([np.concatenate([np.arange(n) for n in lengths])
                     for lengths in lengths_per_sequence]).astype(np.int32)


def diffusion_batch(positions, seed, block=BLOCK, eps=1e-3):
    """(inputs, [ids], weights) as the cell's generator makes them, over
    hand-given documents: clean ids below the mask id, a mask rate per
    block."""
    rng = np.random.default_rng(seed)
    x0 = rng.integers(0, VOCAB - 1, positions.shape).astype(np.int32)
    index = np.cumsum(positions.reshape(-1) % block == 0) - 1
    p = (1 - eps) * rng.random(index[-1] + 1) + eps
    masked = (rng.random(index.size) < p[index]).reshape(positions.shape)
    xt = np.where(masked, VOCAB - 1, x0)
    weights = np.where(masked.reshape(-1), 1.0 / p[index], 0.0)
    return (np.stack([positions, x0], axis=1),
            [np.stack([xt, x0], axis=1).reshape(-1)],
            weights.astype(np.float32))


def small_case(seed, lengths=LENGTHS, **sizes):
    model = small_model(**sizes)
    params = scaled(model.init(jax.random.PRNGKey(seed)))
    return (model, params) + diffusion_batch(packed(lengths), seed)


# ------------------------------------------ the reference against the model
def program_side(model, params, inputs, cats, weights):
    """(loss, (gradient of the dense tree, of the table)) of the program's
    model, under `highest`."""
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(model.loss_fn))(
            params, inputs, cats, weights)
    (table,) = model.embedding.get_weights(grads.pop("embedding"))
    return loss, (grads, jnp.asarray(table))


def reference_loss(dense, embs, inputs, labels):
    return reference.model_loss(dense, embs, inputs, labels, SPEC)


def reference_side(model, params, inputs, cats, weights):
    """The same of the plain reference; its gradient of the embedded
    positions is summed into their rows."""
    dense = {k: v for k, v in params.items() if k != "embedding"}
    (table,) = model.embedding.get_weights(params["embedding"])
    with jax.default_matmul_precision("highest"):
        loss, (g_dense, g_x) = jax.jit(jax.value_and_grad(
            lambda dense, x, inputs, weights: reference_loss(
                dense, [x], inputs, weights),
            argnums=(0, 1)))(dense, jnp.asarray(table)[cats[0]], inputs,
                             weights)
    g_table = np.zeros_like(table)
    np.add.at(g_table, cats[0], np.asarray(g_x))
    return loss, (g_dense, jnp.asarray(g_table))


@functools.lru_cache(maxsize=None)
def reference_of_seed(seed, lengths=LENGTHS):
    return reference_side(*small_case(seed, lengths))


def worst_relative(got, want):
    """The worst leaf's largest error over its largest entry."""
    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        scale = float(np.max(np.abs(w)))
        worst = max(worst, float(np.max(np.abs(g - w))) / scale)
    return worst


# the other token models' limits (`tests/test_glm4_moe_lite.py`): f32
# summation order over 2 x 2,048 positions, two layers deep, reads 1e-6
LOSS_RTOL, GRAD_RTOL = 1e-5, 2e-4


@pytest.mark.parametrize("seed, lengths", [
    (0, LENGTHS), (1, LENGTHS), (2, LENGTHS), (9, TILE_DOCUMENT)],
    ids=["0", "1", "2", "tile_document"])
def test_the_model_agrees_with_the_plain_reference(seed, lengths):
    """Loss to 1e-5 and every gradient, the table's rows among them, to
    2e-4 of its leaf's largest entry. Every dense leaf has a gradient. The
    last case's second tile starts its clean keys at 512 in one sequence."""
    loss, grads = program_side(*small_case(seed, lengths))
    want_loss, want_grads = reference_of_seed(seed, lengths)
    assert float(loss) == pytest.approx(float(want_loss), rel=LOSS_RTOL)
    assert float(loss) > 1.0
    assert worst_relative(grads, want_grads) < GRAD_RTOL
    assert all(np.any(np.asarray(leaf)) for leaf in jax.tree.leaves(grads))


def test_a_bfloat16_model_fails_the_same_comparison_tenfold():
    """The mutation: the program's matrices rounded to bfloat16, as a model
    that kept its weights in the lower precision would hold them."""
    model, params, *batch = small_case(0)
    rounded = jax.tree.map(
        lambda p: p.astype(jnp.bfloat16).astype(p.dtype) if p.ndim > 1 else p,
        {k: v for k, v in params.items() if k != "embedding"})
    _, grads = program_side(
        model, dict(rounded, embedding=params["embedding"]), *batch)
    _, want_grads = reference_of_seed(0)
    # by one of the comparison's two limits, tenfold: the gradients'
    assert worst_relative(grads, want_grads) > 10 * GRAD_RTOL


def test_two_adam_steps_move_the_table_as_the_reference_does():
    """The training path (`make_sparse_train_step`, adam: the table's rows
    lazily and row-wise) against `benchmark/reference.py::train_steps` over
    the plain reference's loss, two batches: every row's change to 1e-3 of
    what it could have moved had no contribution cancelled (the check's
    rule (c) allows four times f32 order, 4e-5, here on 4,096 lookups of 64
    rows), the mask id's row, which a quarter of the lookups hit, among
    them."""
    model, params, *first = small_case(3)
    second = diffusion_batch(packed(LENGTHS), 4)
    optimizer = {"kind": "adam", "lr": 1e-2, "b1": 0.9, "b2": 0.999,
                 "eps": 1e-8}
    (table,) = model.embedding.get_weights(params["embedding"])
    dense = jax.tree.map(np.asarray, {k: v for k, v in params.items()
                                      if k != "embedding"})
    with jax.default_matmul_precision("highest"):
        *_, change, moved, _ = plain.train_steps(
            reference_loss, optimizer, [np.asarray(table)], [0], dense,
            [(inputs, [ids[:, None]], weights)
             for inputs, (ids,), weights in (first, second)])
        init_fn, step_fn = make_sparse_train_step(model, "adam", lr=1e-2,
                                                  donate=False)
        state = init_fn(params)
        for inputs, cats, weights in (first, second):
            params, state, _ = step_fn(params, state, inputs, cats, weights)
    (after,) = model.embedding.get_weights(params["embedding"])
    got = np.asarray(after) - np.asarray(table)
    hit = np.unique(np.concatenate([first[1][0], second[1][0]]))
    assert VOCAB - 1 in hit and len(hit) == VOCAB
    err = np.abs(got - np.asarray(change[0]))
    assert np.all(err <= 1e-3 * np.asarray(moved[0]) + 1e-7)
    assert np.all(np.abs(got).max(axis=1) > 1e-3)


# ------------------------------------------------------- the mask's shape
def noisy_states(model, params, inputs, ids):
    (table,) = model.embedding.get_weights(params["embedding"])
    return jax.jit(model.hidden_states)(
        {k: v for k, v in params.items() if k != "embedding"},
        jnp.asarray(inputs[:, 0]), jnp.asarray(table)[ids])


@functools.partial(jax.jit, static_argnums=0)
def _one_layer(model, layer, x, positions):
    document, blk = sdar.block_terms(positions, BLOCK)
    x = model._attend(layer, x, positions, document, blk, last=False)
    return model._sparse_mlp(layer, x)


def both_streams(model, params, inputs, ids):
    """One layer over both streams, as every layer but the last runs."""
    (table,) = model.embedding.get_weights(params["embedding"])
    return _one_layer(model, params["layers"][0], jnp.asarray(table)[ids],
                      jnp.asarray(inputs[:, 0]))


# the last document of sequence 0, positions 817-1023, and its 40th block
DOC, AT = slice(817, 1024), 817 + 4 * 40


@pytest.mark.parametrize("changed", [
    "later_blocks", "noisy_of_other_blocks", "other_documents",
    "clean_reads_no_noisy"])
def test_the_mask_keeps_what_the_equations_keep(changed):
    """Ids changed where the mask says no query may look: the noisy stream's
    last states of a block are unmoved by its document's later blocks (both
    streams), by the noisy tokens of every other block, and by other
    documents; one layer's clean states are unmoved by every noisy token.
    Where the mask does look, the same change moves them."""
    model, params, inputs, (ids,), _ = small_case(5)
    n, _, length = inputs.shape
    streams = ids.reshape(n, 2, length).copy()
    where = np.zeros((n, 2, length), bool)
    if changed == "later_blocks":
        where[0, :, AT + BLOCK:DOC.stop] = True
        kept, moved = slice(DOC.start, AT + BLOCK), slice(AT + BLOCK, None)
    elif changed == "noisy_of_other_blocks":
        where[:, 0] = True
        where[0, 0, AT:AT + BLOCK] = False
        kept, moved = slice(AT, AT + BLOCK), slice(DOC.start, AT)
    elif changed == "other_documents":
        where[:] = True
        where[0, :, DOC] = False
        kept, moved = DOC, slice(0, DOC.start)
    else:
        where[:, 0] = True
    streams[where] = (streams[where] + 7) % (VOCAB - 1)
    if changed == "clean_reads_no_noisy":
        before, after = (both_streams(model, params, inputs, i.reshape(-1))
                         .reshape(n, 2, length, -1)
                         for i in (ids, streams))
        np.testing.assert_allclose(before[:, 1], after[:, 1], rtol=1e-6,
                                   atol=1e-6)
        assert not np.allclose(before[:, 0], after[:, 0])
        return
    before, after = (noisy_states(model, params, inputs, i.reshape(-1))
                     .reshape(n, length, -1) for i in (ids, streams))
    np.testing.assert_allclose(before[0, kept], after[0, kept], rtol=1e-6,
                               atol=1e-6)
    assert np.max(np.abs(before[0, moved] - after[0, moved])) > 1e-2


def test_the_plain_references_mask_is_the_four_quarters():
    """A document of 5 then one of 3 at B = 2, written out."""
    got = np.asarray(reference.block_diffusion_mask(
        jnp.asarray([0, 1, 2, 3, 4, 0, 1, 2]), 2)).astype(int)
    blocks = [0, 0, 1, 1, 2, 3, 3, 4]                 # (document, block)
    same = np.equal.outer(blocks, blocks)
    doc = np.equal.outer([0] * 5 + [1] * 3, [0] * 5 + [1] * 3)
    earlier = np.less.outer(blocks, blocks).T & doc   # key block < query's
    np.testing.assert_array_equal(got[:8, :8], same)
    np.testing.assert_array_equal(got[:8, 8:], earlier)
    np.testing.assert_array_equal(got[8:, :8], 0)
    np.testing.assert_array_equal(got[8:, 8:], earlier | same)
    assert got.sum() == 2 * 24                        # `visible_pairs` x 2


# ------------------------------------------------------------------ shares
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """model-configs, section 4: 8 shares of 16 of 128 experts, each
    routing over all 128 and computing its own experts' part, plus the
    attention every chip computes alike counted once, give the plain
    reference's whole layer."""
    whole = small_model(layers=1, held=range(128), total=128, top_k=8)
    layer = scaled({"layers": [whole._init_layer(jax.random.PRNGKey(0))]})[
        "layers"][0]
    inputs, (ids,), _ = diffusion_batch(packed(((40, 24),)), 6)
    x = jax.random.normal(jax.random.PRNGKey(1), (ids.size, HIDDEN))
    spec = dict(SPEC, num_experts_per_tok=8, first_expert_held=0)

    @jax.jit
    def shares_and_reference(layer, x, inputs):
        positions = inputs[:, 0]
        document, blk = sdar.block_terms(positions, BLOCK)
        h = whole._attend(layer, x, positions, document, blk, last=False)
        parts = []
        for first in range(0, 128, 16):
            share = small_model(layers=1, held=range(first, first + 16),
                                total=128, top_k=8)
            cut = {"router": layer["experts"]["router"],
                   **{k: layer["experts"][k][first:first + 16]
                      for k in ("gate", "up", "down")}}
            parts.append(share._sparse_mlp(dict(layer, experts=cut), h) - h)
        attended = x + reference.attention(layer, x, inputs, spec)
        want = attended + reference.experts_held(
            layer["experts"], reference.rms_norm(
                attended, layer["post_attention_layernorm"], 1e-6), 1, spec)
        return h, parts, attended, want

    h, parts, attended, want = shares_and_reference(layer, x, inputs)
    assert len(parts) == 8
    assert all(0 < float(jnp.abs(p).max()) for p in parts)
    np.testing.assert_allclose(h, attended, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h + sum(parts), want, rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------------- tiles
def covered(tiles, length):
    """``[2L, 2L]`` bool: the pairs the tiles compute, over ``[xt ; x0]``."""
    out = np.zeros((2 * length, 2 * length), bool)
    for t in tiles:
        rows = slice(t.stream * length + t.start, t.stream * length + t.stop)
        out[rows, length:length + t.clean_stop] = True
        out[rows, t.noisy_start:t.noisy_stop] = True
    return out


def one_stream_pairs(length):
    """The pairs `mellum._attend_blocks` computes over one stream of
    ``2L`` positions, causal: whole blocks of keys up to each block of
    queries' last."""
    tile = mellum.ATTN_BLOCK
    return sum((min(s + tile, 2 * length) - s) * min(s + tile, 2 * length)
               for s in range(0, 2 * length, tile))


def test_the_visited_tiles_hold_every_visible_pair_and_nothing_else_whole():
    """Every pair the plain reference's mask keeps lies in a tile; each
    tile's own mask is the reference's over its pairs; every tile holds a
    visible pair; the clean-to-noisy quarter is never computed. Through the
    gauge: the visible pairs of the tiles are the generator's count of the
    whole mask, and the share beats the one-stream rule's."""
    model = small_model()
    inputs, (ids,), weights = diffusion_batch(packed(LENGTHS), 7)
    positions = jnp.asarray(inputs[:, 0])
    length = positions.shape[1]
    document, blk = sdar.block_terms(positions, BLOCK)
    tiles = sdar.block_diffusion_tiles(length, BLOCK)
    assert len(tiles) == 4
    cover = covered(tiles, length)
    assert not cover[length:, :length].any()            # clean -> noisy
    for n in range(positions.shape[0]):
        mask = np.asarray(reference.block_diffusion_mask(positions[n], BLOCK))
        assert np.all(cover[mask])
    for t in tiles:
        visible = np.asarray(sdar._visible(t, document, blk))
        assert visible.any(axis=(1, 2)).all()
        for n in range(positions.shape[0]):
            mask = np.asarray(reference.block_diffusion_mask(positions[n],
                                                            BLOCK))
            rows = mask[t.stream * length + t.start:
                        t.stream * length + t.stop]
            want = np.concatenate(
                [rows[:, length:length + t.clean_stop],
                 rows[:, t.noisy_start:t.noisy_stop]], axis=1)
            np.testing.assert_array_equal(visible[n], want)
    stats = jax.jit(model.diffusion_stats)(inputs, [ids], weights)
    pairs = block_diffusion.visible_pairs(inputs[:, 0], BLOCK)
    computed = sum(positions.shape[0] * (t.stop - t.start) * t.keys()
                   for t in tiles) + sum(
        positions.shape[0] * (t.stop - t.start) * t.keys()
        for t in sdar.block_diffusion_tiles(length, BLOCK, (sdar.NOISY,)))
    assert float(stats["visited_pair_share"]) == pytest.approx(
        3 * pairs / computed, rel=1e-6)
    one_stream = 2 * pairs / (positions.shape[0] * one_stream_pairs(length))
    assert float(stats["visited_pair_share"]) > 1.1 * one_stream


@functools.partial(jax.jit, static_argnums=1)
def _clean_key_starts(positions, length):
    document, _ = sdar.block_terms(positions, BLOCK)
    return jnp.stack([sdar.clean_key_start(document, start)
                      for start in range(0, length, mellum.ATTN_BLOCK)],
                     axis=1)


@pytest.mark.parametrize("lengths, want", [
    (LENGTHS, [[0, 0], [0, 0]]), (TILE_DOCUMENT, [[0, 512], [0, 0]])],
    ids=["every_tile_from_0", "tile_document"])
def test_each_tiles_clean_keys_start_at_its_first_querys_document(
        lengths, want):
    """A (sequence, tile)'s clean keys start at the document start of the
    tile's first query, rounded down to 512; the mask keeps no pair of the
    tile's queries before it, in either stream, so the tiles from there on
    still hold every pair the plain reference's mask keeps."""
    positions = packed(lengths)
    length = positions.shape[1]
    starts = np.asarray(_clean_key_starts(jnp.asarray(positions), length))
    np.testing.assert_array_equal(starts, want)
    tile = mellum.ATTN_BLOCK
    for n, row in enumerate(positions):
        mask = np.asarray(reference.block_diffusion_mask(
            jnp.asarray(row), BLOCK))
        for i, start in enumerate(range(0, length, tile)):
            document_start = start - row[start]
            assert starts[n, i] == document_start // tile * tile
            for stream in (sdar.NOISY, sdar.CLEAN):
                rows = mask[stream * length + start:
                            stream * length + start + tile]
                assert not rows[:, length:length + starts[n, i]].any()


def masked_attention(q, k, v, positions):
    """The plain reference's attention over ``[xt ; x0]`` of each sequence:
    `q` ``[n, streams, L, heads, d]`` (the noisy stream's rows of the
    ``[2L, 2L]`` mask alone where one stream), `k`, `v` ``[n, 2, L, kv
    heads, d]``; ``[n, streams, L, heads * d]``."""
    n, streams, length, heads, d = q.shape
    group = heads // k.shape[3]
    outs = []
    for s in range(n):
        mask = reference.block_diffusion_mask(positions[s], BLOCK)
        mask = mask[:streams * length]
        queries = q[s].reshape(streams * length, heads, d)
        keys = jnp.repeat(k[s].reshape(2 * length, -1, d), group, axis=1)
        values = jnp.repeat(v[s].reshape(2 * length, -1, d), group, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", queries, keys) / math.sqrt(d)
        weights = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", weights, values).reshape(
            streams, length, heads * d))
    return jnp.stack(outs)


@pytest.mark.parametrize("streams", [2, 1], ids=["both", "noisy_last"])
def test_the_attention_and_its_gradients_match_the_plain_masked_attention(
        streams):
    """`attend_block_diffusion` on documents that start the second tile's
    clean keys at 512 in one sequence and at 0 in the other: outputs and
    the gradients of q, k and v to the model's limit (2e-4 of a leaf's
    largest entry) against the plain reference's ``[2L, 2L]`` masked
    attention, both streams' queries (every layer but the last) and the
    noisy stream's alone (the last)."""
    positions = jnp.asarray(packed(TILE_DOCUMENT))
    n, length = positions.shape
    keys = jax.random.split(jax.random.PRNGKey(10), 4)
    q = jax.random.normal(keys[0], (n, streams, length, HEADS, HEAD_DIM))
    k = jax.random.normal(keys[1], (n, 2, length, KV_HEADS, HEAD_DIM))
    v = jax.random.normal(keys[2], (n, 2, length, KV_HEADS, HEAD_DIM))
    cotangent = jax.random.normal(keys[3], (n, streams, length,
                                            HEADS * HEAD_DIM))

    def program(q, k, v, positions):
        document, blk = sdar.block_terms(positions, BLOCK)
        return sdar.attend_block_diffusion(q, k, v, document, blk, BLOCK)

    def run(attend):
        def total(q, k, v, positions):
            return jnp.sum(attend(q, k, v, positions) * cotangent)
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda *a: (attend(*a), jax.grad(
                total, argnums=(0, 1, 2))(*a)))(q, k, v, positions)

    got, want = run(program), run(masked_attention)
    assert worst_relative(got, want) < GRAD_RTOL


# ------------------------------------------------------------------ gauges
def test_the_gauges_read_a_batch_counted_by_hand():
    """One sequence of 8: a document of 5 and one of 3, blocks of 2, so
    blocks of 2, 2, 1 and 2, 1. One stream's queries keep 2 + 2 + 4 + 4 +
    5 + 2 + 2 + 3 = 24 keys (the keys of their document up to their
    block's end). One tile a stream: a noisy query computes 8 clean and 8
    noisy keys, a clean one 8 clean keys; two layers, the last of them the
    noisy queries' alone: 24 x 3 of 8 x (16 + 8 + 16) = 320 pairs. Three
    of the eight tokens are masked: 3 of 8 noisy positions, 3 of 16
    lookups."""
    model = small_model(layers=2, block=2)
    positions = np.asarray([[0, 1, 2, 3, 4, 0, 1, 2]], np.int32)
    x0 = np.asarray([[5, 6, 7, 8, 9, 10, 11, 12]], np.int32)
    masked = np.asarray([0, 1, 0, 0, 1, 0, 0, 1], bool)
    xt = np.where(masked, VOCAB - 1, x0)
    inputs = np.stack([positions, x0], axis=1)
    ids = np.concatenate([xt, x0], axis=1).reshape(-1)
    weights = np.where(masked, 2.5, 0.0).astype(np.float32)
    assert block_diffusion.visible_pairs(positions, 2) == 24
    registry = MetricRegistry()
    said = export_diffusion_gauges(registry, jax.jit(model.diffusion_stats)(
        inputs, [ids], weights))
    assert said == {"masked_share": 3 / 8, "mask_row_share": 3 / 16,
                    "skipped_pair_share": 0.0,
                    "visited_pair_share": pytest.approx(72 / 320)}
    gauges = registry.snapshot()["gauges"]
    assert gauges["diffusion/masked_share"] == 3 / 8
    assert gauges["diffusion/mask_row_share"] == 3 / 16
    assert gauges["attn/visited_pair_share{mask=block_diffusion}"] == (
        pytest.approx(0.225))
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "docs", "observability.md")) as f:
        catalog = f.read()
    assert all(f"`{name}" in catalog for name in (
        "diffusion/masked_share", "diffusion/mask_row_share",
        "attn/visited_pair_share{mask=block_diffusion}",
        "attn/skipped_pair_share{mask=block_diffusion}"))


def test_the_skipped_pair_share_reads_a_sequence_counted_by_hand():
    """One sequence of 1,024: a document of 512 then one of 512, blocks of
    4, two layers. The static tiles (512 queries each): noisy 512 + 515
    and 1,024 + 515 keys, clean 515 and 1,024, the noisy ones in both
    layers: 512 x (2 x 2,566 + 1,539) = 512 x 6,671 pairs. The second
    tile's first query starts a document at 512, so both streams' second
    tiles leave out 512 clean keys: 512 x 512 x (2 + 1) pairs. A stream
    keeps 2 x 16 x (1 + ... + 128) = 264,192 pairs a layer, three times."""
    model = small_model(layers=2)
    positions = packed(((512, 512),))
    inputs, (ids,), weights = diffusion_batch(positions, 11)
    assert block_diffusion.visible_pairs(positions, BLOCK) == 264_192
    registry = MetricRegistry()
    said = export_diffusion_gauges(registry, jax.jit(model.diffusion_stats)(
        inputs, [ids], weights))
    assert said["skipped_pair_share"] == pytest.approx(1_536 / 6_671)
    assert said["visited_pair_share"] == pytest.approx(
        3 * 264_192 / (512 * (6_671 - 1_536)))
    gauges = registry.snapshot()["gauges"]
    assert gauges["attn/skipped_pair_share{mask=block_diffusion}"] == (
        pytest.approx(1_536 / 6_671))


# ------------------------------------ the shared attention, as it was before
@jax.checkpoint
def _scores_to_values_before(q, k, v, visible):
    scores = jnp.einsum("nqhgd,nkhd->nhgqk", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(visible[:, None, None], scores, -jnp.inf)
    weights = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return jnp.einsum("nhgqk,nkhd->nqhgd", weights, v)


def _attend_blocks_before(q, k, v, document, window):
    """`mellum._attend_blocks` as the three token cells ran it before the
    block-diffusion model came, block size 512."""
    n_seq, length, heads, d = q.shape
    q = q.reshape(n_seq, length, k.shape[2], heads // k.shape[2], d)
    block = min(512, length)
    index = jnp.arange(length)
    outs = []
    for start in range(0, length, block):
        stop = min(start + block, length)
        first = max(0, start - (window - 1))
        first -= first % block
        behind = index[start:stop, None] - index[None, first:stop]
        visible = ((document[:, start:stop, None]
                    == document[:, None, first:stop])
                   & (behind >= 0) & (behind < window))
        outs.append(_scores_to_values_before(
            q[:, start:stop], k[:, first:stop], v[:, first:stop], visible))
    return jnp.concatenate(outs, axis=1).reshape(n_seq, length, -1)


@pytest.mark.parametrize("kind, heads, kv_heads, d, dv, window", [
    ("causal", 8, 2, 16, 16, 1100),          # Mellum2's full layers, LFM2's
    ("window", 8, 2, 16, 16, 200),           # Mellum2's window layers
    ("latent", 4, 4, 24, 32, 1100)])         # GLM's: values wider than keys
def test_the_shared_attention_is_bit_equal_to_its_code_before(
        kind, heads, kv_heads, d, dv, window):
    """The three token cells' attention: the same outputs and gradients,
    bit for bit, as the code they ran before, over blocks of 512 queries
    with documents across the blocks' edges."""
    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    length = 1100
    q = jax.random.normal(keys[0], (2, length, heads, d))
    k = jax.random.normal(keys[1], (2, length, kv_heads, d))
    v = jax.random.normal(keys[2], (2, length, kv_heads, dv))
    document, _ = mellum.packed_mask_terms(jnp.asarray(packed(
        ((300, 500, 300), (1, 1099)))))

    def run(attend):
        def total(q, k, v):
            return jnp.sum(attend(q, k, v, document, window) ** 2)
        return jax.jit(jax.value_and_grad(total, argnums=(0, 1, 2)))(q, k, v)

    got, want = run(mellum._attend_blocks), run(_attend_blocks_before)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert mellum.ATTN_BLOCK == 512
