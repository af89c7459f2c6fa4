"""`models/lfm2.py` and the sigmoid rule of `layers/experts.py` at a small
size on the CPU: the model against its plain reference
(`benchmark/references/lfm2.py`) over the cell's five-layer pattern, and a
bfloat16 mutation that must fail the same comparison; the expert layer's
shares under the sigmoid rule, a selection bias that selects and does not
weigh, the short convolution's mask over packed documents, the q/k norms,
the model's stage scopes and its gauges.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import lfm2 as reference
from distributed_embeddings_tpu.layers.experts import (SIGMOID_NORM_EPS,
                                                       ExpertLayer)
from distributed_embeddings_tpu.models import lfm2
from distributed_embeddings_tpu.obs import stages
from distributed_embeddings_tpu.obs.instrument import export_moe_gauges
from distributed_embeddings_tpu.obs.registry import MetricRegistry
from distributed_embeddings_tpu.training import make_sparse_train_step

HIDDEN, WIDTH, TOTAL, TOP_K = 32, 16, 16, 4
ROPE = {"rope_type": "default", "rope_theta": 10000}
# the cell's pattern: published layers 1-5
PATTERN = (("conv", "dense"), ("full_attention", "sparse"),
           ("conv", "sparse"), ("conv", "sparse"), ("conv", "sparse"))
SPEC = {   # a test's spec: what `reference.published_spec` reads from file
    "head_dim": 8, "layer_types": [mixer for mixer, _ in PATTERN],
    "num_dense_layers": 1, "rope_theta": 10000, "num_experts_per_tok": TOP_K,
    "routed_scaling_factor": 1, "first_expert_held": 4, "norm_eps": 1e-5}


def small_model(layers=PATTERN, held=range(4, 8)):
    return lfm2.Lfm2(
        vocab_rows=64, hidden=HIDDEN, num_heads=4, num_kv_heads=2, head_dim=8,
        layers=layers, rope=ROPE, conv_taps=3, dense_width=48,
        num_experts_total=TOTAL, held_experts=held, top_k=TOP_K,
        expert_width=WIDTH, bias_range=0.01)


def packed(lengths_per_sequence):
    return np.stack([np.concatenate([np.arange(n) for n in lengths])
                     for lengths in lengths_per_sequence]).astype(np.int32)


def small_case(seed, lengths=((20, 28), (10, 30, 8))):
    model = small_model()
    params = model.init(jax.random.PRNGKey(seed))
    # weights large enough that every block matters to the loss, and a bias
    # large enough to choose for some tokens
    params = jax.tree.map(lambda p: p * 8.0 if p.ndim > 1 else p, params)
    for layer in params["layers"]:
        if "experts" in layer:
            layer["experts"]["bias"] = layer["experts"]["bias"] * 8.0
    rng = np.random.default_rng(seed)
    positions = packed(lengths)
    ids = rng.integers(0, 64, positions.size).astype(np.int32)
    next_ids = rng.integers(0, 64, positions.size).astype(np.int32)
    return model, params, positions, [ids], next_ids


# ------------------------------------------ the reference against the model
def both_sides(model, params, positions, cats, next_ids):
    """(loss, gradient of the dense tree and of the table) of the program's
    model and of the plain reference, under `highest`. The reference's
    gradient of the embedded tokens is summed into their rows."""
    dense = {k: v for k, v in params.items() if k != "embedding"}
    (table,) = model.embedding.get_weights(params["embedding"])
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(model.loss_fn))(
            params, positions, cats, next_ids)
        want_loss, (want_dense, want_x) = jax.jit(jax.value_and_grad(
            lambda dense, x, positions, next_ids: reference.model_loss(
                dense, [x], positions, next_ids, SPEC),
            argnums=(0, 1)))(dense, jnp.asarray(table)[cats[0]], positions,
                             next_ids)
    (got_table,) = model.embedding.get_weights(grads.pop("embedding"))
    want_table = np.zeros_like(table)
    np.add.at(want_table, cats[0], np.asarray(want_x))
    return ((loss, (grads, jnp.asarray(got_table))),
            (want_loss, (want_dense, jnp.asarray(want_table))))


def worst_relative(got, want):
    """The worst leaf's largest error over its largest entry; a leaf whose
    gradient is zero on both sides (the selection bias) counts as 0."""
    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        scale = float(np.max(np.abs(w)))
        off = float(np.max(np.abs(g - w)))
        worst = max(worst, off / scale if scale else off)
    return worst


LOSS_RTOL, GRAD_RTOL = 1e-5, 2e-4


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_model_agrees_with_the_plain_reference(seed):
    """Loss to 1e-5 and every gradient to 2e-4 of its leaf's largest entry:
    f32 summation order over 96 tokens, five layers deep. No gradient
    reaches a selection bias, on either side."""
    (loss, grads), (want_loss, want_grads) = both_sides(*small_case(seed))
    assert float(loss) == pytest.approx(float(want_loss), rel=LOSS_RTOL)
    assert float(loss) > 2.0
    assert worst_relative(grads, want_grads) < GRAD_RTOL
    for side in (grads[0], want_grads[0]):
        biases = [layer["experts"]["bias"] for layer in side["layers"]
                  if "experts" in layer]
        assert len(biases) == 4
        assert not any(np.any(np.asarray(b)) for b in biases)


def test_a_bfloat16_model_fails_the_same_comparison_tenfold():
    """The mutation: the program's matrices rounded to bfloat16, as a model
    that kept its weights in the lower precision would hold them."""
    model, params, *batch = small_case(0)
    rounded = jax.tree.map(
        lambda p: p.astype(jnp.bfloat16).astype(p.dtype) if p.ndim > 1 else p,
        {k: v for k, v in params.items() if k != "embedding"})
    (loss, grads), _ = both_sides(
        model, dict(rounded, embedding=params["embedding"]), *batch)
    _, (want_loss, want_grads) = both_sides(model, params, *batch)
    # by one of the comparison's two limits, tenfold: the gradients'. (The
    # loss is a mean over 90 tokens and leaves by 3 times its own.)
    assert worst_relative(grads, want_grads) > 10 * GRAD_RTOL
    assert abs(float(loss) - float(want_loss)) > LOSS_RTOL * float(want_loss)


# ------------------------------------------------- the router's sigmoid rule
def sigmoid_layer(held=range(TOTAL)):
    return ExpertLayer(HIDDEN, WIDTH, TOTAL, held, TOP_K, router="sigmoid")


def test_the_eight_shares_under_the_sigmoid_rule_add_up_to_the_uncut_layer():
    """model-configs, section 4, for the sigmoid rule with its bias: every
    share routes over all experts alike and computes its own experts' part."""
    layer = sigmoid_layer()
    params = layer.init(jax.random.PRNGKey(0), std=0.3, bias_range=0.05)
    x = jax.random.normal(jax.random.PRNGKey(1), (96, HIDDEN))

    @jax.jit
    def whole_and_shares(params, x):
        parts = []
        for first in range(0, TOTAL, 2):
            share = sigmoid_layer(range(first, first + 2))
            parts.append(share(
                {"router": params["router"], "bias": params["bias"],
                 **{k: params[k][first:first + 2]
                    for k in ("gate", "up", "down")}}, x))
        return layer(params, x), parts

    whole, parts = whole_and_shares(params, x)
    assert len(parts) == 8
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=2e-6)
    assert all(0 < float(jnp.abs(p).max()) for p in parts)
    # against every pair written out, from scores taken by hand
    scores = 1 / (1 + np.exp(-np.asarray(x @ params["router"], np.float64)))
    chosen = np.argsort(-(scores + np.asarray(params["bias"])), axis=1)[:, :TOP_K]
    want = np.zeros((96, HIDDEN))
    for t in range(96):
        total = scores[t, chosen[t]].sum() + 1e-6
        for e in chosen[t]:
            h = np.asarray(x[t], np.float64)
            inner = h @ np.asarray(params["gate"][e], np.float64)
            inner = inner / (1 + np.exp(-inner)) * (
                h @ np.asarray(params["up"][e], np.float64))
            want[t] += scores[t, e] / total * (
                inner @ np.asarray(params["down"][e], np.float64))
    np.testing.assert_allclose(whole, want, rtol=2e-4, atol=2e-5)


def test_the_bias_selects_and_does_not_weigh():
    layer = sigmoid_layer(range(4, 8))
    router = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (HIDDEN, TOTAL))
    x = jax.random.normal(jax.random.PRNGKey(3), (256, HIDDEN))
    scores = np.asarray(jax.nn.sigmoid(x @ router))
    bias = jnp.asarray(np.random.default_rng(4).uniform(-0.2, 0.2, TOTAL),
                       jnp.float32)
    plain, biased = layer.route(router, x), layer.route(router, x, bias)
    # the chosen set follows score + bias ...
    np.testing.assert_array_equal(
        biased.experts, np.argsort(-(scores + np.asarray(bias)), axis=1,
                                   kind="stable")[:, :TOP_K])
    np.testing.assert_array_equal(
        plain.experts, np.argsort(-scores, axis=1, kind="stable")[:, :TOP_K])
    # ... and the weights follow the scores alone, renormalised
    picked = np.take_along_axis(scores, np.asarray(biased.experts), axis=1)
    np.testing.assert_allclose(
        biased.weights,
        picked / (picked.sum(axis=1, keepdims=True) + SIGMOID_NORM_EPS),
        rtol=1e-6)
    same = np.all(np.sort(plain.experts, 1) == np.sort(biased.experts, 1), 1)
    assert 0.2 < same.mean() < 0.95          # the bias chose for some tokens
    # a pair that stays chosen keeps its score as its weight's numerator: a
    # token whose set the bias left alone keeps its weights, and
    # where the set changed two pairs that stayed keep their ratio
    def by_expert(routing):              # the rank follows score + bias
        order = np.argsort(routing.experts, axis=1)
        return np.take_along_axis(np.asarray(routing.weights), order, 1)[same]
    # (an ulp: the four scores are summed in the order of their rank)
    np.testing.assert_allclose(by_expert(plain), by_expert(biased), rtol=3e-7)
    for t in np.flatnonzero(~same)[:20]:
        stay = [e for e in np.asarray(biased.experts[t])
                if e in np.asarray(plain.experts[t])]
        if len(stay) < 2:
            continue

        def weight(routing, e):
            return float(routing.weights[t][list(routing.experts[t]).index(e)])
        assert weight(biased, stay[0]) / weight(biased, stay[1]) == (
            pytest.approx(weight(plain, stay[0]) / weight(plain, stay[1]),
                          rel=1e-5))
    # no gradient reaches the bias; the router's passes through the scores
    params = dict(layer.init(jax.random.PRNGKey(5), std=0.3), bias=bias)
    grads = jax.jit(jax.grad(lambda p: jnp.sum(layer(p, x) ** 2)))(params)
    assert not np.any(np.asarray(grads["bias"]))
    assert np.any(np.asarray(grads["router"]))
    stats = layer.routing_stats(params, x)
    moved = np.mean(~np.all(
        np.sort(layer.route(params["router"], x).experts, 1)
        == np.sort(layer.route(params["router"], x, bias).experts, 1), 1))
    assert float(stats["bias_moved_share"]) == pytest.approx(moved)
    assert 0.05 < moved < 0.95
    zero = layer.routing_stats(dict(params, bias=jnp.zeros(TOTAL)), x)
    assert float(zero["bias_moved_share"]) == 0.0


@pytest.mark.parametrize("kwargs, message", [
    ({"router": "tanh"}, "router rule"),
    ({"router": "Sigmoid"}, "router rule")])
def test_a_router_rule_outside_the_two_is_refused(kwargs, message):
    with pytest.raises(ValueError, match=message):
        ExpertLayer(HIDDEN, WIDTH, TOTAL, range(4), TOP_K, **kwargs)
    with pytest.raises(ValueError, match="a mixer is one of"):
        small_model(layers=(("sliding_attention", "sparse"),))


# ------------------------------------------------- the short convolution
def test_the_convolution_is_three_taps_inside_the_document():
    """Against the sum written out position by position."""
    rng = np.random.default_rng(0)
    positions = packed([(5, 1, 2, 8), (16,)])
    v = rng.normal(size=(2, 16, 6)).astype(np.float32)
    taps = rng.normal(size=(6, 3)).astype(np.float32)
    want = np.zeros_like(v)
    for n in range(2):
        for t in range(16):
            for j in range(3):
                d = 2 - j
                if positions[n, t] >= d:
                    want[n, t] += taps[:, j] * v[n, t - d]
    got = lfm2.short_conv(jnp.asarray(v), jnp.asarray(taps),
                          jnp.asarray(positions))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_a_tap_never_reaches_into_the_document_before():
    model = small_model(layers=(("conv", "dense"),))
    layer = model.init(jax.random.PRNGKey(0))["layers"][0]
    layer = jax.tree.map(lambda p: p * 8.0 if p.ndim > 1 else p, layer)
    positions = jnp.asarray(packed([(20, 28)]))
    x = jax.random.normal(jax.random.PRNGKey(1), (48, HIDDEN))
    convolve = jax.jit(model._convolve)
    before = np.asarray(convolve(layer, x, positions))
    for token, changed in ((19, [19]), (5, [5, 6, 7]), (20, [20, 21, 22])):
        after = np.asarray(convolve(layer, x.at[token].add(1.0), positions))
        np.testing.assert_array_equal(
            np.flatnonzero(np.abs(after - before).max(axis=1) > 1e-7), changed)


def test_positions_restart_with_each_document():
    """A document's hidden states do not depend on what is packed before
    it, nor on where in the sequence it starts: through the convolutions,
    attention and both kinds of MLP."""
    model = small_model()
    params = model.init(jax.random.PRNGKey(0))
    params = jax.tree.map(lambda p: p * 8.0 if p.ndim > 1 else p, params)
    rng = np.random.default_rng(1)
    doc = rng.normal(size=(28, HIDDEN)).astype(np.float32)
    a = np.concatenate([rng.normal(size=(20, HIDDEN)), doc]).astype(np.float32)
    b = np.concatenate([rng.normal(size=(7, HIDDEN)), doc,
                        rng.normal(size=(13, HIDDEN))]).astype(np.float32)
    hidden_states = jax.jit(model.hidden_states)
    out_a = hidden_states(params, jnp.asarray(packed([(20, 28)])), a)
    out_b = hidden_states(params, jnp.asarray(packed([(7, 28, 13)])), b)
    np.testing.assert_allclose(out_a[20:], out_b[7:35], rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------- q and k norms
def test_queries_and_keys_are_normed_over_each_heads_width():
    """One token, so attention returns its value: the output does not see q
    or k. With two tokens of one document, the second token's weights are a
    softmax of two scores that hand arithmetic gives: q and k RMS-normed
    over the head's 8 with their weights, rotated, scaled by 1 / sqrt(8)."""
    model = small_model(layers=(("full_attention", "sparse"),))
    layer = model.init(jax.random.PRNGKey(0))["layers"][0]
    layer = dict(layer, wq=8 * layer["wq"], wk=8 * layer["wk"],
                 q_norm=jnp.linspace(0.5, 1.5, 8),
                 k_norm=jnp.linspace(2.0, 1.0, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, HIDDEN))
    positions = jnp.asarray([[0, 1]], jnp.int32)
    document = jnp.ones((1, 2), jnp.int32)
    got = np.asarray(model._attention(layer, x, positions, document))

    x64 = np.asarray(x, np.float64)

    def heads(w, n):
        return (x64 @ np.asarray(w, np.float64)).reshape(2, n, 8)

    def normed(a, weight):
        return a / np.sqrt((a ** 2).mean(-1, keepdims=True) + 1e-5) * (
            np.asarray(weight, np.float64))

    def rotated(a, position):
        angle = position * 10000.0 ** (-np.arange(4) / 4.0)
        a1, a2 = a[..., :4], a[..., 4:]
        return np.concatenate([a1 * np.cos(angle) - a2 * np.sin(angle),
                               a2 * np.cos(angle) + a1 * np.sin(angle)], -1)

    q = normed(heads(layer["wq"], 4), layer["q_norm"])
    k = normed(heads(layer["wk"], 2), layer["k_norm"])
    v = heads(layer["wv"], 2)
    out = np.zeros((2, 4, 8))
    out[0] = v[0][np.arange(4) // 2]             # token 0 sees itself alone
    q1 = rotated(q[1], 1.0)
    for head in range(4):
        kv = head // 2
        scores = np.array([q1[head] @ k[0, kv],             # position 0: no turn
                           q1[head] @ rotated(k[1, kv], 1.0)]) / np.sqrt(8)
        weights = np.exp(scores - scores.max())
        weights /= weights.sum()
        out[1, head] = weights[0] * v[0, kv] + weights[1] * v[1, kv]
    want = out.reshape(2, 32) @ np.asarray(layer["wo"], np.float64)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-7)
    # and the norms matter: with weights of one the scores are others
    plain = np.asarray(model._attention(
        dict(layer, q_norm=jnp.ones(8), k_norm=jnp.ones(8)), x, positions,
        document))
    assert np.abs(plain[1] - got[1]).max() > 1e-4 * np.abs(got[1]).max()


# ------------------------------------------------------- the training path
def test_the_step_trains_and_holds_shortconv_and_mlp():
    model = small_model()
    init_fn, step_fn = make_sparse_train_step(model, "adam", lr=3e-3)
    params = model.init(jax.random.PRNGKey(0))
    biases = [np.asarray(layer["experts"]["bias"])
              for layer in params["layers"] if "experts" in layer]
    assert all(np.any(b) and np.abs(b).max() <= model.bias_range
               for b in biases)
    state = init_fn(params)
    _, _, positions, cats, next_ids = small_case(0)
    positions, cats, next_ids = jax.tree.map(jnp.asarray,
                                             (positions, cats, next_ids))
    text = step_fn.lower(params, state, positions, cats,
                         next_ids).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    held = {s for n in names for s in re.findall(r"det\.([a-z_]+)", n)[-1:]}
    # (`latent` and `shared` are another model's: no step of this one has
    # them)
    assert held >= (set(stages.MODEL_STAGES) - {"latent", "shared"}) | {
        "lookup", "model", "dense_opt", "apply"}
    assert not {"latent", "shared"} & held
    assert {"shortconv", "mlp"} <= set(stages.MODEL_STAGES)
    assert held <= set(stages.STAGES + stages.MODEL_STAGES)
    paths = [n for n in names if "/" in n]
    assert [n for n in paths if "det." not in n] == []
    assert any("det.model/" in n and n.count("det.shortconv") for n in paths)
    assert any("transpose(" in n and n.count("det.mlp") for n in paths)
    losses = []
    for _ in range(30):
        params, state, loss = step_fn(params, state, positions, cats,
                                      next_ids)
        losses.append(float(loss))
    assert losses[0] == pytest.approx(np.log(64), rel=0.05)
    assert losses[-1] < 0.6 * losses[0]
    # the selection bias is a buffer: thirty steps of adam leave it alone
    for before, layer in zip(biases, [la for la in params["layers"]
                                      if "experts" in la]):
        np.testing.assert_array_equal(layer["experts"]["bias"], before)
    stats = jax.jit(model.routing_stats)(params, positions, cats)
    assert set(stats) == {"held_pairs_share", "max_expert_load_share",
                          "bias_moved_share"}
    assert stats["held_pairs_share"].shape == (4,)      # the sparse layers
    assert np.all((0 <= stats["bias_moved_share"])
                  & (stats["bias_moved_share"] <= 1))
    # the gauges, in the registry and in the catalog
    registry = MetricRegistry()
    said = export_moe_gauges(registry, stats)
    gauges = registry.snapshot()["gauges"]
    assert gauges["moe/bias_moved_share{layer=3}"] == pytest.approx(
        said["bias_moved_share"][3])
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "docs", "observability.md")) as f:
        catalog = f.read()
    assert all(f"`moe/{name}{{layer=}}`" in catalog for name in stats)
    assert all(f"`det.{name}`" in catalog for name in stages.MODEL_STAGES)
