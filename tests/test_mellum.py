"""`models/mellum.py` and `layers/experts.py` at a small size on the CPU:
the expert layer told which experts it holds (the shares add up, nothing is
dropped, nothing absent is computed), attention's mask over packed
documents, the rotary tables, the model's stage scopes and a training run.
The model against its plain reference is `tests/benchmark/
test_benchmark_mellum2.py`'s.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_embeddings_tpu.layers import experts as experts_lib
from distributed_embeddings_tpu.layers.experts import ExpertLayer, Routing
from distributed_embeddings_tpu.models import mellum
from distributed_embeddings_tpu.obs import stages
from distributed_embeddings_tpu.obs.instrument import export_moe_gauges
from distributed_embeddings_tpu.obs.registry import MetricRegistry
from distributed_embeddings_tpu.training import make_sparse_train_step

PUBLISHED_YARN = {
    "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
    "original_max_position_embeddings": 8192, "beta_fast": 32,
    "beta_slow": 1, "attention_factor": 1.2772588722239782}
SMALL_ROPE = {
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000},
    "full_attention": {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                       "original_max_position_embeddings": 16,
                       "beta_fast": 4, "beta_slow": 1,
                       "attention_factor": 1.1386294361119891}}
HIDDEN, WIDTH, TOTAL, TOP_K = 32, 16, 16, 4


def small_model(held=range(4, 8), layer_types=("sliding_attention",
                                                "full_attention")):
    return mellum.Mellum(
        vocab_rows=64, hidden=HIDDEN, num_heads=4, num_kv_heads=2, head_dim=8,
        layer_types=layer_types, window=12, rope_parameters=SMALL_ROPE,
        num_experts_total=TOTAL, held_experts=held, top_k=TOP_K,
        expert_width=WIDTH)


def packed(lengths_per_sequence):
    return np.stack([np.concatenate([np.arange(n) for n in lengths])
                     for lengths in lengths_per_sequence]).astype(np.int32)


def small_batch(seed=0, lengths=((20, 28), (10, 30, 8))):
    rng = np.random.default_rng(seed)
    positions = packed(lengths)
    ids = rng.integers(0, 64, positions.size).astype(np.int32)
    return positions, [ids], rng.integers(0, 64, positions.size).astype(
        np.int32)


# ------------------------------------------------------------ expert layer
def dense_experts(params, first, x, routing):
    """Every pair written out, one at a time: the layer without a sort."""
    out = np.zeros_like(np.asarray(x))
    gate, up, down = (np.asarray(params[k], np.float64)
                      for k in ("gate", "up", "down"))
    x64 = np.asarray(x, np.float64)
    for t in range(x.shape[0]):
        for e, w in zip(np.asarray(routing.experts[t]),
                        np.asarray(routing.weights[t])):
            local = int(e) - first
            if 0 <= local < gate.shape[0]:
                h = x64[t] @ gate[local]
                out[t] += w * ((h / (1 + np.exp(-h)) * (x64[t] @ up[local]))
                               @ down[local])
    return out


def whole_layer(tokens=96, seed=0):
    layer = ExpertLayer(HIDDEN, WIDTH, TOTAL, range(TOTAL), TOP_K)
    params = layer.init(jax.random.PRNGKey(seed), std=0.3)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (tokens, HIDDEN))
    return layer, params, x


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """model-configs, section 4: the parts of the result that all the shares
    give add up to what the uncut layer gives (nothing is computed alike on
    every chip: there is no shared expert)."""
    layer, params, x = whole_layer()
    whole = layer(params, x)
    parts = []
    for first in range(0, TOTAL, 4):
        share = ExpertLayer(HIDDEN, WIDTH, TOTAL, range(first, first + 4),
                            TOP_K)
        assert share.fast_rows(x.shape[0]) < x.shape[0] * TOP_K
        parts.append(share(
            {"router": params["router"],
             **{k: params[k][first:first + 4] for k in ("gate", "up", "down")}},
            x))
    # f32 summation order: a token's 4 contributions in another order
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=2e-6)
    want = dense_experts(params, 0, x, layer.route(params["router"], x))
    np.testing.assert_allclose(whole, want, rtol=2e-5, atol=2e-6)
    # a share does compute something, and not everything
    assert all(0 < float(jnp.abs(p).max()) for p in parts)


@pytest.mark.parametrize("picked, rows", [
    ("all held", "every slot"), ("none held", "fast"), ("mixed", "fast")])
def test_no_pair_is_dropped_and_no_absent_expert_is_computed(picked, rows):
    """A routing in which every token picks only held experts has four times
    the pairs `fast_rows` holds and takes the other branch: every pair is
    computed. One in which none does gives exactly zero, and a zero
    gradient. Output and gradients against every pair written out."""
    tokens, held = 64, range(4, 8)
    layer = ExpertLayer(HIDDEN, WIDTH, TOTAL, held, TOP_K)
    params = layer.init(jax.random.PRNGKey(2), std=0.3)
    x = jax.random.normal(jax.random.PRNGKey(3), (tokens, HIDDEN))
    rng = np.random.default_rng(4)
    pool = {"all held": list(held), "none held": [0, 1, 2, 3, 8, 12, 15],
            "mixed": list(range(TOTAL))}[picked]
    chosen = np.stack([rng.choice(pool, TOP_K, replace=False)
                       for _ in range(tokens)]).astype(np.int32)
    weights = rng.dirichlet(np.ones(TOP_K), tokens).astype(np.float32)
    routing = Routing(jnp.asarray(chosen), jnp.asarray(weights))
    count = int(np.isin(chosen, list(held)).sum())
    assert (count > layer.fast_rows(tokens)) == (rows == "every slot")

    def part(params, x, weights):
        return layer._held_part(params, x, routing._replace(weights=weights))

    got, vjp = jax.vjp(part, params, x, routing.weights)
    want = dense_experts(params, held.start, x, routing)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    g = jax.random.normal(jax.random.PRNGKey(5), got.shape)
    d_params, d_x, d_w = vjp(g)
    if picked == "none held":
        assert not np.any(np.asarray(got)) and not np.any(np.asarray(d_x))
        assert not any(np.any(np.asarray(v)) for v in d_params.values())
        return
    # the gradient against autodiff through the masked dense product
    def dense(params, x, weights):
        out = jnp.zeros_like(x)
        for local in range(len(held)):
            w = jnp.sum(jnp.where(routing.experts == held.start + local,
                                  weights, 0.0), axis=1)
            out += w[:, None] * ((jax.nn.silu(x @ params["gate"][local])
                                  * (x @ params["up"][local]))
                                 @ params["down"][local])
        return out

    want_params, want_x, want_w = jax.vjp(dense, params, x,
                                          routing.weights)[1](g)
    np.testing.assert_allclose(d_x, want_x, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(d_w, want_w, rtol=1e-4, atol=1e-5)
    for k in ("gate", "up", "down"):
        np.testing.assert_allclose(d_params[k], want_params[k], rtol=1e-4,
                                   atol=1e-5)


def test_the_router_scores_all_experts_and_renormalises_the_top_k():
    layer, params, x = whole_layer(tokens=32)
    held = ExpertLayer(HIDDEN, WIDTH, TOTAL, range(4, 8), TOP_K)
    routing = held.route(params["router"], x)
    scores = np.asarray(jax.nn.softmax(x @ params["router"], axis=-1))
    want = np.argsort(-scores, axis=1)[:, :TOP_K]
    np.testing.assert_array_equal(routing.experts, want)
    np.testing.assert_allclose(
        routing.weights, np.take_along_axis(scores, want, 1)
        / np.take_along_axis(scores, want, 1).sum(1, keepdims=True), rtol=1e-6)
    stats = held.routing_stats({"router": params["router"]}, x)
    share = np.isin(want, range(4, 8)).mean()
    assert float(stats["held_pairs_share"]) == pytest.approx(share)
    loads = [(want == e).sum() for e in range(4, 8)]
    assert float(stats["max_expert_load_share"]) == pytest.approx(
        max(loads) / sum(loads))


@pytest.mark.parametrize("held, message", [
    ([4, 6, 7], "not a range"), (range(14, 18), "not a range"),
    (range(0), "not a range")])
def test_held_experts_are_a_range_of_the_routers(held, message):
    with pytest.raises(ValueError, match=message):
        ExpertLayer(HIDDEN, WIDTH, TOTAL, held, TOP_K)


def test_fast_rows_are_twice_an_even_share_in_whole_tiles():
    layer = ExpertLayer(2304, 896, 64, range(8), 8)
    assert layer.fast_rows(16384) == 32768          # of 131,072 slots
    assert ExpertLayer(8, 8, 4, range(4), 2).fast_rows(16) == 32   # all held
    assert layer.fast_rows(100) % experts_lib.ROW_TILE == 0


# --------------------------------------------------------------- attention
def attention_out(model, kind, positions, x, layer):
    document, _ = mellum.packed_mask_terms(jnp.asarray(positions))
    return np.asarray(model._attention(layer, kind, x,
                                       jnp.asarray(positions), document))


@pytest.mark.parametrize("block", [16, 512])
def test_a_token_never_sees_another_document_and_a_window_ends(block,
                                                               monkeypatch):
    monkeypatch.setattr(mellum, "ATTN_BLOCK", block)
    model = small_model()
    layer = model.init(jax.random.PRNGKey(0))["layers"][0]
    positions = packed([(20, 28)])
    x = jax.random.normal(jax.random.PRNGKey(1), (48, HIDDEN))
    moved = x.at[5].add(1.0)            # a token of the first document
    for kind in ("sliding_attention", "full_attention"):
        before = attention_out(model, kind, positions, x, layer)
        after = attention_out(model, kind, positions, moved, layer)
        changed = np.flatnonzero(np.abs(after - before).max(axis=1) > 1e-7)
        # itself and its document's later tokens; the window holds 12 keys
        last = 16 if kind == "sliding_attention" else 19
        np.testing.assert_array_equal(changed, np.arange(5, last + 1))


def test_positions_restart_with_each_document():
    """A document's hidden states do not depend on what is packed before
    it, nor on where in the sequence it starts."""
    model = small_model()
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    doc = rng.normal(size=(28, HIDDEN)).astype(np.float32)
    a = np.concatenate([rng.normal(size=(20, HIDDEN)), doc]).astype(np.float32)
    b = np.concatenate([rng.normal(size=(7, HIDDEN)), doc,
                        rng.normal(size=(13, HIDDEN))]).astype(np.float32)
    out_a = model.hidden_states(params, jnp.asarray(packed([(20, 28)])), a)
    out_b = model.hidden_states(params, jnp.asarray(packed([(7, 28, 13)])), b)
    np.testing.assert_allclose(out_a[20:], out_b[7:35], rtol=2e-5, atol=2e-6)
    document, follows = mellum.packed_mask_terms(packed([(7, 28, 13)]))
    np.testing.assert_array_equal(document[0], [1] * 7 + [2] * 28 + [3] * 13)
    # the last token of a document and of the sequence has no successor
    np.testing.assert_array_equal(
        np.flatnonzero(~np.asarray(follows)), [6, 34, 47])


def test_yarn_frequencies_of_the_published_config():
    """Values worked out by hand from the config's numbers: dimensions that
    turn more than 32 times over 8,192 positions keep `theta ** (-2i/128)`
    (i < 18), those that turn less than once are divided by 16 (i >= 35),
    and between them the two are blended by (i - 18) / 17."""
    freqs, factor = mellum.rotary_frequencies(128, PUBLISHED_YARN)
    assert factor == 1.2772588722239782
    assert freqs.shape == (64,)
    want = {0: 1.0, 17: 0.030634520893224042, 18: 0.024955408670558694,
            19: 0.020329105980970152 * (1 - 1 / 17 + 1 / 17 / 16),
            26: 0.004839421345719893 * (1 - 8 / 17 + 8 / 17 / 16),
            35: 0.0007644969883171747 / 16, 63: 2.455140791131609e-06 / 16}
    for i, value in want.items():
        assert freqs[i] == pytest.approx(value, rel=1e-12), i
    plain, one = mellum.rotary_frequencies(
        128, {"rope_type": "default", "rope_theta": 500000})
    assert one == 1.0 and plain[26] == pytest.approx(0.004839421345719893)
    with pytest.raises(ValueError, match="longrope"):
        mellum.rotary_frequencies(128, {"rope_type": "longrope",
                                        "rope_theta": 1.0})


# ------------------------------------------------------- the training path
def test_the_step_trains_and_holds_the_models_stages():
    model = small_model()
    init_fn, step_fn = make_sparse_train_step(model, "adam", lr=3e-3)
    params = model.init(jax.random.PRNGKey(0))
    state = init_fn(params)
    positions, cats, next_ids = jax.tree.map(jnp.asarray, small_batch())
    text = step_fn.lower(params, state, positions, cats,
                         next_ids).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    held = {s for n in names for s in re.findall(r"det\.([a-z_]+)", n)[-1:]}
    # this model's own blocks of `MODEL_STAGES` (another model's, such as
    # `shortconv` and `mlp`, are in no step of this one)
    assert held >= {"attn", "router", "experts", "head", "lookup", "model",
                    "dense_opt", "apply"}
    assert held <= set(stages.STAGES + stages.MODEL_STAGES)
    paths = [n for n in names if "/" in n]
    assert [n for n in paths if "det." not in n] == []
    # the model's blocks nest inside `model` and win there
    assert any("det.model/" in n and n.count("det.experts") for n in paths)
    losses = []
    for _ in range(30):
        params, state, loss = step_fn(params, state, positions, cats,
                                      next_ids)
        losses.append(float(loss))
    assert losses[0] == pytest.approx(np.log(64), rel=0.05)
    assert losses[-1] < 0.6 * losses[0]
    stats = jax.jit(model.routing_stats)(params, positions, cats)
    assert set(stats) == {"held_pairs_share", "max_expert_load_share"}
    assert stats["held_pairs_share"].shape == (2,)
    assert np.all((0 <= stats["held_pairs_share"])
                  & (stats["held_pairs_share"] <= 1))
    # the two gauges, in the registry and in the catalog
    registry = MetricRegistry()
    said = export_moe_gauges(registry, stats)
    gauges = registry.snapshot()["gauges"]
    assert gauges["moe/held_pairs_share{layer=1}"] == pytest.approx(
        said["held_pairs_share"][1])
    assert "moe/max_expert_load_share{layer=0}" in gauges
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "docs", "observability.md")) as f:
        catalog = f.read()
    assert all(f"`moe/{name}{{layer=}}`" in catalog for name in stats)
    assert all(f"`det.{name}`" in catalog for name in stages.MODEL_STAGES)
