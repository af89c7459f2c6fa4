"""`models/glm4_moe_lite.py` and what it asks of `layers/experts.py` at a
small size on the CPU: the model against its plain reference
(`benchmark/references/glm4_moe_lite.py`) over a dense + sparse stack, and a
bfloat16 mutation that must fail the same comparison; the eight shares'
routed parts plus the shared expert counted once against the uncut layer;
the routed scaling factor and a selection bias that selects and does not
weigh; latent attention's one rotary key a token, its unrotated part, its
document mask and a value width that is not the key width; the model's
stage scopes and its gauges.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import glm4_moe_lite as reference
from distributed_embeddings_tpu.layers.experts import ExpertLayer
from distributed_embeddings_tpu.models import glm4_moe_lite as glm
from distributed_embeddings_tpu.models import mellum
from distributed_embeddings_tpu.obs import stages
from distributed_embeddings_tpu.obs.instrument import export_moe_gauges
from distributed_embeddings_tpu.obs.registry import MetricRegistry
from distributed_embeddings_tpu.training import make_sparse_train_step

HIDDEN, WIDTH, TOTAL, TOP_K, SCALE = 32, 16, 16, 4, 1.8
HEADS, Q_RANK, KV_RANK, NOPE, ROPE, V_DIM = 4, 12, 8, 6, 4, 8
ROPE_ENTRY = {"rope_type": "default", "rope_theta": 10000}
# published layer 0 and two of the layers behind it
PATTERN = (("mla", "dense"), ("mla", "sparse"), ("mla", "sparse"))
SPEC = {   # a test's spec: what `reference.published_spec` reads from file
    "qk_nope_head_dim": NOPE, "qk_rope_head_dim": ROPE, "rope_theta": 10000,
    "num_experts_per_tok": TOP_K, "routed_scaling_factor": SCALE,
    "first_expert_held": 4, "rms_norm_eps": 1e-5}


def small_model(layers=PATTERN, held=range(4, 8), **sizes):
    args = dict(
        vocab_rows=64, hidden=HIDDEN, num_heads=HEADS, q_lora_rank=Q_RANK,
        kv_lora_rank=KV_RANK, qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE,
        v_head_dim=V_DIM, layers=layers, rope=ROPE_ENTRY, dense_width=48,
        num_experts_total=TOTAL, held_experts=held, top_k=TOP_K,
        expert_width=WIDTH, routed_scale=SCALE, shared_width=WIDTH,
        bias_range=0.01)
    return glm.Glm4MoeLite(**{**args, **sizes})


def packed(lengths_per_sequence):
    return np.stack([np.concatenate([np.arange(n) for n in lengths])
                     for lengths in lengths_per_sequence]).astype(np.int32)


def scaled(params):
    """Weights large enough that every block matters to the loss, and a
    bias large enough to choose for some tokens."""
    params = jax.tree.map(lambda p: p * 8.0 if p.ndim > 1 else p, params)
    for layer in params["layers"]:
        if "experts" in layer:
            layer["experts"]["bias"] = layer["experts"]["bias"] * 8.0
    return params


def small_case(seed, lengths=((20, 28), (10, 30, 8)), **sizes):
    model = small_model(**sizes)
    params = scaled(model.init(jax.random.PRNGKey(seed)))
    rng = np.random.default_rng(seed)
    positions = packed(lengths)
    ids = rng.integers(0, 64, positions.size).astype(np.int32)
    next_ids = rng.integers(0, 64, positions.size).astype(np.int32)
    return model, params, positions, [ids], next_ids


# ------------------------------------------ the reference against the model
def program_side(model, params, positions, cats, next_ids):
    """(loss, (gradient of the dense tree, of the table)) of the program's
    model, under `highest`."""
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(model.loss_fn)(
            params, positions, cats, next_ids)
    (table,) = model.embedding.get_weights(grads.pop("embedding"))
    return loss, (grads, jnp.asarray(table))


def reference_side(model, params, positions, cats, next_ids):
    """The same of the plain reference; its gradient of the embedded tokens
    is summed into their rows."""
    dense = {k: v for k, v in params.items() if k != "embedding"}
    (table,) = model.embedding.get_weights(params["embedding"])
    with jax.default_matmul_precision("highest"):
        loss, (g_dense, g_x) = jax.value_and_grad(
            lambda dense, x: reference.model_loss(dense, [x], positions,
                                                  next_ids, SPEC),
            argnums=(0, 1))(dense, jnp.asarray(table)[cats[0]])
    g_table = np.zeros_like(table)
    np.add.at(g_table, cats[0], np.asarray(g_x))
    return loss, (g_dense, jnp.asarray(g_table))


@functools.lru_cache(maxsize=None)
def reference_of_seed(seed):
    return reference_side(*small_case(seed))


def worst_relative(got, want):
    """The worst leaf's largest error over its largest entry; a leaf whose
    gradient is zero on both sides (the selection bias) counts as 0."""
    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(w)))
        off = float(jnp.max(jnp.abs(g - w)))
        worst = max(worst, off / scale if scale else off)
    return worst


LOSS_RTOL, GRAD_RTOL = 1e-5, 2e-4


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_model_agrees_with_the_plain_reference(seed):
    """Loss to 1e-5 and every gradient to 2e-4 of its leaf's largest entry
    (`tests/test_lfm2.py`'s limits): f32 summation order over 96 tokens,
    three layers deep, reads 1e-6. No gradient reaches a selection bias, on
    either side."""
    loss, grads = program_side(*small_case(seed))
    want_loss, want_grads = reference_of_seed(seed)
    assert float(loss) == pytest.approx(float(want_loss), rel=LOSS_RTOL)
    assert float(loss) > 2.0
    assert worst_relative(grads, want_grads) < GRAD_RTOL
    for side in (grads[0], want_grads[0]):
        assert ["experts" in layer for layer in side["layers"]] == [
            False, True, True]
        biases = [layer["experts"]["bias"] for layer in side["layers"][1:]]
        assert not any(np.any(np.asarray(b)) for b in biases)
        assert all(np.any(np.asarray(leaf)) for layer in side["layers"]
                   for name, leaf in layer.items() if name != "experts"
                   for leaf in jax.tree.leaves(leaf))


def test_a_bfloat16_model_fails_the_same_comparison_tenfold():
    """The mutation: the program's matrices rounded to bfloat16, as a model
    that kept its weights in the lower precision would hold them."""
    model, params, *batch = small_case(0)
    rounded = jax.tree.map(
        lambda p: p.astype(jnp.bfloat16).astype(p.dtype) if p.ndim > 1 else p,
        {k: v for k, v in params.items() if k != "embedding"})
    loss, grads = program_side(
        model, dict(rounded, embedding=params["embedding"]), *batch)
    want_loss, want_grads = reference_of_seed(0)
    # by one of the comparison's two limits, tenfold: the gradients'
    assert worst_relative(grads, want_grads) > 10 * GRAD_RTOL
    assert abs(float(loss) - float(want_loss)) > LOSS_RTOL * float(want_loss)


@pytest.mark.parametrize("mutation, factor", [
    ("scale_of_one", 100), ("no_shared_expert", 100),
    ("a_rotary_key_per_head", 100)])
def test_a_mechanism_left_out_fails_the_same_comparison(mutation, factor,
                                                        monkeypatch):
    """One mechanism taken out of the PROGRAM, the reference as it is: the
    routed scaling factor set to 1, the shared expert dropped, the rotary
    key taken per head (each head rotating and reading its own slice of a
    wider `kv_a_proj`, as a plain multi-head attention would). Each fails
    the gradients' limit a hundredfold."""
    model, params, *batch = small_case(0)
    _, want_grads = reference_of_seed(0)
    if mutation == "scale_of_one":
        model.experts.routed_scale = 1
    elif mutation == "no_shared_expert":
        monkeypatch.setattr(
            glm, "swiglu", lambda x, gate, up, down: (
                jnp.zeros_like(x) if gate.shape[1] == WIDTH
                else mellum.swiglu(x, gate, up, down)))
    else:
        def per_head(x, positions, inv_freq, factor):
            if x.shape[2] == 1:           # the one key: every head its own
                x = jnp.concatenate(
                    [jnp.roll(x, head, axis=-1) for head in range(HEADS)],
                    axis=2)
            return mellum._rotate(x, positions, inv_freq, factor)
        monkeypatch.setattr(glm, "_rotate", per_head)
    _, grads = program_side(model, params, *batch)
    assert worst_relative(grads, want_grads) > factor * GRAD_RTOL


# ------------------------------------------------- shares, scale and bias
def sparse_layer(held=range(TOTAL)):
    return small_model(layers=(("mla", "sparse"),), held=held)


def test_the_eight_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """model-configs, section 4: every share routes over all experts alike
    and computes its own experts' routed part; what every chip computes
    alike, the shared expert, is counted once. Against the plain reference
    given the whole layer."""
    whole = sparse_layer()
    layer = scaled({"layers": [whole._init_layer(jax.random.PRNGKey(0),
                                                 "sparse")]})["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (96, HIDDEN))
    normed = mellum._rms_norm(x, layer["post_attention_layernorm"], 1e-5)
    shared = mellum.swiglu(normed, **layer["shared"])
    parts = []
    for first in range(0, TOTAL, 2):
        share = sparse_layer(range(first, first + 2))
        cut = {"router": layer["experts"]["router"],
               "bias": layer["experts"]["bias"],
               **{k: layer["experts"][k][first:first + 2]
                  for k in ("gate", "up", "down")}}
        parts.append(share.experts(cut, normed))
        # a share's block is its routed part, the shared expert and the
        # stream
        np.testing.assert_allclose(
            share._sparse_mlp(dict(layer, experts=cut), x),
            x + parts[-1] + shared, rtol=1e-5, atol=1e-6)
    assert len(parts) == 8
    assert all(0 < float(jnp.abs(p).max()) for p in parts)
    want = reference.sparse_ffn(layer, normed, 2,
                                dict(SPEC, first_expert_held=0))
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_allclose(whole._sparse_mlp(layer, x), x + want,
                               rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(shared).max()) > 0.1 * float(jnp.abs(want).max())


def test_the_scaling_factor_multiplies_and_the_bias_selects():
    plain = ExpertLayer(HIDDEN, WIDTH, TOTAL, range(4, 8), TOP_K,
                        router="sigmoid", norm_eps=glm.NORM_EPS)
    scaled_layer = ExpertLayer(HIDDEN, WIDTH, TOTAL, range(4, 8), TOP_K,
                               router="sigmoid", routed_scale=SCALE,
                               norm_eps=glm.NORM_EPS)
    router = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (HIDDEN, TOTAL))
    x = jax.random.normal(jax.random.PRNGKey(3), (256, HIDDEN))
    scores = np.asarray(jax.nn.sigmoid(x @ router))
    bias = jnp.asarray(np.random.default_rng(4).uniform(-0.2, 0.2, TOTAL),
                       jnp.float32)
    one, many = plain.route(router, x, bias), scaled_layer.route(router, x,
                                                                  bias)
    # the chosen set follows score + bias, whatever the scale ...
    np.testing.assert_array_equal(one.experts, many.experts)
    np.testing.assert_array_equal(
        many.experts, np.argsort(-(scores + np.asarray(bias)), axis=1,
                                 kind="stable")[:, :TOP_K])
    unbiased = scaled_layer.route(router, x).experts
    same = np.all(np.sort(unbiased, 1) == np.sort(many.experts, 1), 1)
    assert 0.2 < same.mean() < 0.95          # the bias chose for some tokens
    # ... the weights follow the scores alone, renormalised over their sum
    # plus 1e-20, times the factor: they sum to it
    picked = np.take_along_axis(scores, np.asarray(many.experts), axis=1)
    np.testing.assert_allclose(
        many.weights, SCALE * picked / picked.sum(axis=1, keepdims=True),
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(many.weights).sum(1), SCALE,
                               rtol=1e-6)
    np.testing.assert_array_equal(
        many.weights, np.asarray(one.weights) * np.float32(SCALE))
    # and the held part is linear in them
    params = dict(plain.init(jax.random.PRNGKey(5), std=0.3), bias=bias)
    np.testing.assert_allclose(scaled_layer(params, x),
                               SCALE * plain(params, x), rtol=1e-5, atol=1e-6)
    grads = jax.grad(lambda p: jnp.sum(scaled_layer(p, x) ** 2))(params)
    assert not np.any(np.asarray(grads["bias"]))
    assert np.any(np.asarray(grads["router"]))
    # the defaults are the other two models': no factor, 1e-6
    default = ExpertLayer(HIDDEN, WIDTH, TOTAL, range(4, 8), TOP_K,
                          router="sigmoid")
    assert (default.routed_scale, default.norm_eps) == (1.0, 1e-6)
    np.testing.assert_allclose(
        default.route(router, x, bias).weights,
        picked / (picked.sum(axis=1, keepdims=True) + 1e-6), rtol=1e-6)
    # the softmax rule takes the factor too
    soft = ExpertLayer(HIDDEN, WIDTH, TOTAL, range(4, 8), TOP_K,
                       routed_scale=2.5)
    np.testing.assert_allclose(
        np.asarray(soft.route(router, x).weights).sum(1), 2.5, rtol=1e-6)


def test_a_layer_kind_outside_the_declared_ones_is_refused():
    with pytest.raises(ValueError, match="a mixer is one of"):
        small_model(layers=(("full_attention", "sparse"),))
    with pytest.raises(ValueError, match="an MLP one of"):
        small_model(layers=(("mla", "shared"),))


# ------------------------------------------------------- latent attention
def attention_layer(seed=0, **sizes):
    model = small_model(layers=(("mla", "dense"),), **sizes)
    layer = model.init(jax.random.PRNGKey(seed))["layers"][0]
    layer = dict(layer, **{k: 8 * layer[k] for k in (
        "q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj")})
    layer["q_a_layernorm"] = jnp.linspace(0.5, 1.5, layer["q_a_layernorm"].size)
    layer["kv_a_layernorm"] = jnp.linspace(2.0, 1.0,
                                           layer["kv_a_layernorm"].size)
    return model, layer


def by_hand(layer, x, positions, heads=HEADS, nope=NOPE, rope=ROPE, v_dim=V_DIM,
            theta=10000.0):
    """Latent attention of ONE sequence in float64 numpy, token by token and
    head by head, from the equations."""
    f = {k: np.asarray(v, np.float64) for k, v in layer.items()
         if not isinstance(v, dict)}
    x = np.asarray(x, np.float64)

    def normed(a, weight):
        return a / np.sqrt((a ** 2).mean(-1, keepdims=True) + 1e-5) * weight

    def rotated(a, position):             # [..., rope], halves paired
        angle = position * theta ** (-np.arange(rope // 2) / (rope / 2.0))
        a1, a2 = a[..., :rope // 2], a[..., rope // 2:]
        return np.concatenate([a1 * np.cos(angle) - a2 * np.sin(angle),
                               a2 * np.cos(angle) + a1 * np.sin(angle)], -1)

    length = x.shape[0]
    c_q = normed(x @ f["q_a_proj"], f["q_a_layernorm"])
    q = (c_q @ f["q_b_proj"]).reshape(length, heads, nope + rope)
    kv_a = x @ f["kv_a_proj"]
    c_kv = normed(kv_a[:, :-rope], f["kv_a_layernorm"])
    k_rope = np.stack([rotated(kv_a[t, -rope:], positions[t])
                       for t in range(length)])
    kv = (c_kv @ f["kv_b_proj"]).reshape(length, heads, nope + v_dim)
    out = np.zeros((length, heads, v_dim))
    starts = np.arange(length) - positions           # a token's document
    for t in range(length):
        seen = [s for s in range(t + 1) if starts[s] == starts[t]]
        for head in range(heads):
            q_t = np.concatenate([q[t, head, :nope],
                                  rotated(q[t, head, nope:], positions[t])])
            scores = np.array([
                q_t @ np.concatenate([kv[s, head, :nope], k_rope[s]])
                for s in seen]) / np.sqrt(nope + rope)
            weights = np.exp(scores - scores.max())
            weights /= weights.sum()
            out[t, head] = sum(w * kv[s, head, nope:]
                               for w, s in zip(weights, seen))
    return out.reshape(length, -1) @ f["o_proj"], k_rope


@pytest.mark.parametrize("sizes", [
    {}, {"v_head_dim": 5, "qk_nope_head_dim": 3, "qk_rope_head_dim": 6}],
    ids=["values_as_wide_as_keys", "values_5_keys_9"])
def test_latent_attention_is_the_equations_written_out(sizes):
    """Against float64 arithmetic by hand over two packed documents: the
    bottleneck's norms, the per-head expansion, one rotary key a token under
    every head, scores over the head's whole width scaled by its root. The
    second case runs a value width that is not the key width, and another
    split of the key, through the same function."""
    model, layer = attention_layer(**sizes)
    positions = packed([(7, 9)])
    x = jax.random.normal(jax.random.PRNGKey(1), (16, HIDDEN))
    document, _ = mellum.packed_mask_terms(jnp.asarray(positions))
    got = model._attention(layer, x, jnp.asarray(positions), document)
    want, _ = by_hand(layer, x, positions[0], nope=model.latent[
        "qk_nope_head_dim"], rope=model.latent["qk_rope_head_dim"],
        v_dim=model.latent["v_head_dim"])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)
    # and the plain reference says the same of the whole half-block
    spec = dict(SPEC, qk_nope_head_dim=model.latent["qk_nope_head_dim"],
                qk_rope_head_dim=model.latent["qk_rope_head_dim"])
    np.testing.assert_allclose(
        model._attend(layer, x, jnp.asarray(positions), document),
        x + reference.latent_attention(layer, x, jnp.asarray(positions), spec),
        rtol=2e-5, atol=2e-6)


def test_one_rotary_key_serves_every_head_and_the_rest_is_not_rotated():
    model, layer = attention_layer()
    positions = jnp.asarray(packed([(7, 9)]))
    x = jax.random.normal(jax.random.PRNGKey(1), (16, HIDDEN))
    q, k, v = glm.latent_qkv(layer, x, positions, **model.latent)
    assert q.shape == k.shape == (1, 16, HEADS, NOPE + ROPE)
    assert v.shape == (1, 16, HEADS, V_DIM)
    # the key's rotary part: the same 4 numbers under all 4 heads, and what
    # hand arithmetic gives for the one vector a token
    for head in range(1, HEADS):
        np.testing.assert_array_equal(k[..., head, NOPE:], k[..., 0, NOPE:])
    _, k_rope = by_hand(layer, x, np.asarray(positions[0]))
    np.testing.assert_allclose(k[0, :, 0, NOPE:], k_rope, rtol=2e-5,
                               atol=1e-6)
    # the unrotated parts do not see the positions; the rotary parts do,
    # but where a document starts (position 0: no turn)
    later = positions + 3
    q3, k3, v3 = glm.latent_qkv(layer, x, later, **model.latent)
    np.testing.assert_array_equal(q3[..., :NOPE], q[..., :NOPE])
    np.testing.assert_array_equal(k3[..., :NOPE], k[..., :NOPE])
    np.testing.assert_array_equal(v3, v)
    assert float(jnp.abs(q3[..., NOPE:] - q[..., NOPE:]).max()) > 1e-2
    assert float(jnp.abs(k3[..., NOPE:] - k[..., NOPE:]).max()) > 1e-2
    # a key's heads differ in their unrotated part
    assert float(jnp.abs(k[..., 0, :NOPE] - k[..., 1, :NOPE]).max()) > 1e-2
    # sizes that do not fit the arrays are refused by name
    with pytest.raises(ValueError, match="qk_rope_head_dim"):
        glm.latent_qkv(layer, x, positions,
                       **dict(model.latent, kv_lora_rank=KV_RANK - 2))
    with pytest.raises(ValueError, match="q_lora_rank"):
        glm.latent_qkv(layer, x, positions,
                       **dict(model.latent, q_lora_rank=Q_RANK + 1))


def test_a_key_in_another_document_is_never_seen():
    model, layer = attention_layer()
    positions = jnp.asarray(packed([(20, 28)]))
    document, _ = mellum.packed_mask_terms(positions)
    x = jax.random.normal(jax.random.PRNGKey(1), (48, HIDDEN))
    before = np.asarray(model._attend(layer, x, positions, document))
    for token, changed in ((19, [19]), (5, list(range(5, 20))),
                           (20, list(range(20, 48)))):
        after = np.asarray(model._attend(layer, x.at[token].add(1.0),
                                         positions, document))
        np.testing.assert_array_equal(
            np.flatnonzero(np.abs(after - before).max(axis=1) > 1e-7), changed)


def test_positions_restart_with_each_document():
    """A document's hidden states do not depend on what is packed before
    it, nor on where in the sequence it starts: through latent attention and
    both kinds of MLP."""
    model = small_model()
    params = scaled(model.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(1)
    doc = rng.normal(size=(28, HIDDEN)).astype(np.float32)
    a = np.concatenate([rng.normal(size=(20, HIDDEN)), doc]).astype(np.float32)
    b = np.concatenate([rng.normal(size=(7, HIDDEN)), doc,
                        rng.normal(size=(13, HIDDEN))]).astype(np.float32)
    out_a = model.hidden_states(params, jnp.asarray(packed([(20, 28)])), a)
    out_b = model.hidden_states(params, jnp.asarray(packed([(7, 28, 13)])), b)
    np.testing.assert_allclose(out_a[20:], out_b[7:35], rtol=2e-5, atol=2e-5)


# ------------------------------------------------------- the training path
def test_the_step_trains_and_holds_latent_and_shared():
    model = small_model()
    init_fn, step_fn = make_sparse_train_step(model, "adam", lr=3e-3)
    params = model.init(jax.random.PRNGKey(0))
    biases = [np.asarray(layer["experts"]["bias"])
              for layer in params["layers"] if "experts" in layer]
    assert all(np.any(b) and np.abs(b).max() <= model.bias_range
               for b in biases)
    # the residual writers are drawn for the depth the model is told
    assert model.residual_std == pytest.approx(0.02 / 6 ** 0.5)
    assert float(jnp.std(params["layers"][1]["shared"]["down"])) == (
        pytest.approx(model.residual_std, rel=0.1))
    assert float(jnp.std(params["layers"][1]["o_proj"])) == (
        pytest.approx(model.residual_std, rel=0.1))
    # a routed expert's writer stands behind the routed scaling factor
    assert float(jnp.std(params["layers"][1]["experts"]["down"])) == (
        pytest.approx(model.residual_std / SCALE, rel=0.1))
    state = init_fn(params)
    _, _, positions, cats, next_ids = small_case(0)
    positions, cats, next_ids = jax.tree.map(jnp.asarray,
                                             (positions, cats, next_ids))
    text = step_fn.lower(params, state, positions, cats,
                         next_ids).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    held = {s for n in names for s in re.findall(r"det\.([a-z_]+)", n)[-1:]}
    assert held >= {"attn", "latent", "mlp", "router", "experts", "shared",
                    "head", "lookup", "model", "dense_opt", "apply"}
    assert {"latent", "shared"} <= set(stages.MODEL_STAGES)
    assert held <= set(stages.STAGES + stages.MODEL_STAGES)
    paths = [n for n in names if "/" in n]
    assert [n for n in paths if "det." not in n] == []
    # `latent` nests inside `attn` and wins there; `shared` lies beside the
    # expert layer's two
    assert any("det.model/" in n and "det.attn/det.latent/" in n
               for n in paths)
    assert any("transpose(" in n and n.count("det.shared") for n in paths)
    assert not any("det.experts/det.shared" in n or "det.shared/det.experts"
                   in n for n in paths)
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
    assert stats["held_pairs_share"].shape == (2,)      # the sparse layers
    registry = MetricRegistry()
    said = export_moe_gauges(registry, stats)
    gauges = registry.snapshot()["gauges"]
    assert gauges["moe/held_pairs_share{layer=1}"] == pytest.approx(
        said["held_pairs_share"][1])
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "docs", "observability.md")) as f:
        catalog = f.read()
    assert all(f"`det.{name}`" in catalog for name in stages.MODEL_STAGES)


def test_the_backward_pass_takes_the_scores_twice_and_not_three_times():
    """`_mix` keeps a block's input and attention's output and takes the
    latent products again; the forward's scores, whose result is kept, are
    not taken a third time (a plain `jax.checkpoint` around the half-block
    would)."""
    model, layer = attention_layer()
    positions = jnp.asarray(packed([(20, 28)]))
    document, _ = mellum.packed_mask_terms(positions)
    x = jax.random.normal(jax.random.PRNGKey(1), (48, HIDDEN))

    def count(jaxpr, name):
        total = 0
        for eqn in jaxpr.eqns:
            total += eqn.primitive.name == name
            for value in eqn.params.values():
                for inner in (value if isinstance(value, (list, tuple))
                              else [value]):
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        total += count(inner, name)
        return total

    def passes(half_block):
        return count(jax.make_jaxpr(jax.grad(
            lambda layer, x: jnp.sum(half_block(layer, x, positions,
                                                document) ** 2)))(
                layer, x).jaxpr, "exp")

    assert passes(model._mix) == 2
    assert passes(jax.checkpoint(model._attend)) == 3
