"""Plain reference of one chip's share of Mellum2-12B-A2.5B-Instruct
(``https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json``),
written from the layer equations, importing nothing of the program.

Per layer ``l`` on ``x`` ``[T, hidden]``: ``h = x + Attn_l(RMSNorm(x))``,
``x' = h + MoE(RMSNorm(h))``; then a final RMSNorm, logits over the head's
slice of the vocabulary, and the mean softmax cross-entropy over the tokens
whose successor continues their document.

* ``Attn``: q, k, v projections without bias; rotary embedding of q and k by
  the position inside the document (``x cos + rotate_half(x) sin``, the
  frequencies repeated over both halves); 8 query heads share a key/value
  head; scores scaled by ``1 / sqrt(head_dim)``; a query sees a key iff the
  key lies in the query's document and not after it, ``q - position[q] <= k
  <= q``, and on a ``sliding_attention`` layer ``q - k < sliding_window``;
  softmax over the visible keys; output projection.
  ``sliding_attention`` layers turn by ``theta ** (-2i / d)``;
  ``full_attention`` layers by YaRN's blend of that frequency and itself
  over ``factor`` (a linear ramp between the dimensions that make
  ``beta_fast`` and ``beta_slow`` turns over
  ``original_max_position_embeddings``), cos and sin times
  ``attention_factor``.
* ``MoE``: ``p = softmax(h W_r)`` over all 64 experts, the 8 largest,
  their weights renormalised to sum 1; the output is the sum, over the
  chosen experts **held here**, of ``w_e W_down_e (silu(W_gate_e h) *
  W_up_e h)``. What the absent experts would add is left out, as in the
  program: this is the chip's share, and that partial sum goes on.

Departures from the straightest form, all for memory (the check takes this
loss's gradient beside the program's state, on one chip): a layer is
recomputed in the backward pass (``jax.checkpoint``); attention is taken one
(sequence, key/value head) at a time (``jax.lax.map``), so a masked score
matrix is ``[8, length, length]`` and never ``[32, T, T]``; the experts are a
loop over the held ones, each applied to every token and weighted by the
token's weight for it, zero where it was not chosen: no sort, no gather, no
grouped product.

``inputs`` is ``[sequences, length]`` int32, each token's position inside
its document as the generator packed them; ``labels`` ``[T]`` int32, each
token's successor; ``embs`` one ``[T, hidden]`` array; ``dense`` the
program's parameters without the embedding: ``{"layers": [{"attn_norm",
"mlp_norm", "wq", "wk", "wv", "wo", "experts": {"router", "gate", "up",
"down"}}], "final_norm", "head"}``. Widths and counts are read from the
arrays' shapes; what no shape says (`spec`) from the configuration's file.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

_CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "mellum2-12b-a2.5b.json")


def published_spec():
    """What the arrays' shapes do not say, from the configuration's file."""
    with open(_CONFIG) as f:
        config = json.load(f)
    return {"head_dim": config["head_dim"],
            "layer_types": config["layer_types"],
            "sliding_window": config["sliding_window"],
            "rope_parameters": config["rope_parameters"],
            "num_experts_per_tok": config["num_experts_per_tok"],
            "first_expert_held": config["deployment"]["first_expert_held"],
            "rms_norm_eps": config["rms_norm_eps"]}


def inverse_frequencies(head_dim, rope):
    """(``[head_dim / 2]`` inverse frequencies, factor on cos and sin)."""
    theta = rope["rope_theta"]
    plain = [theta ** (-2.0 * i / head_dim) for i in range(head_dim // 2)]
    if rope["rope_type"] == "default":
        return np.array(plain), 1.0

    def dimension_of(turns):      # whose wavelength makes `turns` turns
        return (head_dim * math.log(rope["original_max_position_embeddings"]
                                    / (turns * 2.0 * math.pi))
                / (2.0 * math.log(theta)))

    low = max(math.floor(dimension_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dimension_of(rope["beta_slow"])), head_dim - 1)
    freqs = []
    for i, f in enumerate(plain):
        interpolated = min(max((i - low) / (high - low), 0.0), 1.0)
        freqs.append(f / rope["factor"] * interpolated
                     + f * (1.0 - interpolated))
    return np.array(freqs), rope["attention_factor"]


def rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x ** 2, axis=-1, keepdims=True) + eps) * weight


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rotary(x, positions, rope):
    """``x [sequences, length, heads, head_dim]``."""
    freqs, factor = inverse_frequencies(x.shape[-1], rope)
    angles = positions[..., None] * jnp.asarray(freqs, jnp.float32)
    angles = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]
    return x * (jnp.cos(angles) * factor) + rotate_half(x) * (
        jnp.sin(angles) * factor)


def attention(layer, x, positions, kind, spec):
    n_seq, length = positions.shape
    d = spec["head_dim"]
    q = (x @ layer["wq"]).reshape(n_seq, length, -1, d)
    k = (x @ layer["wk"]).reshape(n_seq, length, -1, d)
    v = (x @ layer["wv"]).reshape(n_seq, length, -1, d)
    rope = spec["rope_parameters"][kind]
    q, k = rotary(q, positions, rope), rotary(k, positions, rope)
    kv_heads = k.shape[2]
    q = q.reshape(n_seq, length, kv_heads, -1, d)      # heads of a kv head

    at = jnp.arange(length)
    behind = at[:, None] - at[None, :]                  # query - key

    @jax.checkpoint
    def one(args):
        q, k, v, position = args          # [length, group, d], [length, d] x 2
        visible = (behind >= 0) & (behind <= position[:, None])
        if kind == "sliding_attention":
            visible &= behind < spec["sliding_window"]
        scores = jnp.einsum("qgd,kd->gqk", q, k) / math.sqrt(d)
        scores = jnp.where(visible, scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        weights = jnp.exp(scores)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return jnp.einsum("gqk,kd->qgd", weights, v)

    def flat(a):                          # (sequence, kv head) in front
        return jnp.moveaxis(a, 2, 1).reshape((n_seq * kv_heads,) + a.shape[1:2]
                                             + a.shape[3:])

    out = jax.lax.map(one, (flat(q), flat(k), flat(v),
                            jnp.repeat(positions, kv_heads, axis=0)))
    out = jnp.moveaxis(out.reshape(n_seq, kv_heads, length, -1, d), 1, 2)
    return out.reshape(n_seq * length, -1) @ layer["wo"]


def experts_held(experts, x, spec):
    probabilities = jax.nn.softmax(x @ experts["router"], axis=-1)
    top, chosen = jax.lax.top_k(probabilities, spec["num_experts_per_tok"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for local in range(experts["gate"].shape[0]):
        expert = spec["first_expert_held"] + local
        weight = jnp.sum(jnp.where(chosen == expert, top, 0.0), axis=-1)
        inner = jax.nn.silu(x @ experts["gate"][local]) * (
            x @ experts["up"][local])
        out = out + weight[:, None] * (inner @ experts["down"][local])
    return out


def model_loss(dense, embs, inputs, labels, spec):
    (x,) = embs
    positions = inputs
    eps = spec["rms_norm_eps"]

    def block(layer, x, kind):
        h = x + attention(layer, rms_norm(x, layer["attn_norm"], eps),
                          positions, kind, spec)
        return h + experts_held(layer["experts"],
                                rms_norm(h, layer["mlp_norm"], eps), spec)

    for layer, kind in zip(dense["layers"], spec["layer_types"]):
        x = jax.checkpoint(block, static_argnums=2)(layer, x, kind)
    logits = rms_norm(x, dense["final_norm"], eps) @ dense["head"]
    top = jnp.max(logits, axis=-1)
    log_sum = top + jnp.log(jnp.sum(jnp.exp(logits - top[:, None]), axis=-1))
    nll = log_sum - logits[jnp.arange(labels.shape[0]), labels]
    # a token counts iff its successor continues its document in its sequence
    continues = positions[:, 1:] == positions[:, :-1] + 1
    counted = jnp.concatenate(
        [continues, jnp.zeros_like(continues[:, :1])], axis=1).reshape(-1)
    return jnp.sum(jnp.where(counted, nll, 0.0)) / jnp.sum(counted)


def loss(dense, embs, inputs, labels):
    return model_loss(dense, embs, inputs, labels, published_spec())
