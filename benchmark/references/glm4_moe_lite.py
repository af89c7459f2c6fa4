"""Plain reference of one chip's share of GLM-4.7-Flash
(``https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json``,
``model_type`` ``glm4_moe_lite``), written from the layer equations,
importing nothing of the program.

Per layer on ``x`` ``[T, hidden]``: ``h = x + Attn(RMSNorm(x;
input_layernorm))``, ``x' = h + FFN(RMSNorm(h; post_attention_layernorm))``;
after the last layer ``RMSNorm(.; norm)``, logits over the head's slice of
the vocabulary, and the mean softmax cross-entropy over the tokens whose
successor continues their document. No bias in any projection; eps =
``rms_norm_eps`` for every norm, the two inside attention too.

* latent attention, per token ``x``, ``H`` heads (`latent_attention`):
  ``c_q = RMSNorm(x W_qa; q_a_layernorm)`` (``q_lora_rank`` wide);
  ``[q_nope | q_rope] = c_q W_qb`` as ``H x (nope + rope)``;
  ``[c_kv | k_rope] = x W_kva`` (``kv_lora_rank + rope`` wide: ``k_rope`` is
  ONE vector a token); ``c_kv = RMSNorm(c_kv; kv_a_layernorm)``;
  ``[k_nope | v] = c_kv W_kvb`` as ``H x (nope + v_head_dim)``; the rotary
  embedding (``x cos + rotate_half(x) sin``, frequencies ``theta ** (-2i /
  rope)`` repeated over both halves, by the position inside the document) on
  each head's ``q_rope`` and on ``k_rope``; ``q = [q_nope | q_rope]``, ``k =
  [k_nope | k_rope]`` with the same rotated ``k_rope`` for every head;
  scores ``q . k / sqrt(nope + rope)``, scaled after the product; a query
  sees a key iff the key lies in the query's document and not after it;
  softmax; the weighted sum of ``v``; ``W_o``. Nothing is absorbed: the two
  pairs of projections stay two products each with the norm between them.
* dense FFN (a layer that holds ``w1``): ``W_2 (silu(W_1 x) * W_3 x)``.
* sparse FFN (a layer that holds ``experts``): ``s = sigmoid(x W_r)`` over
  all the router's experts; the chosen are the top ``num_experts_per_tok``
  of ``s + b`` (``b`` = ``e_score_correction_bias``: it selects and does not
  weigh; ``n_group`` = ``topk_group`` = 1, so no group limits the choice);
  ``w_e = s_e / (sum of the chosen s + 1e-20)``, times
  ``routed_scaling_factor``; the routed part is the sum, over the chosen
  experts **held here**, of ``w_e W_down_e (silu(W_gate_e x) * W_up_e x)``;
  ``FFN(x) = routed part + Shared(x)``, ``Shared`` one more such SwiGLU that
  every token passes, unweighted. What the absent experts would add is left
  out, as in the program: this is the chip's share. The shared expert is
  whole on every chip.

Departures from the straightest form, all for memory and all
`benchmark/references/lfm2.py`'s (the check takes this loss's gradient,
twice, beside the program's state, on one chip): a layer is recomputed in
the backward pass (``jax.checkpoint``); what is computed token by token (the
latent projections, a dense FFN, the shared expert, the head with its loss)
is taken one sequence at a time (`per_sequence`: ``jax.lax.map`` over
recomputed blocks); attention is taken one (sequence, head) at a time; the
experts are a loop over the held ones inside one sequence's block, each
recomputed, applied to every token of the sequence and weighted by the
token's weight for it, zero where it was not chosen: no sort, no gather, no
grouped product.

``inputs`` is ``[sequences, length]`` int32, each token's position inside
its document as the generator packed them; ``labels`` ``[T]`` int32, each
token's successor; ``embs`` one ``[T, hidden]`` array; ``dense`` the
program's parameters without the embedding: ``{"layers":
[{"input_layernorm", "post_attention_layernorm", "q_a_proj",
"q_a_layernorm", "q_b_proj", "kv_a_proj", "kv_a_layernorm", "kv_b_proj",
"o_proj", then "w1", "w3", "w2" or "experts": {"router", "bias", "gate",
"up", "down"} and "shared": {"gate", "up", "down"}}], "norm", "head"}``.
Widths and counts are read from the arrays' shapes (the two ranks, the heads
as ``q_b_proj``'s columns over a head's ``nope + rope``, a value's width as
what ``kv_b_proj`` gives a head beside ``nope``); what no shape says
(`published_spec`: where a head's width parts into ``nope`` and ``rope``,
theta, the experts a token, the scaling factor, the first expert held, eps)
from the configuration's file.
"""

import json
import math
import os

import jax
import jax.numpy as jnp

_CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "glm-4.7-flash.json")


def published_spec():
    """What the arrays' shapes do not say, from the configuration's file."""
    with open(_CONFIG) as f:
        config = json.load(f)
    return {"qk_nope_head_dim": config["qk_nope_head_dim"],
            "qk_rope_head_dim": config["qk_rope_head_dim"],
            "rope_theta": config["rope_theta"],
            "num_experts_per_tok": config["num_experts_per_tok"],
            "routed_scaling_factor": config["routed_scaling_factor"],
            "first_expert_held": config["deployment"]["first_expert_held"],
            "rms_norm_eps": config["rms_norm_eps"]}


def rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x ** 2, axis=-1, keepdims=True) + eps) * weight


def per_sequence(f, n_seq, *arrays):
    """``f`` over each sequence's block of ``[T, ...]`` arrays, recomputed in
    the backward pass; the results side by side again."""
    blocks = tuple(a.reshape((n_seq, -1) + a.shape[1:]) for a in arrays)
    out = jax.lax.map(lambda block: jax.checkpoint(f)(*block), blocks)
    return out.reshape((-1,) + out.shape[2:])


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rotary(x, position, theta):
    """``x [length, heads, d]`` by ``position [length]``."""
    d = x.shape[-1]
    freqs = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)],
                        jnp.float32)
    angles = position[:, None] * freqs
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    return x * jnp.cos(angles) + rotate_half(x) * jnp.sin(angles)


def latent_attention(layer, x, positions, spec):
    n_seq, length = positions.shape
    nope, rope = spec["qk_nope_head_dim"], spec["qk_rope_head_dim"]
    heads = layer["q_b_proj"].shape[1] // (nope + rope)
    eps = spec["rms_norm_eps"]
    at = jnp.arange(length)
    behind = at[:, None] - at[None, :]                  # query - key

    @jax.checkpoint
    def head(args):
        q, k, v, position = args              # [length, d] x 2, [length, dv]
        visible = (behind >= 0) & (behind <= position[:, None])
        scores = (q @ k.T) / math.sqrt(nope + rope)
        scores = jnp.where(visible, scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        weights = jnp.exp(scores)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return weights @ v

    def one(x, position):                     # one sequence: [length, hidden]
        x = rms_norm(x, layer["input_layernorm"], eps)
        c_q = rms_norm(x @ layer["q_a_proj"], layer["q_a_layernorm"], eps)
        q = (c_q @ layer["q_b_proj"]).reshape(length, heads, nope + rope)
        q_nope, q_rope = q[..., :nope], q[..., nope:]
        kv_a = x @ layer["kv_a_proj"]
        kv_rank = kv_a.shape[-1] - rope
        c_kv, k_rope = kv_a[:, :kv_rank], kv_a[:, kv_rank:]
        c_kv = rms_norm(c_kv, layer["kv_a_layernorm"], eps)
        kv = (c_kv @ layer["kv_b_proj"]).reshape(length, heads, -1)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_rope = rotary(q_rope, position, spec["rope_theta"])
        k_rope = rotary(k_rope[:, None, :], position, spec["rope_theta"])
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, q_rope.shape)], axis=-1)
        out = jax.lax.map(head, (
            jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
            jnp.moveaxis(v, 1, 0),
            jnp.broadcast_to(position, (heads, length))))
        return jnp.moveaxis(out, 0, 1).reshape(length, -1) @ layer["o_proj"]

    return per_sequence(one, n_seq, x, positions.reshape(-1))


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def dense_ffn(layer, x, n_seq, eps):
    def one(x):
        return swiglu(rms_norm(x, layer["post_attention_layernorm"], eps),
                      layer["w1"], layer["w3"], layer["w2"])

    return per_sequence(one, n_seq, x)


def sparse_ffn(layer, x, n_seq, spec):
    """The held experts' routed part plus the shared expert, of normed
    tokens ``x``."""
    experts, shared = layer["experts"], layer["shared"]
    scores = jax.nn.sigmoid(x @ experts["router"])
    _, chosen = jax.lax.top_k(scores + experts["bias"],
                              spec["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * spec["routed_scaling_factor"]

    held = range(experts["gate"].shape[0])
    # [T, held]: a token's weight for each held expert, zero where not chosen
    weights = jnp.stack(
        [jnp.sum(jnp.where(chosen == spec["first_expert_held"] + local, top,
                           0.0), axis=-1) for local in held], axis=-1)

    @jax.checkpoint
    def expert(x, weight, gate, up, down):
        return weight[:, None] * swiglu(x, gate, up, down)

    def one(x, weights):                  # one sequence's tokens
        routed = sum(expert(x, weights[:, local], experts["gate"][local],
                            experts["up"][local], experts["down"][local])
                     for local in held)
        return routed + jax.checkpoint(swiglu)(
            x, shared["gate"], shared["up"], shared["down"])

    return per_sequence(one, n_seq, x, weights)


def model_loss(dense, embs, inputs, labels, spec):
    (x,) = embs
    positions = inputs
    n_seq, eps = positions.shape[0], spec["rms_norm_eps"]

    def block(layer, x):
        h = x + latent_attention(layer, x, positions, spec)
        if "experts" not in layer:            # a leading dense layer
            return h + dense_ffn(layer, h, n_seq, eps)
        return h + sparse_ffn(
            layer, rms_norm(h, layer["post_attention_layernorm"], eps),
            n_seq, spec)

    for layer in dense["layers"]:
        x = jax.checkpoint(block)(layer, x)

    def token_losses(x, labels):          # one sequence's tokens
        logits = rms_norm(x, dense["norm"], eps) @ dense["head"]
        top = jnp.max(logits, axis=-1)
        log_sum = top + jnp.log(jnp.sum(jnp.exp(logits - top[:, None]),
                                        axis=-1))
        return log_sum - logits[jnp.arange(labels.shape[0]), labels]

    nll = per_sequence(token_losses, n_seq, x, labels)
    # a token counts iff its successor continues its document in its sequence
    continues = positions[:, 1:] == positions[:, :-1] + 1
    counted = jnp.concatenate(
        [continues, jnp.zeros_like(continues[:, :1])], axis=1).reshape(-1)
    return jnp.sum(jnp.where(counted, nll, 0.0)) / jnp.sum(counted)


def loss(dense, embs, inputs, labels):
    return model_loss(dense, embs, inputs, labels, published_spec())
