"""Plain reference of one chip's share of SDAR-30B-A3B-Chat
(``https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json``,
``model_type`` ``sdar_moe``) trained as a block-diffusion model, written
from the equations (SDAR, arXiv:2510.06303; BD3-LM's vectorised training,
arXiv:2503.09573), importing nothing of the program.

A sequence ``x0`` of ``L`` packed tokens is read as ``2L`` positions,
``[xt ; x0]``: the noisy copy and the clean one, both at the token's
position inside its document for the rotary. Blocks are ``block_length``
tokens counted from each document's start. Per layer on ``x`` ``[2L,
hidden]``: ``h = x + Attn(RMSNorm(x; input_layernorm))``, ``x' = h +
MoE(RMSNorm(h; post_attention_layernorm))``; after the last layer, at the
noisy positions alone, ``RMSNorm(.; norm)``, logits over the head's slice of
the vocabulary, and ``sum_i w_i CE(logits_i, x0_i) / T``, ``w_i`` the label
(``1 / p`` of the block at a masked position, 0 elsewhere). No bias in any
projection; eps = ``rms_norm_eps`` for every norm.

* ``Attn``: q, k, v projections; q and k RMS-normed over each head's
  ``head_dim`` (``q_norm``, ``k_norm``), then the rotary embedding (``x cos
  + rotate_half(x) sin``, frequencies ``theta ** (-2i / d)`` repeated over
  both halves); ``heads / kv_heads`` query heads share a key/value head;
  scores ``q . k / sqrt(head_dim)``; the mask over ``[2L, 2L]``, with
  ``doc`` and ``blk`` of a position: a noisy query sees a noisy key iff same
  document and same block, a clean key iff same document and an earlier
  block; a clean query sees a clean key iff same document and a block not
  after its own, and never a noisy key; softmax; ``W_o``.
* ``MoE``: ``p = softmax(x W_r)`` over all the router's experts, the top
  ``num_experts_per_tok``, their weights divided by their sum; the output is
  the sum, over the chosen experts **held here**, of ``w_e W_down_e
  (silu(W_gate_e x) * W_up_e x)``. What the absent experts would add is
  left out, as in the program: this is the chip's share. No shared expert.

The whole of every layer is computed for both streams, the last one too:
the loss reads the noisy stream.

Departures from the straightest form, all for memory and all
`benchmark/references/glm4_moe_lite.py`'s (the check takes this loss's
gradient, twice, beside the program's state, on one chip): a layer is
recomputed in the backward pass (``jax.checkpoint``); what is computed token
by token is taken one sequence (``2L`` positions) at a time (`per_sequence`);
attention is taken one (sequence, query head) at a time; the experts are a
loop over the held ones inside one sequence's block, each recomputed,
applied to every position and weighted by the position's weight for it,
zero where it was not chosen: no sort, no gather, no grouped product.

``inputs`` is ``[sequences, 2, length]`` int32: each token's position
inside its document and its clean id; ``labels`` ``[T]`` f32, the loss
weights; ``embs`` one ``[2T, hidden]`` array, a sequence's noisy stream then
its clean one; ``dense`` the program's parameters without the embedding:
``{"layers": [{"input_layernorm", "post_attention_layernorm", "wq", "wk",
"wv", "wo", "q_norm", "k_norm", "experts": {"router", "gate", "up",
"down"}}], "norm", "head"}``. Widths and counts are read from the arrays'
shapes (a head's width as ``q_norm``'s); what no shape says
(`published_spec`) from the configuration's file.
"""

import json
import math
import os

import jax
import jax.numpy as jnp

_CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "sdar-30b-a3b-chat.json")


def published_spec():
    """What the arrays' shapes do not say, from the configuration's file."""
    with open(_CONFIG) as f:
        config = json.load(f)
    return {"rope_theta": config["rope_theta"],
            "block_length": config["block_length"],
            "num_experts_per_tok": config["num_experts_per_tok"],
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
    """``x [positions, heads, d]`` by ``position [positions]``."""
    d = x.shape[-1]
    freqs = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)],
                        jnp.float32)
    angles = position[:, None] * freqs
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    return x * jnp.cos(angles) + rotate_half(x) * jnp.sin(angles)


def block_diffusion_mask(position, block):
    """``[2L, 2L]`` bool over ``[xt ; x0]`` of one sequence whose tokens sit
    at ``position [L]`` inside their documents."""
    doc = jnp.cumsum(position == 0)
    blk = position // block
    same = doc[:, None] == doc[None, :]
    qb, kb = blk[:, None], blk[None, :]
    noisy_noisy = same & (qb == kb)
    noisy_clean = same & (kb < qb)
    clean_clean = same & (kb <= qb)
    clean_noisy = jnp.zeros_like(same)
    return jnp.concatenate([
        jnp.concatenate([noisy_noisy, noisy_clean], axis=1),
        jnp.concatenate([clean_noisy, clean_clean], axis=1)], axis=0)


def attention(layer, x, inputs, spec):
    n_seq = inputs.shape[0]
    d, eps = layer["q_norm"].shape[0], spec["rms_norm_eps"]
    heads = layer["wq"].shape[1] // d
    group = heads // (layer["wk"].shape[1] // d)

    def one(x, position):                  # one sequence: [2L, hidden], [L]
        x = rms_norm(x, layer["input_layernorm"], eps)
        both = jnp.concatenate([position, position])
        q = rms_norm((x @ layer["wq"]).reshape(x.shape[0], -1, d),
                     layer["q_norm"], eps)
        k = rms_norm((x @ layer["wk"]).reshape(x.shape[0], -1, d),
                     layer["k_norm"], eps)
        v = (x @ layer["wv"]).reshape(k.shape)
        q = rotary(q, both, spec["rope_theta"])
        k = rotary(k, both, spec["rope_theta"])
        visible = block_diffusion_mask(position, spec["block_length"])

        @jax.checkpoint
        def head(args):
            q, kv_head = args                          # [2L, d], index
            k_h, v_h = k[:, kv_head], v[:, kv_head]
            scores = (q @ k_h.T) / math.sqrt(d)
            scores = jnp.where(visible, scores, -jnp.inf)
            scores = scores - jnp.max(scores, axis=-1, keepdims=True)
            weights = jnp.exp(scores)
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
            return weights @ v_h

        out = jax.lax.map(head, (jnp.moveaxis(q, 1, 0),
                                 jnp.arange(heads) // group))
        return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1) @ layer["wo"]

    return per_sequence(one, n_seq, x, inputs[:, 0].reshape(-1))


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def experts_held(experts, x, n_seq, spec):
    """The held experts' part of normed positions ``x``."""
    probabilities = jax.nn.softmax(x @ experts["router"], axis=-1)
    top, chosen = jax.lax.top_k(probabilities, spec["num_experts_per_tok"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    held = range(experts["gate"].shape[0])
    # [2T, held]: a position's weight for each held expert, 0 where not chosen
    weights = jnp.stack(
        [jnp.sum(jnp.where(chosen == spec["first_expert_held"] + local, top,
                           0.0), axis=-1) for local in held], axis=-1)

    @jax.checkpoint
    def expert(x, weight, gate, up, down):
        return weight[:, None] * swiglu(x, gate, up, down)

    def one(x, weights):                  # one sequence's positions
        return sum(expert(x, weights[:, local], experts["gate"][local],
                          experts["up"][local], experts["down"][local])
                   for local in held)

    return per_sequence(one, n_seq, x, weights)


def model_loss(dense, embs, inputs, labels, spec):
    (x,) = embs
    n_seq, _, length = inputs.shape
    eps = spec["rms_norm_eps"]

    def block(layer, x):
        h = x + attention(layer, x, inputs, spec)
        return h + experts_held(
            layer["experts"], rms_norm(h, layer["post_attention_layernorm"],
                                       eps), n_seq, spec)

    for layer in dense["layers"]:
        x = jax.checkpoint(block)(layer, x)
    noisy = x.reshape(n_seq, 2, length, -1)[:, 0].reshape(n_seq * length, -1)

    def token_losses(x, targets):          # one sequence's noisy positions
        logits = rms_norm(x, dense["norm"], eps) @ dense["head"]
        top = jnp.max(logits, axis=-1)
        log_sum = top + jnp.log(jnp.sum(jnp.exp(logits - top[:, None]),
                                        axis=-1))
        return log_sum - logits[jnp.arange(targets.shape[0]), targets]

    nll = per_sequence(token_losses, n_seq, noisy, inputs[:, 1].reshape(-1))
    return jnp.sum(labels * nll) / labels.shape[0]


def loss(dense, embs, inputs, labels):
    return model_loss(dense, embs, inputs, labels, published_spec())
