"""Plain reference of one chip's share of LFM2-24B-A2B
(``https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json``,
``model_type`` ``lfm2_moe``), written from the layer equations, importing
nothing of the program.

Per layer on ``x`` ``[T, hidden]``: ``h = x + Mixer(RMSNorm(x;
operator_norm))``, ``x' = h + FFN(RMSNorm(h; ffn_norm))``; after the last
layer ``RMSNorm(.; embedding_norm)``, logits over the head's slice of the
vocabulary, and the mean softmax cross-entropy over the tokens whose
successor continues their document. No bias anywhere; eps = ``norm_eps``.

* ``conv`` mixer (``layer_types``): ``[B, C, u] = split3(x W_in)``, ``v = B *
  u``, ``c_t = sum_{j=0..K-1} w[:, j] * v_{t-(K-1-j)}`` per channel (``K`` =
  ``conv_L_cache`` = 3 taps, causal, depthwise), where ``v_s`` is 0 for a
  position ``s`` before the start of ``t``'s document: each packed document
  is a sequence of its own, so the tap at distance ``d`` counts only where
  ``t``'s position inside its document is at least ``d``. Output ``(C * c)
  W_out``.
* ``full_attention`` mixer: q (32 heads), k, v (8 heads) projections; q and
  k each RMS-normed over the head's 64 (``q_norm``, ``k_norm``), then the
  rotary embedding by the position inside the document (``x cos +
  rotate_half(x) sin``, frequencies ``theta ** (-2i / d)`` repeated over
  both halves); 4 query heads share a key/value head; scores scaled by ``1 /
  sqrt(head_dim)`` after the product; a query sees a key iff the key lies in
  the query's document and not after it; softmax; output projection.
* dense FFN (the first ``num_dense_layers`` layers held): ``W_2 (silu(W_1
  x) * W_3 x)``.
* sparse FFN: ``s = sigmoid(x W_r)`` over all 64 experts; the chosen are the
  top 4 of ``s + b`` (``b`` the expert bias: it selects and does not weigh);
  ``w_e = s_e / (sum of the chosen s + 1e-6)``, times
  ``routed_scaling_factor``; the output is the sum, over the chosen experts
  **held here**, of ``w_e W_down_e (silu(W_gate_e x) * W_up_e x)``. What the
  absent experts would add is left out, as in the program: this is the
  chip's share, and that partial sum goes on. No shared expert.

Departures from the straightest form, all for memory (the check takes this
loss's gradient, twice, beside the program's state, on one chip): a layer is
recomputed in the backward pass (``jax.checkpoint``); what is computed token
by token (a dense FFN, the head with its loss) is taken one sequence at a
time (`per_sequence`: ``jax.lax.map`` over recomputed blocks), as is a
convolution, which never leaves its sequence; attention is taken one
(sequence, key/value head) at a time; the experts are a loop over the held
ones inside one sequence's block, each recomputed, applied to every token
of the sequence and weighted by the token's weight for it, zero where it was
not chosen: no sort, no gather, no grouped product (one map over the
sequences and not one a held expert: each map keeps a copy of the layer's
input for its backward pass, and eight of them were the GiB by which ISSUE
38's share did not fit the chip beside the program).

``inputs`` is ``[sequences, length]`` int32, each token's position inside
its document as the generator packed them; ``labels`` ``[T]`` int32, each
token's successor; ``embs`` one ``[T, hidden]`` array; ``dense`` the
program's parameters without the embedding: ``{"layers": [{"operator_norm",
"ffn_norm", then "in_proj", "conv", "out_proj" or "wq", "wk", "wv", "wo",
"q_norm", "k_norm", then "w1", "w3", "w2" or "experts": {"router", "bias",
"gate", "up", "down"}}], "embedding_norm", "head"}``. Widths and counts are
read from the arrays' shapes; what no shape says (`spec`) from the
configuration's file.
"""

import json
import math
import os

import jax
import jax.numpy as jnp

_CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "lfm2-24b-a2b.json")


def published_spec():
    """What the arrays' shapes do not say, from the configuration's file."""
    with open(_CONFIG) as f:
        config = json.load(f)
    first = config["deployment"]["first_layer_held"]
    return {"head_dim": config["head_dim"],
            "layer_types": config["layer_types"][
                first:first + config["num_hidden_layers"]],
            "num_dense_layers": config["num_dense_layers"],
            "rope_theta": config["rope_parameters"]["rope_theta"],
            "num_experts_per_tok": config["num_experts_per_tok"],
            "routed_scaling_factor": config["routed_scaling_factor"],
            "first_expert_held": config["deployment"]["first_expert_held"],
            "norm_eps": config["norm_eps"]}


def rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x ** 2, axis=-1, keepdims=True) + eps) * weight


def per_sequence(f, n_seq, *arrays):
    """``f`` over each sequence's block of ``[T, ...]`` arrays, recomputed in
    the backward pass; the results side by side again."""
    blocks = tuple(a.reshape((n_seq, -1) + a.shape[1:]) for a in arrays)
    out = jax.lax.map(lambda block: jax.checkpoint(f)(*block), blocks)
    return out.reshape((-1,) + out.shape[2:])


def short_convolution(layer, x, positions, eps):
    n_seq = positions.shape[0]

    def one(x, position):                 # one sequence: [length, hidden]
        b, c, u = jnp.split(rms_norm(x, layer["operator_norm"], eps)
                            @ layer["in_proj"], 3, axis=-1)
        v = b * u
        taps = layer["conv"].shape[1]
        out = jnp.zeros_like(v)
        for j in range(taps):
            d = taps - 1 - j              # how far back this tap reads
            earlier = jnp.concatenate(
                [jnp.zeros_like(v[:d]), v[:v.shape[0] - d]], axis=0)
            inside = (position >= d)[:, None]       # still in t's document
            out = out + layer["conv"][:, j] * jnp.where(inside, earlier, 0.0)
        return (c * out) @ layer["out_proj"]

    return per_sequence(one, n_seq, x, positions.reshape(-1))


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rotary(x, positions, theta):
    """``x [sequences, length, heads, head_dim]``."""
    d = x.shape[-1]
    freqs = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)],
                        jnp.float32)
    angles = positions[..., None] * freqs
    angles = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]
    return x * jnp.cos(angles) + rotate_half(x) * jnp.sin(angles)


def attention(layer, x, positions, spec):
    n_seq, length = positions.shape
    d, eps = spec["head_dim"], spec["norm_eps"]
    x = rms_norm(x, layer["operator_norm"], eps)
    q = (x @ layer["wq"]).reshape(n_seq, length, -1, d)
    k = (x @ layer["wk"]).reshape(n_seq, length, -1, d)
    v = (x @ layer["wv"]).reshape(n_seq, length, -1, d)
    q = rotary(rms_norm(q, layer["q_norm"], eps), positions,
               spec["rope_theta"])
    k = rotary(rms_norm(k, layer["k_norm"], eps), positions,
               spec["rope_theta"])
    kv_heads = k.shape[2]
    q = q.reshape(n_seq, length, kv_heads, -1, d)      # heads of a kv head

    at = jnp.arange(length)
    behind = at[:, None] - at[None, :]                  # query - key

    @jax.checkpoint
    def one(args):
        q, k, v, position = args          # [length, group, d], [length, d] x 2
        visible = (behind >= 0) & (behind <= position[:, None])
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


def dense_ffn(layer, x, n_seq, eps):
    def one(x):
        x = rms_norm(x, layer["ffn_norm"], eps)
        return (jax.nn.silu(x @ layer["w1"]) * (x @ layer["w3"])) @ layer["w2"]

    return per_sequence(one, n_seq, x)


def experts_held(experts, x, n_seq, spec):
    scores = jax.nn.sigmoid(x @ experts["router"])
    _, chosen = jax.lax.top_k(scores + experts["bias"],
                              spec["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-6)
    top = top * spec["routed_scaling_factor"]

    held = range(experts["gate"].shape[0])
    # [T, held]: a token's weight for each held expert, zero where not chosen
    weights = jnp.stack(
        [jnp.sum(jnp.where(chosen == spec["first_expert_held"] + local, top,
                           0.0), axis=-1) for local in held], axis=-1)

    @jax.checkpoint
    def expert(x, weight, gate, up, down):
        return weight[:, None] * ((jax.nn.silu(x @ gate) * (x @ up)) @ down)

    def one(x, weights):                  # one sequence's tokens
        return sum(expert(x, weights[:, local], experts["gate"][local],
                          experts["up"][local], experts["down"][local])
                   for local in held)

    return per_sequence(one, n_seq, x, weights)


def model_loss(dense, embs, inputs, labels, spec):
    (x,) = embs
    positions = inputs
    n_seq, eps = positions.shape[0], spec["norm_eps"]

    def block(layer, x, mixer, is_dense):
        if mixer == "conv":
            h = x + short_convolution(layer, x, positions, eps)
        else:
            h = x + attention(layer, x, positions, spec)
        if is_dense:
            return h + dense_ffn(layer, h, n_seq, eps)
        return h + experts_held(layer["experts"],
                                rms_norm(h, layer["ffn_norm"], eps), n_seq,
                                spec)

    for i, (layer, mixer) in enumerate(zip(dense["layers"],
                                           spec["layer_types"])):
        x = jax.checkpoint(block, static_argnums=(2, 3))(
            layer, x, mixer, i < spec["num_dense_layers"])

    def token_losses(x, labels):          # one sequence's tokens
        logits = rms_norm(x, dense["embedding_norm"], eps) @ dense["head"]
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
