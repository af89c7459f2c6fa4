"""One chip's share of a Mellum-2-style decoder on the training path.

The architecture (JetBrains/Mellum2-12B-A2.5B-Instruct's ``config.json``):
pre-norm blocks of grouped-query attention and a sparse MLP, ``x -> h = x +
Attn(RMSNorm(x)) -> h + MoE(RMSNorm(h))``, layers alternating by
``layer_types`` between a causal sliding window and full causal attention,
rotary positions (plain on window layers, YaRN's blended frequencies and
attention factor on full layers), a softmax router over all experts with
the top-k renormalised, a final RMSNorm and an untied head.

What a chip of an expert- and vocabulary-parallel deployment holds of it:
all of attention (it is data-parallel there), the experts `ExpertLayer` is
told it holds, and a slice of the vocabulary: the rows of the embedding, a
`DistributedEmbedding` table trained sparsely, and as many columns of the
head. A sliced vocabulary is a smaller vocabulary: ids, logits and loss are
over the slice.

A batch is packed documents, ``(positions, [ids], next_ids)``: `positions`
``[sequences, length]`` int32 is each token's position inside its document
(0 starts a document; attention never crosses one), ``ids`` ``[T]`` the
tokens row-major, ``next_ids`` ``[T]`` each token's successor. The loss is
the mean softmax cross-entropy over the tokens whose successor is in the
same document and sequence.
"""

import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from distributed_embeddings_tpu.layers.dist_model_parallel import (
    DistributedEmbedding)
from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.layers.experts import ExpertLayer
from distributed_embeddings_tpu.obs.spans import spanned
from distributed_embeddings_tpu.obs.stages import stage

__all__ = ["Mellum", "rotary_frequencies", "packed_mask_terms"]

INIT_STD = 0.02          # every matrix, but:
# the two projections that write to the residual stream, attention's output
# and an expert's down projection, are drawn at INIT_STD / sqrt(2 * layers of
# the whole model), as GPT-2 and Megatron-LM's scaled init draw them: 2 *
# layers blocks add to the stream, and its size should not grow with them.
# At a random init that also keeps a token's own row the largest term of
# what a router reads (PERF.md section 6, PR 36)
# The table's rows are drawn at the scale of the residual stream's other
# terms or above it, so that a token's own row, not the average over its
# document that a random attention layer adds, decides where the routers
# send it. At 0.02 every token of a document picks the same experts at a
# random init: a layer's held pairs then swing from 0.3 to 2.4 times an even
# router's with the seed, which no trained router does (PERF.md section 6,
# PR 36)
TABLE_STD = 1.0
# queries of one block of scores: [sequences, heads, ATTN_BLOCK, keys] f32 is
# what attention keeps live, 1 GiB at 4 x 32 x 512 x 4,096
ATTN_BLOCK = 512


def _normal_init(key, shape, dtype=jnp.float32):
    return INIT_STD * jax.random.normal(key, shape, dtype)


def _table_init(key, shape, dtype=jnp.float32):
    return TABLE_STD * jax.random.normal(key, shape, dtype)


def rotary_frequencies(head_dim: int, rope: dict):
    """(inverse frequencies ``[head_dim / 2]`` float64, attention factor) of
    one entry of the config's ``rope_parameters``. ``default``: ``theta **
    (-2i / d)``. ``yarn`` (Peng et al., arXiv:2309.00071, as the published
    config's library computes it): each frequency a blend of itself and
    itself over `factor`, by a linear ramp between the dimensions that turn
    `beta_fast` and `beta_slow` times over the original context; cos and
    sin are scaled by the attention factor."""
    base = float(rope["rope_theta"])
    plain = base ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    if rope["rope_type"] == "default":
        return plain, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"no rotary rule for rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    context = float(rope["original_max_position_embeddings"])

    def turns_to_dim(turns):
        return (head_dim * math.log(context / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(turns_to_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(turns_to_dim(rope["beta_slow"])), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    blended = plain / factor * ramp + plain * (1.0 - ramp)
    attention_factor = rope.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return blended, float(attention_factor)


def _rotate(x, positions, inv_freq, factor):
    """Rotary embedding of ``x [sequences, length, heads, head_dim]`` by
    ``positions [sequences, length]``, halves paired (x1, x2) -> (x1 cos -
    x2 sin, x2 cos + x1 sin)."""
    angle = (positions.astype(jnp.float32)[:, :, None]
             * jnp.asarray(inv_freq, jnp.float32))
    cos = (factor * jnp.cos(angle))[:, :, None, :]
    sin = (factor * jnp.sin(angle))[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def packed_mask_terms(positions):
    """(document index inside its sequence ``[sequences, length]``, which
    tokens' successor is in the same document and sequence ``[T]`` bool)."""
    starts = positions == 0
    document = jnp.cumsum(starts, axis=1)
    follows = jnp.concatenate(
        [~starts[:, 1:], jnp.zeros_like(starts[:, :1])], axis=1)
    return document, follows.reshape(-1)


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x ** 2, axis=-1, keepdims=True)
                        + eps) * weight


def swiglu(x, gate, up, down):
    """``(silu(x gate) * (x up)) down``: a dense SwiGLU block's three
    products."""
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def embed_tokens(embedding, params, cats, taps, return_residuals):
    """(the tokens' rows ``[T, hidden]``, the lookup's residuals or None):
    the one-table embedding as `make_sparse_train_step`'s ``loss_fn`` calls
    it, tapped where the step asks."""
    if taps is not None or return_residuals:
        (x,), res = embedding(params, list(cats), taps=taps,
                              return_residuals=True)
        return x, res
    (x,) = embedding(params, list(cats))
    return x, None


def head_loss(x, final_norm, head, positions, next_ids, eps):
    """Stage ``head``: the final RMSNorm, logits over the head's slice of
    the vocabulary, and the mean softmax cross-entropy over the tokens whose
    successor is in the same document and sequence."""
    with stage("head"):
        _, follows = packed_mask_terms(positions)
        logits = _rms_norm(x, final_norm, eps) @ head
        nll = (jax.nn.logsumexp(logits, axis=-1)
               - jnp.take_along_axis(logits, next_ids[:, None],
                                     axis=-1)[:, 0])
        return jnp.sum(jnp.where(follows, nll, 0.0)) / jnp.sum(follows)


class Mellum:
    """Static configuration; ``init(key)`` returns the parameters and
    ``loss_fn`` is what `training.make_sparse_train_step` asks of a model.

    Args:
      vocab_rows: rows of the embedding and columns of the head held here.
      hidden, num_heads, num_kv_heads, head_dim: attention's published sizes.
      layer_types: per layer ``"sliding_attention"`` or ``"full_attention"``.
      window: a window layer's query sees the keys ``query - key < window``.
      rope_parameters: ``{layer type: rope entry}`` (`rotary_frequencies`).
      num_experts_total, held_experts, top_k, expert_width: `ExpertLayer`'s.
      rms_eps: RMSNorm's epsilon.
      num_layers_total: the whole model's depth, of which `layer_types` are
        the layers held here; the residual writers' init is scaled for it
        (default: the layers held).
      mesh: the embedding's device mesh (None: one device).

    Every product reads the values the layer's equations name, in their
    order: scores are scaled after the product and not the queries before
    it, a norm divides by the root. At the chip's default precision a
    product rounds its operands to bfloat16, and a program that rounds other
    values than a plain transcription of the equations does is as far from
    it as that transcription is from f32: far enough to send one token in
    sixty to another expert (PERF.md section 6, PR 36).
    """

    def __init__(self, vocab_rows: int, hidden: int, num_heads: int,
                 num_kv_heads: int, head_dim: int,
                 layer_types: Sequence[str], window: int,
                 rope_parameters: dict, num_experts_total: int,
                 held_experts: Sequence[int], top_k: int, expert_width: int,
                 rms_eps: float = 1e-6, num_layers_total: int = None,
                 mesh=None):
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads over {num_kv_heads} "
                             "key/value heads")
        self.vocab_rows, self.hidden = vocab_rows, hidden
        self.num_heads, self.num_kv_heads, self.head_dim = (
            num_heads, num_kv_heads, head_dim)
        self.layer_types, self.window = tuple(layer_types), window
        self.rms_eps = rms_eps
        self.residual_std = INIT_STD / math.sqrt(
            2 * (num_layers_total or len(self.layer_types)))
        self.rotary = {kind: rotary_frequencies(head_dim, rope_parameters[kind])
                       for kind in set(self.layer_types)}
        self.experts = ExpertLayer(hidden, expert_width, num_experts_total,
                                   held_experts, top_k)
        self.embedding = DistributedEmbedding(
            [Embedding(vocab_rows, hidden,
                       embeddings_initializer=_table_init)], mesh=mesh)
        self.mesh = mesh

    # ------------------------------------------------------------ parameters
    @spanned("model/init")
    def init(self, key) -> dict:
        ke, kh, *kl = jax.random.split(key, 2 + len(self.layer_types))
        h, d = self.hidden, self.head_dim
        layers = []
        for k in kl:
            kq, kk, kv, ko, kx = jax.random.split(k, 5)
            layers.append({
                "attn_norm": jnp.ones(h), "mlp_norm": jnp.ones(h),
                "wq": _normal_init(kq, (h, self.num_heads * d)),
                "wk": _normal_init(kk, (h, self.num_kv_heads * d)),
                "wv": _normal_init(kv, (h, self.num_kv_heads * d)),
                "wo": self.residual_std * jax.random.normal(
                    ko, (self.num_heads * d, h)),
                "experts": self.experts.init(kx, INIT_STD,
                                             self.residual_std)})
        return {"embedding": self.embedding.init(ke), "layers": layers,
                "final_norm": jnp.ones(h),
                "head": _normal_init(kh, (h, self.vocab_rows))}

    # --------------------------------------------------------------- forward
    def _attention(self, layer, kind, x, positions, document):
        """``[T, hidden] -> [T, hidden]``. ``[heads, length, length]`` never
        exists: scores are made a block of `ATTN_BLOCK` queries at a time
        against the keys that block can see (all earlier ones of the
        sequence, or the window's), and a block is recomputed in the
        backward pass (`_attend_blocks`)."""
        n_seq, length = positions.shape
        inv_freq, factor = self.rotary[kind]
        q = (x @ layer["wq"]).reshape(n_seq, length, self.num_heads,
                                      self.head_dim)
        k = (x @ layer["wk"]).reshape(n_seq, length, self.num_kv_heads,
                                      self.head_dim)
        v = (x @ layer["wv"]).reshape(k.shape)
        q = _rotate(q, positions, inv_freq, factor)
        k = _rotate(k, positions, inv_freq, factor)
        window = self.window if kind == "sliding_attention" else length
        return _attend_blocks(q, k, v, document, window).reshape(
            n_seq * length, -1) @ layer["wo"]

    def _attend(self, layer, kind, x, positions, document):
        with stage("attn"):
            return x + self._attention(
                layer, kind, _rms_norm(x, layer["attn_norm"], self.rms_eps),
                positions, document)

    def _sparse_mlp(self, layer, x):
        # the expert layer opens its own two stages
        return x + self.experts(
            layer["experts"], _rms_norm(x, layer["mlp_norm"], self.rms_eps))

    def hidden_states(self, params, positions, x):
        """The blocks over embedded tokens ``x [T, hidden]``. What the
        backward pass takes again: each block of attention's scores, and
        each layer's sparse MLP from its input; attention's projections are
        kept (0.75 GB a layer at the cell's size)."""
        document, _ = packed_mask_terms(positions)
        for layer, kind in zip(params["layers"], self.layer_types):
            x = self._attend(layer, kind, x, positions, document)
            x = jax.checkpoint(self._sparse_mlp)(layer, x)
        return x

    def loss_fn(self, params, positions, cats, next_ids, taps=None,
                return_residuals: bool = False):
        x, res = embed_tokens(self.embedding, params["embedding"], cats, taps,
                              return_residuals)
        x = self.hidden_states(params, positions, x)
        loss = head_loss(x, params["final_norm"], params["head"], positions,
                         next_ids, self.rms_eps)
        return (loss, res) if return_residuals else loss

    def routing_stats(self, params, positions, cats) -> dict:
        """Per layer of this chip's blocks, `ExpertLayer.routing_stats` of
        what the layer's router saw: ``{name: [layers] f32}``. Forward
        only; jit it."""
        (x,) = self.embedding(params["embedding"], list(cats))
        document, _ = packed_mask_terms(positions)
        stats = []
        for layer, kind in zip(params["layers"], self.layer_types):
            x = self._attend(layer, kind, x, positions, document)
            normed = _rms_norm(x, layer["mlp_norm"], self.rms_eps)
            stats.append(self.experts.routing_stats(layer["experts"], normed))
            x = x + self.experts(layer["experts"], normed)
        return {name: jnp.stack([s[name] for s in stats])
                for name in stats[0]}


@jax.checkpoint
def _scores_to_values(q, k, v, visible):
    scores = jnp.einsum("nqhgd,nkhd->nhgqk", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(visible[:, None, None], scores, -jnp.inf)
    weights = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return jnp.einsum("nhgqk,nkhd->nqhgd", weights, v)


def _attend_blocks(q, k, v, document, window):
    """Attention of `q` ``[n, length, heads, d]`` over `k` and `v` ``[n,
    length, kv heads, d]``, scores scaled by ``1 / sqrt(d)``, in plain XLA:
    a query sees a key of its own document that is not after it and less
    than `window` before it (itself, always). `ATTN_BLOCK` queries at a time
    over the keys their block can see. Returns ``[n, length, heads * d]``."""
    n_seq, length, heads, d = q.shape
    q = q.reshape(n_seq, length, k.shape[2], heads // k.shape[2], d)
    block = min(ATTN_BLOCK, length)
    index = jnp.arange(length)
    outs = []
    for start in range(0, length, block):
        stop = min(start + block, length)
        first = max(0, start - (window - 1))           # oldest visible key
        first -= first % block                         # whole blocks of keys
        behind = index[start:stop, None] - index[None, first:stop]
        visible = ((document[:, start:stop, None]
                    == document[:, None, first:stop])
                   & (behind >= 0) & (behind < window))
        outs.append(_scores_to_values(q[:, start:stop], k[:, first:stop],
                                      v[:, first:stop], visible))
    return jnp.concatenate(outs, axis=1).reshape(n_seq, length, -1)
