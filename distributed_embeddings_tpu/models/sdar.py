"""One chip's share of an SDAR-style block-diffusion decoder on the training
path.

The architecture (JetLM/SDAR-30B-A3B-Chat's ``config.json``, ``sdar_moe``) is
a Qwen3-MoE decoder trained to denoise blocks instead of predicting the next
token: pre-norm blocks ``x -> h = x + Attn(RMSNorm(x)) -> h +
MoE(RMSNorm(h))``, grouped-query attention with q and k RMS-normed over each
head's width before the rotary embedding, a softmax router over all experts
with the top k renormalised, no shared expert, a final RMSNorm and an untied
head.

Training reads two streams of one packed sequence ``x0`` of length ``L``
(BD3-LM's vectorised form, arXiv:2503.09573; SDAR, arXiv:2510.06303):

* blocks of ``block`` tokens counted from each document's start, ``blk(i) =
  (document(i), position(i) // block)``;
* the noisy copy ``xt``: every token of block b replaced by the mask id with
  the block's own probability ``p_b``;
* the model reads ``[xt ; x0]``, ``2L`` positions; the two copies of a token
  share its position for the rotary;
* a noisy query sees the noisy keys of its own block and the clean keys of
  its document's earlier blocks; a clean query sees the clean keys of its
  document up to the end of its own block; no clean query sees a noisy key
  (`attend_block_diffusion`);
* the final norm and the head run at the noisy positions alone, and a masked
  one predicts the clean token at the same position: ``loss = sum over the
  masked i of CE(logits_i, x0_i) / p_blk(i) / N``, ``N`` the clean tokens.

So the last layer computes no clean query, no clean attention output and no
clean MLP: the loss reads none of them, and the noisy stream reads only the
clean stream's keys and values, which come from that layer's input.

What a chip of an expert- and vocabulary-parallel deployment holds of it is
`models/mellum.py`'s: all of attention, the experts `ExpertLayer` is told it
holds, and a slice of the vocabulary (the table's rows and the head's
columns), the mask id among them. A batch is ``(inputs, [ids], weights)``:
`inputs` ``[sequences, 2, length]`` int32 holds each token's position inside
its document and its clean id (the target); `ids` ``[2T]`` the tokens as the
table is asked for them, sequence by sequence the noisy stream then the clean
one; `weights` ``[T]`` f32 ``1 / p_blk(i)`` at a masked noisy position and 0
elsewhere.
"""

import functools
import math
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from distributed_embeddings_tpu.layers.dist_model_parallel import (
    DistributedEmbedding)
from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.layers.experts import ExpertLayer
from distributed_embeddings_tpu.models.mellum import (
    ATTN_BLOCK, INIT_STD, _normal_init, _rms_norm, _rotate, _scores_to_values,
    _table_init, embed_tokens, packed_mask_terms, rotary_frequencies)
from distributed_embeddings_tpu.obs.spans import spanned
from distributed_embeddings_tpu.obs.stages import stage

__all__ = ["Sdar", "Tile", "attend_block_diffusion", "block_diffusion_tiles",
           "clean_key_start", "diffusion_head_loss"]

NOISY, CLEAN = 0, 1                    # the streams, in the order of the ids
# attention's output in front of its out-projection: the one array of a
# block's first half that the backward pass is handed and does not take again
ATTENDED = "det_attended"


class Tile(NamedTuple):
    """One static block of scores: queries ``[start, stop)`` of one stream
    against the clean keys ``[0, clean_stop)`` (of which a sequence's tile
    computes those from `clean_key_start` on) and, for noisy queries, the
    noisy keys ``[noisy_start, noisy_stop)``."""
    stream: int
    start: int
    stop: int
    clean_stop: int
    noisy_start: int
    noisy_stop: int

    def keys(self) -> int:
        return self.clean_stop + self.noisy_stop - self.noisy_start


def block_diffusion_tiles(length: int, block: int, streams=(NOISY, CLEAN),
                          tile: int = ATTN_BLOCK):
    """The tiles `attend_block_diffusion` computes, `tile` queries of each
    of `streams` at a time. A block may straddle a tile's edge by up to
    ``block - 1`` tokens, so a clean tile reads that far past its last
    query, and a noisy tile reads the noisy keys that far on both sides:
    the only noisy keys its queries can see. A noisy tile's clean keys end
    at its last query (they lie in earlier blocks), a clean tile's at its
    last query's block's end. No tile pairs a clean query with a noisy
    key."""
    reach = block - 1
    tiles = []
    for start in range(0, length, tile):
        stop = min(start + tile, length)
        for stream in streams:
            if stream == NOISY:
                tiles.append(Tile(NOISY, start, stop, stop,
                                  max(0, start - reach),
                                  min(length, stop + reach)))
            else:
                tiles.append(Tile(CLEAN, start, stop,
                                  min(length, stop + reach), 0, 0))
    return tiles


def _pair_mask(stream: int, queries, clean, noisy=None):
    """``[sequences, queries, keys]`` bool: which pairs the mask keeps,
    from the (document, block) terms ``[sequences, positions]`` of the
    queries, of the clean keys and, for noisy queries, of the noisy keys;
    keys in that order (clean, then noisy)."""
    (doc_q, blk_q), (doc_c, blk_c) = queries, clean
    same_doc = doc_q[:, :, None] == doc_c[:, None, :]
    earlier = blk_q[:, :, None] - blk_c[:, None, :]
    if stream == CLEAN:
        return same_doc & (earlier >= 0)
    doc_n, blk_n = noisy
    own = ((doc_q[:, :, None] == doc_n[:, None, :])
           & (blk_q[:, :, None] == blk_n[:, None, :]))
    return jnp.concatenate([same_doc & (earlier > 0), own], axis=2)


def _visible(tile: Tile, document, blk):
    """``[sequences, queries, keys]`` bool: which pairs of `tile` the mask
    keeps, keys in the tile's order (clean, then noisy)."""
    q, c = slice(tile.start, tile.stop), slice(0, tile.clean_stop)
    n = slice(tile.noisy_start, tile.noisy_stop)
    return _pair_mask(tile.stream, (document[:, q], blk[:, q]),
                      (document[:, c], blk[:, c]), (document[:, n], blk[:, n]))


def block_terms(positions, block: int):
    """(document index inside its sequence, block index inside its
    document), both ``[sequences, length]``."""
    document, _ = packed_mask_terms(positions)
    return document, positions // block


def clean_key_start(document, start: int):
    """``[sequences]`` int32: the first clean key that the tile of queries
    from `start` computes in each sequence, the first position of the
    document that holds query `start`, rounded down to whole tiles of
    `ATTN_BLOCK` keys. A packed sequence's documents are contiguous, so no
    query from `start` on sees a clean key before it."""
    first = jnp.sum(document < document[:, start:start + 1], axis=1)
    return jnp.clip(first // ATTN_BLOCK, 0, start // ATTN_BLOCK) * ATTN_BLOCK


def _windows(stream: int, chunks: int, reach: int, tile: int, length: int,
             start, first):
    """Where the keys of the tile of queries from `start` lie, whose clean
    keys start at `first`: ``((stream, first key, keys), ...)``, clean then
    noisy. The clean keys run over `chunks` tiles to the tile's last query
    and, for clean queries, `reach` past it (a block may straddle the
    tile's edge); noisy queries also read the noisy keys `reach` past both
    edges, the only noisy keys they can see: `block_diffusion_tiles`'s keys
    from `first` on. The counts are static, one per `chunks`, so where a
    window would leave the sequence it starts up to `reach` keys earlier,
    on keys the mask drops (another block, or before the document of the
    tile's first query)."""
    if stream == CLEAN:
        size = min(chunks * tile + reach, length)
        return ((CLEAN, jnp.minimum(first, length - size), size),)
    size = min(tile + 2 * reach, length)
    return ((CLEAN, first, chunks * tile),
            (NOISY, jnp.clip(start - reach, 0, length - size), size))


def _tile_forward(stream, chunks, reach, q, k, v, document, blk, s, start,
                  first):
    """The tile of queries `q` ``[1, tile, kv heads, group, d]`` of sequence
    `s`: `mellum._scores_to_values` over its `_windows` of `k` and `v`."""
    return _scores_to_values(q, *_tile_operands(
        stream, chunks, reach, q, k, v, document, blk, s, start, first))


def _tile_operands(stream, chunks, reach, q, k, v, document, blk, s, start,
                   first):
    """(keys, values, mask) of a tile, keys in `_windows`' order."""
    tile, length = q.shape[1], document.shape[1]
    windows = _windows(stream, chunks, reach, tile, length, start, first)

    def rows(x, at, size):                     # [n, length] -> [1, size]
        return jax.lax.dynamic_slice(x, (s, at), (1, size))

    def gather(x):                             # [n, 2, length, ...]
        return jnp.concatenate([jax.lax.dynamic_slice(
            x, (s, part, at) + (0,) * (x.ndim - 3),
            (1, 1, size) + x.shape[3:])[:, 0]
            for part, at, size in windows], axis=1)

    mask = _pair_mask(stream, (rows(document, start, tile),
                               rows(blk, start, tile)),
                      *((rows(document, at, size), rows(blk, at, size))
                        for _, at, size in windows))
    return gather(k), gather(v), mask


def _tile_backward(stream, chunks, reach, q, k, v, document, blk, s, start,
                   first, grad, dk, dv):
    """The same tile taken again and differentiated: (the gradient of its
    queries, `dk` and `dv` with those of its keys and values added)."""
    keys, values, mask = _tile_operands(stream, chunks, reach, q, k, v,
                                        document, blk, s, start, first)
    _, vjp = jax.vjp(lambda q, keys, values: _scores_to_values(
        q, keys, values, mask), q, keys, values)
    dq, dkeys, dvalues = vjp(grad)
    windows = _windows(stream, chunks, reach, q.shape[1], document.shape[1],
                       start, first)

    def add(acc, grads):
        done = 0
        for part, at, size in windows:
            index = (s, part, at) + (0,) * (acc.ndim - 3)
            acc = jax.lax.dynamic_update_slice(
                acc, jax.lax.dynamic_slice(acc, index,
                                           (1, 1, size) + acc.shape[3:])
                + grads[:, None, done:done + size], index)
            done += size
        return acc

    return dq, add(dk, dkeys), add(dv, dvalues)


@functools.lru_cache(maxsize=None)
def _branches(direction, stream: int, reach: int, count: int):
    """A tile's programs, one per number of tiles of clean keys it reads
    (1 to `count`): the same function objects for every layer, so JAX
    traces each once."""
    return tuple(functools.partial(direction, stream, chunks, reach)
                 for chunks in range(1, count + 1))


def _loop_terms(q, document, first):
    """What each (sequence, tile) of a stream's loop reads: its queries
    ``[n * count, tile, ...]``, sequence, first query, first clean key and
    the branch of its `chunks`."""
    n, length = document.shape
    count = first.shape[1]
    tile = length // count
    index = jnp.arange(n * count)
    seq, at = index // count, index % count
    first = first.reshape(-1)
    return (q.reshape((n * count, tile) + q.shape[2:]), seq, at * tile,
            first * tile, at - first)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _attend_stream(stream: int, reach: int, q, k, v, document, blk, first):
    """Attention of one stream's queries `q` ``[n, length, kv heads, group,
    d]`` tile by tile, `first` ``[n, count]`` the tile at which each
    (sequence, tile)'s clean keys start: a loop over the (sequence, tile)
    pairs whose body switches to the program of the pair's number of tiles
    of clean keys. So the step holds one program per such number, not one
    per (layer, sequence, tile, start): a switch a tile multiplied the
    step's programs several times over, and the time to load it from the
    compile cache with them. The backward pass keeps the loop's inputs
    alone. Returns ``[n, length, kv heads, group, d]``."""
    return _attend_stream_fwd(stream, reach, q, k, v, document, blk,
                              first)[0]


def _attend_stream_fwd(stream, reach, q, k, v, document, blk, first):
    branches = _branches(_tile_forward, stream, reach, first.shape[1])
    tiles, *where, choice = _loop_terms(q, document, first)
    out = jax.lax.map(
        lambda x: jax.lax.switch(x[-1], branches, x[0][None], k, v,
                                 document, blk, *x[1:-1]),
        (tiles, *where, choice))
    return out.reshape(q.shape), (q, k, v, document, blk, first)


def _attend_stream_bwd(stream, reach, saved, grad):
    q, k, v, document, blk, first = saved
    branches = _branches(_tile_backward, stream, reach, first.shape[1])
    tiles, *where, choice = _loop_terms(q, document, first)

    def body(acc, x):
        tile, *at, pick, g = x
        dq, *acc = jax.lax.switch(pick, branches, tile[None], k, v,
                                  document, blk, *at, g[None], *acc)
        return acc, dq

    (dk, dv), dq = jax.lax.scan(
        body, [jnp.zeros_like(k), jnp.zeros_like(v)],
        (tiles, *where, choice, grad.reshape(tiles.shape)))
    return dq.reshape(q.shape), dk, dv, None, None, None


_attend_stream.defvjp(_attend_stream_fwd, _attend_stream_bwd)


def attend_block_diffusion(q, k, v, document, blk, block: int):
    """Attention of the queries `q` ``[n, streams, length, heads, d]`` (both
    streams, or the noisy one alone) over the keys and values `k`, `v` ``[n,
    2, length, kv heads, d]`` of both streams under the block-diffusion
    mask, scores scaled by ``1 / sqrt(d)``, in plain XLA. Only the tiles of
    `block_diffusion_tiles` are computed, and of each sequence's tile only
    the clean keys from `clean_key_start` on, each with
    `mellum._scores_to_values` (taken again in the backward pass), one
    stream at a time by `_attend_stream`. `length` is at most `ATTN_BLOCK`
    or a multiple of it. Returns ``[n, streams, length, heads * d]``."""
    n, streams, length, heads, d = q.shape
    kv_heads = k.shape[3]
    tile = min(ATTN_BLOCK, length)
    if length % tile:
        raise ValueError(f"a sequence of {length} tokens is no whole number "
                         f"of tiles of {tile} queries")
    q = q.reshape(n, streams, length, kv_heads, heads // kv_heads, d)
    first = jnp.stack([clean_key_start(document, start) // tile
                       for start in range(0, length, tile)], axis=1)
    return jnp.stack([
        _attend_stream(stream, block - 1, q[:, stream], k, v, document, blk,
                       first)
        for stream in (NOISY, CLEAN)[:streams]], axis=1).reshape(
            n, streams, length, heads * d)


def diffusion_head_loss(x, final_norm, head, targets, weights, eps):
    """Stage ``head``: the final RMSNorm and the logits over the head's
    slice of the vocabulary at the noisy positions ``x [T, hidden]``, and
    ``sum_i weights_i * CE(logits_i, targets_i) / T``: a masked position
    predicts its own clean token, weighted ``1 / p`` of its block, over the
    ``T`` clean tokens."""
    with stage("head"):
        logits = _rms_norm(x, final_norm, eps) @ head
        nll = (jax.nn.logsumexp(logits, axis=-1)
               - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0])
        return jnp.sum(weights * nll) / weights.shape[0]


class Sdar:
    """Static configuration; ``init(key)`` returns the parameters and
    ``loss_fn`` is what `training.make_sparse_train_step` asks of a model.

    Args:
      vocab_rows: rows of the embedding and columns of the head held here;
        the last of them is the mask id.
      hidden, num_heads, num_kv_heads, head_dim: attention's published sizes.
      num_layers: the layers held here, every one attention + experts.
      rope: the config's rotary entry (`mellum.rotary_frequencies`).
      block: the diffusion block's length in tokens.
      num_experts_total, held_experts, top_k, expert_width: `ExpertLayer`'s,
        under its softmax rule.
      norm_eps: every RMSNorm's epsilon, q's and k's too.
      num_layers_total: the whole model's depth; the init of the matrices
        that write to the residual stream is scaled for it (default: the
        layers held).
      mesh: the embedding's device mesh (None: one device).

    As in `Mellum`, every product reads the values the layer's equations
    name, in their order (PERF.md section 6, PR 36).
    """

    def __init__(self, vocab_rows: int, hidden: int, num_heads: int,
                 num_kv_heads: int, head_dim: int, num_layers: int,
                 rope: dict, block: int, num_experts_total: int,
                 held_experts: Sequence[int], top_k: int, expert_width: int,
                 norm_eps: float = 1e-6, num_layers_total: int = None,
                 mesh=None):
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads over {num_kv_heads} "
                             "key/value heads")
        if block < 1 or num_layers < 1:
            raise ValueError(f"a block of {block} tokens, {num_layers} layers")
        self.vocab_rows, self.hidden = vocab_rows, hidden
        self.mask_id = vocab_rows - 1
        self.num_heads, self.num_kv_heads, self.head_dim = (
            num_heads, num_kv_heads, head_dim)
        self.num_layers, self.block = num_layers, block
        self.norm_eps = norm_eps
        self.residual_std = INIT_STD / math.sqrt(
            2 * (num_layers_total or num_layers))
        self.rotary = rotary_frequencies(head_dim, rope)
        self.experts = ExpertLayer(hidden, expert_width, num_experts_total,
                                   held_experts, top_k)
        self.embedding = DistributedEmbedding(
            [Embedding(vocab_rows, hidden,
                       embeddings_initializer=_table_init)], mesh=mesh)
        self.mesh = mesh

    # ------------------------------------------------------------ parameters
    def _init_layer(self, key) -> dict:
        h, d = self.hidden, self.head_dim
        kq, kk, kv, ko, kx = jax.random.split(key, 5)
        return {
            "input_layernorm": jnp.ones(h),
            "post_attention_layernorm": jnp.ones(h),
            "wq": _normal_init(kq, (h, self.num_heads * d)),
            "wk": _normal_init(kk, (h, self.num_kv_heads * d)),
            "wv": _normal_init(kv, (h, self.num_kv_heads * d)),
            "wo": self.residual_std * jax.random.normal(
                ko, (self.num_heads * d, h)),
            "q_norm": jnp.ones(d), "k_norm": jnp.ones(d),
            "experts": self.experts.init(kx, INIT_STD, self.residual_std)}

    @spanned("model/init")
    def init(self, key) -> dict:
        ke, kh, *kl = jax.random.split(key, 2 + self.num_layers)
        return {"embedding": self.embedding.init(ke),
                "layers": [self._init_layer(k) for k in kl],
                "norm": jnp.ones(self.hidden),
                "head": _normal_init(kh, (self.hidden, self.vocab_rows))}

    # --------------------------------------------------------------- forward
    def _heads(self, x, weight, norm, positions, heads):
        """``x [n, streams, length, hidden] @ weight``, per head RMS-normed
        and rotated by the position the two streams share."""
        n, streams, length, _ = x.shape
        y = _rms_norm((x @ weight).reshape(n * streams, length, heads,
                                           self.head_dim), norm, self.norm_eps)
        inv_freq, factor = self.rotary
        y = _rotate(y, jnp.repeat(positions, streams, axis=0), inv_freq,
                    factor)
        return y.reshape(n, streams, length, heads, self.head_dim)

    def _attend(self, layer, x, positions, document, blk, last: bool):
        """``[2T, hidden] -> [2T, hidden]``, or ``[T, hidden]`` of the noisy
        stream alone where `last`: the residual add of attention's output."""
        with stage("attn"):
            n, length = positions.shape
            both = x.reshape(n, 2, length, -1)
            normed = _rms_norm(both, layer["input_layernorm"], self.norm_eps)
            k = self._heads(normed, layer["wk"], layer["k_norm"], positions,
                            self.num_kv_heads)
            v = (normed @ layer["wv"]).reshape(k.shape)
            queries = normed[:, :1] if last else normed
            q = self._heads(queries, layer["wq"], layer["q_norm"], positions,
                            self.num_heads)
            attended = checkpoint_name(
                attend_block_diffusion(q, k, v, document, blk, self.block),
                ATTENDED)
            kept = both[:, :1] if last else both
            return (kept.reshape(-1, self.hidden)
                    + attended.reshape(-1, attended.shape[-1]) @ layer["wo"])

    def _sparse_mlp(self, layer, x):
        # the expert layer opens its own two stages
        return x + self.experts(
            layer["experts"],
            _rms_norm(x, layer["post_attention_layernorm"], self.norm_eps))

    def hidden_states(self, params, positions, x):
        """The blocks over both streams' embedded tokens ``x [2T, hidden]``;
        the noisy stream's last hidden states ``[T, hidden]``. What the
        backward pass keeps of a block's first half is its input and
        attention's output (`ATTENDED`); the projections and, tile by tile,
        the scores are taken again. A block's second half is taken again
        from its input."""
        document, blk = block_terms(positions, self.block)
        # one function and policy object for the layers alike, so that JAX
        # traces and differentiates each half of a block once
        policy = jax.checkpoint_policies.save_only_these_names(ATTENDED)
        attend = {last: jax.checkpoint(functools.partial(self._attend,
                                                         last=last),
                                       policy=policy)
                  for last in (False, True)}
        sparse_mlp = jax.checkpoint(self._sparse_mlp)
        for i, layer in enumerate(params["layers"]):
            x = attend[i == self.num_layers - 1](layer, x, positions,
                                                 document, blk)
            x = sparse_mlp(layer, x)
        return x

    def loss_fn(self, params, inputs, cats, weights, taps=None,
                return_residuals: bool = False):
        x, res = embed_tokens(self.embedding, params["embedding"], cats, taps,
                              return_residuals)
        x = self.hidden_states(params, inputs[:, 0], x)
        loss = diffusion_head_loss(x, params["norm"], params["head"],
                                   inputs[:, 1].reshape(-1), weights,
                                   self.norm_eps)
        return (loss, res) if return_residuals else loss

    # ---------------------------------------------------------------- gauges
    def diffusion_stats(self, inputs, cats, weights) -> dict:
        """Forward only, what one batch asks of the step: ``masked_share``,
        the masked noisy positions over the noisy positions (the part of
        the noisy stream the loss reads); ``mask_row_share``, the step's
        lookups that hit the mask id's row; ``skipped_pair_share``, the
        (query, key) pairs of the static score tiles whose clean keys lie
        before the sequence's `clean_key_start`, which the step does not
        compute, over the static tiles' pairs; ``visited_pair_share``, the
        pairs the mask keeps over the pairs the step computes (its windows
        add up to ``block - 1`` masked keys to a sequence's edge tiles,
        left out here). Both over its layers (the last one's queries are
        the noisy stream's alone). Reads no parameter; jit it."""
        positions = inputs[:, 0]
        n, length = positions.shape
        document, blk = block_terms(positions, self.block)
        layers = {NOISY: self.num_layers, CLEAN: self.num_layers - 1}
        tiles = block_diffusion_tiles(length, self.block)
        visible = sum(layers[t.stream] * jnp.sum(_visible(t, document, blk))
                      for t in tiles)
        tiled = sum(layers[t.stream] * n * (t.stop - t.start) * t.keys()
                    for t in tiles)
        skipped = sum(layers[t.stream] * (t.stop - t.start)
                      * jnp.sum(clean_key_start(document, t.start))
                      for t in tiles)
        (ids,) = cats
        return {"masked_share": jnp.mean(weights > 0),
                "mask_row_share": jnp.mean(ids == self.mask_id),
                "skipped_pair_share": skipped / tiled,
                "visited_pair_share": visible / (tiled - skipped)}
