"""A mixture-of-experts layer that is told which experts it holds.

Expert parallelism gives each chip a contiguous range of a layer's experts.
The router keeps its published width: every token is scored over ALL
``num_experts_total`` experts and picks its ``top_k``; the layer computes
the part of the result that the experts held here give, for the (token,
expert) pairs routed to them, and leaves out what the absent experts would
add. With the whole range held that part is the layer's output; with 8 of
64 held, a uniform router sends this chip one pair in eight. On one chip the
layer runs with no exchange, and nothing here stands in for absent chips.

No pair is dropped: there is no capacity factor. The pairs are sorted by
owner with the id stream's own sort (`embedding_ops.canonical_id_sort`: a
held expert's local index keeps its value, every other pair keys to the
sentinel ``num_held`` and sorts behind the held ones), so rows
``[0, held pairs)`` of the sorted stream are grouped by expert and the
three products of an expert's SwiGLU are grouped matrix products over them
(`grouped_matmul`). Around the products everything is paid by the row (a
token's row gathered to each of its pairs' slots, the weighted sum back to
tokens), so the usual step runs over `fast_rows` of the stream, twice what
an even router sends here, products included (`_products` says why they
do not stop at the last held pair), and a step whose held pairs do not fit
there runs over every one of the ``tokens * top_k`` slots: the case in
which every token picks only held experts is computed, slower, and never
cut. So the layer's cost follows twice the even share of the pairs, 2T of
8T at 8 of 64 experts, not the tokens times all experts and not every
slot.

What is shared with the id path and what is not: both sorts (by owner,
and of the held pairs by token) are `canonical_id_sort`'s, the group
boundaries `embedding_ops.row_to_split`'s. The sum of a token's pairs is
`sparse_update.dedup_sum`'s doubling scan over the token-sorted rows, cut
to the ``log2(top_k)`` levels a run of at most `top_k` pairs needs and
read back by a gather, where `dedup_sum` takes ``log2(rows)`` levels and
compacts; `ops/wire.py` has nothing to exchange on one chip.
"""

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from distributed_embeddings_tpu.obs.stages import stage
from distributed_embeddings_tpu.ops.embedding_ops import (canonical_id_sort,
                                                          row_to_split)

__all__ = ["ExpertLayer", "Routing", "grouped_matmul"]

ROW_TILE = 128          # the unit `fast_rows` counts in
GMM_TILE_ROWS = 512     # rows of the chip's grouped product's tile: a
#                         group's ragged edge costs one tile more per expert


def _tile(size: int, most: int) -> int:
    """The largest multiple of 128 up to `most` that divides `size`, or
    `size` itself where there is none (a small or odd width: one tile)."""
    for t in range(most - most % 128, 0, -128):
        if size % t == 0:
            return t
    return size


def _tiling(m: int, k: int, n: int):
    """(rows, contraction, columns) of a tile: the last two divide both
    2,304 and 896 = 7 x 128 at the cell's widths."""
    rows = next((t for t in (GMM_TILE_ROWS, 256, ROW_TILE) if m % t == 0), m)
    return (rows, _tile(k, 896), _tile(n, 896))


@jax.custom_vjp
def _gmm_tpu(lhs, rhs, sizes):
    """megablox's grouped product with a tiling per product (its own
    `custom_vjp` hands the forward's tile to both backward products, whose
    contraction is another axis). Operands in bfloat16, sums in f32: what
    the chip's default precision makes of an f32 product."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m, k = lhs.shape
    return gmm(lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16), sizes,
               jnp.float32, _tiling(m, k, rhs.shape[2]))


def _gmm_tpu_fwd(lhs, rhs, sizes):
    return _gmm_tpu(lhs, rhs, sizes), (lhs, rhs, sizes)


def _gmm_tpu_bwd(res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    lhs, rhs, sizes = res
    (m, k), n = lhs.shape, rhs.shape[2]
    g16 = g.astype(jnp.bfloat16)
    d_lhs = gmm(g16, rhs.astype(jnp.bfloat16), sizes, jnp.float32,
                _tiling(m, n, k), transpose_rhs=True)
    d_rhs = tgmm(lhs.astype(jnp.bfloat16).T, g16, sizes, jnp.float32,
                 _tiling(m, k, n))
    return d_lhs.astype(lhs.dtype), d_rhs.astype(rhs.dtype), None


_gmm_tpu.defvjp(_gmm_tpu_fwd, _gmm_tpu_bwd)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                   sizes: jax.Array) -> jax.Array:
    """``lhs[rows of group g] @ rhs[g]`` for consecutive groups of `sizes`
    rows: ``[m, k] x [groups, k, n] -> [m, n]`` in f32. Rows behind the last
    group are not computed and hold anything (the chip's kernel never
    visits their tiles): a caller gives every row a group.

    On a TPU this is the library's Pallas kernel (megablox): XLA's own
    lowering of `lax.ragged_dot` there is a kernel of the same kind, but it
    names its operations for itself and not for the program, so a trace
    could not say whose stage its time is. Elsewhere `lax.ragged_dot`."""
    if jax.default_backend() == "tpu":
        return _gmm_tpu(lhs, rhs, sizes)
    return lax.ragged_dot(lhs, rhs, sizes,
                          preferred_element_type=jnp.float32)


ROUTERS = ("softmax", "sigmoid")
# what a sigmoid router's renormalisation adds to the chosen scores' sum
# (they are independent, and all of them can be next to nothing)
SIGMOID_NORM_EPS = 1e-6


class Routing(NamedTuple):
    """A batch's routing: per token its `top_k` experts, ascending by score
    rank, and their weights, renormalised to sum to 1 (times the layer's
    `routed_scale`)."""
    experts: jax.Array      # [T, top_k] int32, over all experts
    weights: jax.Array      # [T, top_k] f32


class ExpertLayer:
    """SwiGLU experts behind a router; static configuration only.

    Args:
      hidden: the model's width. width: an expert's inner width.
      num_experts_total: the router's outputs, held here or not.
      held: the experts this chip holds, a contiguous ``range``.
      top_k: experts per token; their weights are renormalised to sum 1.
      router: the rule that scores the experts, one of `ROUTERS`.
        ``softmax``: a softmax over all experts, the `top_k` largest, their
        probabilities divided by their sum. ``sigmoid``: each expert's score
        is a sigmoid of its own logit, and the chosen scores are divided by
        their sum plus `SIGMOID_NORM_EPS`. Its parameters hold ``bias``
        ``[num_experts_total]``, which **selects and does not weigh**: the
        `top_k` are taken of ``score + bias`` and weighted by the scores
        alone. A buffer that balances the experts' load from outside the
        loss: no gradient reaches it, and this layer never changes it.
      routed_scale: the renormalised weights are multiplied by it (a
        config's ``routed_scaling_factor``); 1 multiplies nothing.
      norm_eps: what the sigmoid rule's renormalisation adds to the chosen
        scores' sum.
    """

    def __init__(self, hidden: int, width: int, num_experts_total: int,
                 held: Sequence[int], top_k: int, router: str = "softmax",
                 routed_scale: float = 1.0,
                 norm_eps: float = SIGMOID_NORM_EPS):
        given = list(held)
        held = range(given[0], given[0] + len(given)) if given else range(0)
        if not (given and given == list(held) and 0 <= held.start
                and held.stop <= num_experts_total):
            raise ValueError(f"held experts {given} are not a range of the "
                             f"{num_experts_total} experts")
        if not 0 < top_k <= num_experts_total:
            raise ValueError(f"top_k {top_k} of {num_experts_total} experts")
        if router not in ROUTERS:
            raise ValueError(f"router rule {router!r}; the rules are {ROUTERS}")
        self.hidden, self.width = hidden, width
        self.num_experts_total, self.held, self.top_k = (
            num_experts_total, held, top_k)
        self.router = router
        self.routed_scale, self.norm_eps = routed_scale, norm_eps

    def init(self, key, std: float = 0.02, down_std: float = None,
             bias_range: float = 0.0) -> dict:
        """Normal draws at `std`; the down projections, which write to the
        residual stream, at `down_std` (default `std`); a sigmoid router's
        selection bias uniform in ``+-bias_range``."""
        kr, kg, ku, kd = jax.random.split(key, 4)
        down_std = std if down_std is None else down_std
        n, h, f = len(self.held), self.hidden, self.width
        params = {
            "router": std * jax.random.normal(kr, (h, self.num_experts_total)),
            "gate": std * jax.random.normal(kg, (n, h, f)),
            "up": std * jax.random.normal(ku, (n, h, f)),
            "down": down_std * jax.random.normal(kd, (n, f, h))}
        if self.router == "sigmoid":
            params["bias"] = jax.random.uniform(
                jax.random.fold_in(key, 4), (self.num_experts_total,),
                minval=-bias_range, maxval=bias_range)
        return params

    def route(self, router: jax.Array, x: jax.Array,
              bias: jax.Array = None) -> Routing:
        """The router's rule over all experts (`ROUTERS`): the `top_k`
        largest, of ``score + bias`` where a selection bias is given, and
        their scores renormalised, then scaled by `routed_scale`."""
        with stage("router"):
            if self.router == "softmax":
                scores = jax.nn.softmax(x @ router, axis=-1)
                top, experts = lax.top_k(scores, self.top_k)
                weights = top / jnp.sum(top, axis=-1, keepdims=True)
            else:
                scores = jax.nn.sigmoid(x @ router)
                ranked = (scores if bias is None
                          else scores + lax.stop_gradient(bias))
                _, experts = lax.top_k(ranked, self.top_k)
                top = jnp.take_along_axis(scores, experts, axis=-1)
                weights = top / (jnp.sum(top, axis=-1, keepdims=True)
                                 + self.norm_eps)
            if self.routed_scale != 1:
                weights = weights * self.routed_scale
            return Routing(experts.astype(jnp.int32), weights)

    def __call__(self, params: dict, x: jax.Array) -> jax.Array:
        """``[T, hidden] -> [T, hidden]``: the held experts' part of the
        layer's output."""
        routing = self.route(params["router"], x, params.get("bias"))
        with stage("experts"):
            return self._held_part(params, x, routing)

    def fast_rows(self, tokens: int) -> int:
        """Static rows of the sorted pair stream that the usual step
        computes over: twice what a uniform router sends here, in whole
        tiles, at most all ``tokens * top_k`` slots."""
        even = tokens * self.top_k * len(self.held) / self.num_experts_total
        tiles = -(-int(2 * even) // ROW_TILE)
        return min(tokens * self.top_k, max(tiles, 1) * ROW_TILE)

    def _held_part(self, params, x, routing: Routing):
        tokens, n = x.shape[0], len(self.held)
        slots = tokens * self.top_k
        # pairs in owner order: held experts first, by local index
        order = canonical_id_sort(routing.experts - self.held.start, n)
        starts = row_to_split(order.sid, n)            # [n + 1]
        sizes, count = starts[1:] - starts[:-1], starts[n]

        operands = ({k: params[k] for k in ("gate", "up", "down")}, x,
                    routing.weights, order.perm, sizes, count)
        fast = self.fast_rows(tokens)
        if fast == slots:
            return self._products(*operands, slots)
        return _over_fast_rows_or_all(self, fast, operands)

    def _products(self, params, x, weights, perm, sizes, count, rows: int):
        """The held experts' part from the first `rows` slots of the
        sorted pair stream, which hold every held pair. A slot behind the
        held pairs carries a row of zeros, and the last held expert's
        group is stretched over those slots: the grouped products then
        visit every one of the `rows` slots whatever the routing, so a
        step's time does not move with how many pairs a batch sent here
        while they fit (a pipeline stage's chips wait for the most loaded
        of them either way), and no slot holds what a kernel left
        unwritten. The zeros cost the products of up to as many rows again
        as an even router's share."""
        tokens = x.shape[0]
        pair = perm[:rows]                  # flat index t * top_k + j
        live = lax.iota(jnp.int32, rows) < count
        by_token = _token_order(
            jnp.where(live, pair // self.top_k, tokens), tokens)
        sizes = sizes.at[-1].add(rows - count)
        given = _rows_of_tokens(x, by_token, self.top_k)
        inner = (jax.nn.silu(grouped_matmul(given, params["gate"], sizes))
                 * grouped_matmul(given, params["up"], sizes))
        out = grouped_matmul(inner, params["down"], sizes)
        weight = jnp.take(weights.reshape(-1), pair)
        return _sum_to_tokens(out * weight[:, None], by_token, self.top_k)

    def routing_stats(self, params: dict, x: jax.Array) -> dict:
        """Forward only, what a batch's routing asks of this chip:
        ``held_pairs_share``, the share of the ``T * top_k`` pairs that
        picked a held expert (``len(held) / num_experts_total`` under a
        uniform router), ``max_expert_load_share``, the busiest held
        expert's share of the held pairs (``1 / len(held)`` when even),
        and, under the ``sigmoid`` rule, ``bias_moved_share``: the share of
        the tokens whose chosen set is another than their scores alone
        would choose."""
        chosen = self.route(params["router"], x, params.get("bias")).experts
        local = chosen - self.held.start
        loads = jnp.sum(local[:, :, None] == jnp.arange(len(self.held)),
                        axis=(0, 1))
        held = jnp.sum(loads)
        stats = {"held_pairs_share": held / local.size,
                 "max_expert_load_share": jnp.max(loads) / jnp.maximum(held, 1)}
        if self.router == "sigmoid":
            unbiased = self.route(params["router"], x).experts
            stats["bias_moved_share"] = jnp.mean(jnp.any(
                jnp.sort(chosen, axis=-1) != jnp.sort(unbiased, axis=-1),
                axis=-1))
        return stats


# Every gather, mask and scan of `_products` is paid by the row, held or
# not. So the step runs them over `fast` rows of the sorted stream while the
# held pairs fit there, and over every slot when they do not: nothing is
# dropped. The rule for the gradient is written out because `lax.cond` left
# to autodiff keeps both branches' residuals side by side (15.3 GiB of
# temporaries at the cell's size against 3.2): here each branch of the
# backward pass takes its own forward again and keeps nothing outside itself.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _over_fast_rows_or_all(layer, fast: int, operands):
    count, slots = operands[-1], operands[3].shape[0]
    return lax.cond(count <= fast, lambda: layer._products(*operands, fast),
                    lambda: layer._products(*operands, slots))


def _over_fast_rows_or_all_bwd(layer, fast, operands, g):
    params, x, weights, perm, sizes, count = operands

    def grads(rows):
        return jax.vjp(lambda params, x, weights: layer._products(
            params, x, weights, perm, sizes, count, rows),
            params, x, weights)[1](g)

    return (lax.cond(count <= fast, lambda: grads(fast),
                     lambda: grads(perm.shape[0])) + (None, None, None),)


_over_fast_rows_or_all.defvjp(
    lambda layer, fast, operands: (
        _over_fast_rows_or_all(layer, fast, operands), operands),
    _over_fast_rows_or_all_bwd)


class _TokenOrder(NamedTuple):
    """`rows` slots of the pair stream seen from their tokens: a second
    `canonical_id_sort`, by token, puts a token's pairs side by side."""
    token: jax.Array      # [rows] the slot's token; `tokens` behind the held
    slot: jax.Array       # [rows] token-major position -> slot
    offset: jax.Array     # [rows] token-major position inside its token's run
    last: jax.Array       # [tokens] token-major position of its last pair
    any: jax.Array        # [tokens] bool: the token has a held pair


def _token_order(token, tokens: int) -> _TokenOrder:
    order = canonical_id_sort(token, tokens)
    at = lax.iota(jnp.int32, token.shape[0])
    offset = at - lax.cummax(jnp.where(order.seg_start, at, -1))
    last = jnp.maximum(jnp.searchsorted(
        order.sid, jnp.arange(tokens, dtype=jnp.int32), side="right") - 1, 0)
    return _TokenOrder(token, order.perm, offset, last.astype(jnp.int32),
                       jnp.take(order.sid, last) == jnp.arange(tokens))


# Each token's row to its pairs' slots, and the slots' rows summed back to
# their tokens: each the other's transpose, and written out as such. Left to
# autodiff the transpose of a gather is a scatter-add, which the chip pays by
# the row (PERF.md section 5: 74 ns for a row of 128, against a streamed
# pass). A run of one token's pairs is at most `run` = top_k long, so
# log2(run) doubling steps sum it: `sparse_update.dedup_sum`'s scan, cut short.
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rows_of_tokens(x, by_token: _TokenOrder, run: int):
    """``[tokens, h] -> [rows, h]``: a slot holds its token's row, zeros
    behind the held pairs."""
    tokens = x.shape[0]
    return jnp.where((by_token.token < tokens)[:, None],
                     jnp.take(x, jnp.minimum(by_token.token, tokens - 1),
                              axis=0), 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sum_to_tokens(rows, by_token: _TokenOrder, run: int):
    """``[rows, h] -> [tokens, h]``: the sum of each token's slots."""
    x = jnp.take(rows, by_token.slot, axis=0)
    for d in (1 << level for level in range((run - 1).bit_length())):
        moved = lax.pad(x, jnp.zeros((), x.dtype), [(d, -d, 0), (0, 0, 0)])
        x = x + jnp.where((by_token.offset >= d)[:, None], moved, 0.0)
    return jnp.where(by_token.any[:, None],
                     jnp.take(x, by_token.last, axis=0), 0.0)


_rows_of_tokens.defvjp(
    lambda x, by_token, run: (_rows_of_tokens(x, by_token, run), by_token),
    lambda run, by_token, g: (_sum_to_tokens(g, by_token, run), None))
_sum_to_tokens.defvjp(
    lambda rows, by_token, run: (_sum_to_tokens(rows, by_token, run),
                                 by_token),
    lambda run, by_token, g: (_rows_of_tokens(g, by_token, run), None))
