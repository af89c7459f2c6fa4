"""One chip's share of an LFM2-style hybrid decoder on the training path.

The architecture (LiquidAI/LFM2-24B-A2B's ``config.json``, ``lfm2_moe``):
pre-norm blocks ``x -> h = x + Mixer(RMSNorm(x)) -> h + FFN(RMSNorm(h))``
whose layers differ in both halves. The mixer is, by ``layer_types``, a
gated short convolution (three of four layers) or full causal attention;
the FFN is a dense SwiGLU MLP in the first ``num_dense_layers`` layers and
a sparse one behind them. So a layer is declared as a pair ``(mixer, mlp)``:

* ``conv``: ``[B, C, u] = split3(x W_in)``, ``v = B * u``, a causal
  depthwise convolution of a few taps over ``v`` (`short_conv`), the output
  ``(C * conv(v)) W_out``. No bias.
* ``full_attention``: grouped-query attention, q and k RMS-normed over each
  head's width before the rotary embedding, causal inside the document.
* ``dense``: ``W_2 (silu(W_1 x) * W_3 x)``.
* ``sparse``: `ExpertLayer` under its ``sigmoid`` rule with a selection
  bias: the top k of ``score + bias``, weighted by the scores alone.

A final RMSNorm and an untied head follow. What a chip holds of it, a batch
of packed documents and the loss over a slice of the vocabulary are
`models/mellum.py`'s, and attention's blocks, the rotary code, the norm, the
document mask and the head's loss are imported from there.
"""

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from distributed_embeddings_tpu.layers.dist_model_parallel import (
    DistributedEmbedding)
from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.layers.experts import ExpertLayer
from distributed_embeddings_tpu.models.mellum import (
    INIT_STD, _attend_blocks, _normal_init, _rms_norm, _rotate, _table_init,
    embed_tokens, head_loss, packed_mask_terms, rotary_frequencies, swiglu)
from distributed_embeddings_tpu.obs.spans import spanned
from distributed_embeddings_tpu.obs.stages import stage

__all__ = ["Lfm2", "short_conv"]

MIXERS = ("conv", "full_attention")
MLPS = ("dense", "sparse")


def short_conv(v, taps, positions):
    """A causal depthwise convolution over packed documents. ``v [sequences,
    length, channels]``, ``taps [channels, K]``, ``positions [sequences,
    length]``: ``out_t = sum_j taps[:, j] * v_{t - (K - 1 - j)}``, where a
    position before the start of ``t``'s document reads as 0 (each document
    is a sequence of its own: the tap at distance ``d`` counts only where
    ``positions[t] >= d``). `K` shifted multiply-adds, oldest tap first."""
    reach = taps.shape[1] - 1
    out = None
    for j in range(reach + 1):
        d = reach - j
        if d:
            moved = jnp.pad(v, ((0, 0), (d, 0), (0, 0)))[:, :-d]
            term = jnp.where((positions >= d)[..., None], moved,
                             0.0) * taps[:, j]
        else:
            term = v * taps[:, j]
        out = term if out is None else out + term
    return out


class Lfm2:
    """Static configuration; ``init(key)`` returns the parameters and
    ``loss_fn`` is what `training.make_sparse_train_step` asks of a model.

    Args:
      vocab_rows: rows of the embedding and columns of the head held here.
      hidden, num_heads, num_kv_heads, head_dim: the published sizes.
      layers: per layer held here ``(mixer, mlp)``, of `MIXERS` and `MLPS`.
      rope: the config's ``rope_parameters`` (`mellum.rotary_frequencies`).
      conv_taps: a convolution's taps (``conv_L_cache``).
      dense_width: the dense MLP's inner width (``intermediate_size``).
      num_experts_total, held_experts, top_k, expert_width: `ExpertLayer`'s.
      bias_range: a router's selection bias is drawn uniform in
        ``+-bias_range``: the config publishes that there is one
        (``use_expert_bias``) and no value; a trained one is what its
        balancing rule left, small beside the scores' spread and not zero.
      norm_eps: every RMSNorm's epsilon.
      num_layers_total: the whole model's depth; the init of the matrices
        that write to the residual stream is scaled for it
        (`models/mellum.py`; default: the layers held).
      mesh: the embedding's device mesh (None: one device).

    As in `Mellum`, every product reads the values the layer's equations
    name, in their order (PERF.md section 6, PR 36).
    """

    def __init__(self, vocab_rows: int, hidden: int, num_heads: int,
                 num_kv_heads: int, head_dim: int,
                 layers: Sequence[Tuple[str, str]], rope: dict,
                 conv_taps: int, dense_width: int, num_experts_total: int,
                 held_experts: Sequence[int], top_k: int, expert_width: int,
                 bias_range: float, norm_eps: float = 1e-5,
                 num_layers_total: int = None, mesh=None):
        layers = tuple((mixer, mlp) for mixer, mlp in layers)
        for mixer, mlp in layers:
            if mixer not in MIXERS or mlp not in MLPS:
                raise ValueError(f"layer ({mixer!r}, {mlp!r}): a mixer is one "
                                 f"of {MIXERS}, an MLP one of {MLPS}")
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads over {num_kv_heads} "
                             "key/value heads")
        self.vocab_rows, self.hidden = vocab_rows, hidden
        self.num_heads, self.num_kv_heads, self.head_dim = (
            num_heads, num_kv_heads, head_dim)
        self.layers, self.conv_taps = layers, conv_taps
        self.dense_width, self.norm_eps = dense_width, norm_eps
        self.bias_range = bias_range
        self.residual_std = INIT_STD / math.sqrt(
            2 * (num_layers_total or len(layers)))
        self.rotary = rotary_frequencies(head_dim, rope)
        self.experts = ExpertLayer(hidden, expert_width, num_experts_total,
                                   held_experts, top_k, router="sigmoid")
        self.embedding = DistributedEmbedding(
            [Embedding(vocab_rows, hidden,
                       embeddings_initializer=_table_init)], mesh=mesh)
        self.mesh = mesh

    # ------------------------------------------------------------ parameters
    def _init_layer(self, key, mixer, mlp) -> dict:
        h, d = self.hidden, self.head_dim
        k1, k2, k3, k4, kf = jax.random.split(key, 5)

        def residual(key, shape):        # a matrix that writes to the stream
            return self.residual_std * jax.random.normal(key, shape)

        layer = {"operator_norm": jnp.ones(h), "ffn_norm": jnp.ones(h)}
        if mixer == "conv":
            layer.update(in_proj=_normal_init(k1, (h, 3 * h)),
                         conv=_normal_init(k2, (h, self.conv_taps)),
                         out_proj=residual(k3, (h, h)))
        else:
            layer.update(wq=_normal_init(k1, (h, self.num_heads * d)),
                         wk=_normal_init(k2, (h, self.num_kv_heads * d)),
                         wv=_normal_init(k3, (h, self.num_kv_heads * d)),
                         wo=residual(k4, (self.num_heads * d, h)),
                         q_norm=jnp.ones(d), k_norm=jnp.ones(d))
        if mlp == "dense":
            ka, kb, kc = jax.random.split(kf, 3)
            layer.update(w1=_normal_init(ka, (h, self.dense_width)),
                         w3=_normal_init(kb, (h, self.dense_width)),
                         w2=residual(kc, (self.dense_width, h)))
        else:
            layer["experts"] = self.experts.init(
                kf, INIT_STD, self.residual_std, bias_range=self.bias_range)
        return layer

    @spanned("model/init")
    def init(self, key) -> dict:
        ke, kh, *kl = jax.random.split(key, 2 + len(self.layers))
        return {"embedding": self.embedding.init(ke),
                "layers": [self._init_layer(k, *kinds)
                           for k, kinds in zip(kl, self.layers)],
                "embedding_norm": jnp.ones(self.hidden),
                "head": _normal_init(kh, (self.hidden, self.vocab_rows))}

    # --------------------------------------------------------------- forward
    def _convolve(self, layer, x, positions):
        with stage("shortconv"):
            n_seq, length = positions.shape
            normed = _rms_norm(x, layer["operator_norm"], self.norm_eps)
            b, c, u = jnp.split(normed @ layer["in_proj"], 3, axis=-1)
            mixed = short_conv((b * u).reshape(n_seq, length, -1),
                               layer["conv"], positions).reshape(x.shape)
            return x + (c * mixed) @ layer["out_proj"]

    def _attention(self, layer, x, positions, document):
        """``[T, hidden] -> [T, hidden]``, in `mellum._attend_blocks`' blocks
        over the whole sequence (no window)."""
        n_seq, length = positions.shape
        inv_freq, factor = self.rotary
        q = (x @ layer["wq"]).reshape(n_seq, length, self.num_heads,
                                      self.head_dim)
        k = (x @ layer["wk"]).reshape(n_seq, length, self.num_kv_heads,
                                      self.head_dim)
        v = (x @ layer["wv"]).reshape(k.shape)
        q = _rotate(_rms_norm(q, layer["q_norm"], self.norm_eps), positions,
                    inv_freq, factor)
        k = _rotate(_rms_norm(k, layer["k_norm"], self.norm_eps), positions,
                    inv_freq, factor)
        return _attend_blocks(q, k, v, document, length).reshape(
            n_seq * length, -1) @ layer["wo"]

    def _attend(self, layer, x, positions, document):
        with stage("attn"):
            return x + self._attention(
                layer, _rms_norm(x, layer["operator_norm"], self.norm_eps),
                positions, document)

    def _dense_mlp(self, layer, x):
        with stage("mlp"):
            normed = _rms_norm(x, layer["ffn_norm"], self.norm_eps)
            return x + swiglu(normed, layer["w1"], layer["w3"], layer["w2"])

    def _sparse_mlp(self, layer, x):
        # the expert layer opens its own two stages
        return x + self.experts(
            layer["experts"], _rms_norm(x, layer["ffn_norm"], self.norm_eps))

    def _mix(self, layer, mixer, x, positions, document):
        """A block's first half. What the backward pass takes again: a
        convolution from its input (its in-projection alone is 0.4 GB at the
        cell's size), each block of attention's scores; attention's
        projections are kept."""
        if mixer == "conv":
            return jax.checkpoint(self._convolve)(layer, x, positions)
        return self._attend(layer, x, positions, document)

    def _feed(self, layer, mlp, x):
        """A block's second half, taken again from its input in the backward
        pass (a dense MLP's three inner arrays are 2.2 GB at the cell's
        size)."""
        return jax.checkpoint(self._dense_mlp if mlp == "dense"
                              else self._sparse_mlp)(layer, x)

    def hidden_states(self, params, positions, x):
        """The blocks over embedded tokens ``x [T, hidden]``."""
        document, _ = packed_mask_terms(positions)
        for layer, (mixer, mlp) in zip(params["layers"], self.layers):
            x = self._mix(layer, mixer, x, positions, document)
            x = self._feed(layer, mlp, x)
        return x

    def loss_fn(self, params, positions, cats, next_ids, taps=None,
                return_residuals: bool = False):
        x, res = embed_tokens(self.embedding, params["embedding"], cats, taps,
                              return_residuals)
        x = self.hidden_states(params, positions, x)
        loss = head_loss(x, params["embedding_norm"], params["head"],
                         positions, next_ids, self.norm_eps)
        return (loss, res) if return_residuals else loss

    def routing_stats(self, params, positions, cats) -> dict:
        """Per sparse layer of this chip's blocks, `ExpertLayer.
        routing_stats` of what the layer's router saw: ``{name: [sparse
        layers] f32}``. Forward only; jit it."""
        (x,) = self.embedding(params["embedding"], list(cats))
        document, _ = packed_mask_terms(positions)
        stats = []
        for layer, (mixer, mlp) in zip(params["layers"], self.layers):
            x = self._mix(layer, mixer, x, positions, document)
            if mlp == "sparse":
                stats.append(self.experts.routing_stats(
                    layer["experts"],
                    _rms_norm(x, layer["ffn_norm"], self.norm_eps)))
            x = self._feed(layer, mlp, x)
        return {name: jnp.stack([s[name] for s in stats])
                for name in stats[0]}
