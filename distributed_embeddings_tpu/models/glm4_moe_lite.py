"""One chip's share of a GLM-4.7-Flash-style decoder on the training path.

The architecture (zai-org/GLM-4.7-Flash's ``config.json``, ``glm4_moe_lite``):
pre-norm blocks ``x -> h = x + Attn(RMSNorm(x)) -> h + FFN(RMSNorm(h))``.
Every layer's attention is multi-head latent attention (`latent_qkv`):
queries pass a narrow bottleneck with a norm inside it, keys and values are
expanded per head from one normed latent vector a token, and the rotary part
of a head is projected apart from the rest, **one rotary key a token shared
by every head**. The FFN is a dense SwiGLU MLP in the leading layers and a
sparse one behind them, so a layer is declared as a pair ``(mixer, mlp)``:

* ``mla``: the one mixer.
* ``dense``: ``W_2 (silu(W_1 x) * W_3 x)``.
* ``sparse``: `ExpertLayer` under its ``sigmoid`` rule with a selection
  bias, the renormalised weights times the routed scaling factor, **plus a
  shared expert**: one more SwiGLU that every token passes, unweighted, and
  that every chip of an expert-parallel deployment computes for its own
  tokens (stage ``shared``).

A final RMSNorm and an untied head follow. What a chip holds of it, a batch
of packed documents and the loss over a slice of the vocabulary are
`models/mellum.py`'s, and attention's blocks, the rotary code, the norm, the
document mask, the SwiGLU and the head's loss are imported from there.
"""

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from distributed_embeddings_tpu.layers.dist_model_parallel import (
    DistributedEmbedding)
from distributed_embeddings_tpu.layers.embedding import Embedding
from distributed_embeddings_tpu.layers.experts import ExpertLayer
from distributed_embeddings_tpu.models.mellum import (
    INIT_STD, _attend_blocks, _normal_init, _rms_norm, _rotate, _table_init,
    embed_tokens, head_loss, packed_mask_terms, rotary_frequencies, swiglu)
from distributed_embeddings_tpu.obs.spans import spanned
from distributed_embeddings_tpu.obs.stages import stage

__all__ = ["Glm4MoeLite", "latent_qkv"]

MIXERS = ("mla",)
MLPS = ("dense", "sparse")
# what the sigmoid rule's renormalisation adds to the chosen scores' sum in
# this family's published library
NORM_EPS = 1e-20
# attention's output in front of its out-projection: the one array of a
# block's first half that the backward pass is handed and does not take again
ATTENDED = "det_attended"


def latent_qkv(layer, x, positions, *, num_heads: int, q_lora_rank: int,
               kv_lora_rank: int, qk_nope_head_dim: int,
               qk_rope_head_dim: int, v_head_dim: int, rotary, eps: float):
    """Multi-head latent attention's queries, keys and values of normed
    tokens ``x [T, hidden]``, ``positions [sequences, length]``:

      ``c_q = RMSNorm(x W_qa)``, ``[q_nope | q_rope] = c_q W_qb`` per head;
      ``[c_kv | k_rope] = x W_kva``, ``c_kv = RMSNorm(c_kv)``,
      ``[k_nope | v] = c_kv W_kvb`` per head;
      rotary on each head's ``q_rope`` and on the ONE ``k_rope`` a token;
      ``q = [q_nope | q_rope]``, ``k = [k_nope | k_rope]``, the same rotated
      ``k_rope`` under every head.

    Returns ``q, k [sequences, length, heads, nope + rope]`` and ``v [...,
    heads, v_head_dim]``: plain per-head arrays for `_attend_blocks`.
    Nothing is absorbed into a neighbouring product: each of the four
    projections is a product of its own with the norm between them, as the
    plain reference writes them. `rotary` is `rotary_frequencies` of the
    rope width."""
    n_seq, length = positions.shape
    inv_freq, factor = rotary
    c_q = _rms_norm(x @ layer["q_a_proj"], layer["q_a_layernorm"], eps)
    if c_q.shape[-1] != q_lora_rank:
        raise ValueError(f"q_a_proj is {c_q.shape[-1]} wide, not "
                         f"q_lora_rank {q_lora_rank}")
    q = (c_q @ layer["q_b_proj"]).reshape(
        n_seq, length, num_heads, qk_nope_head_dim + qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [qk_nope_head_dim], axis=-1)
    c_kv, k_rope = jnp.split(x @ layer["kv_a_proj"], [kv_lora_rank], axis=-1)
    if k_rope.shape[-1] != qk_rope_head_dim:
        raise ValueError(f"kv_a_proj leaves {k_rope.shape[-1]} beside "
                         f"kv_lora_rank {kv_lora_rank}, not "
                         f"qk_rope_head_dim {qk_rope_head_dim}")
    c_kv = _rms_norm(c_kv, layer["kv_a_layernorm"], eps)
    kv = (c_kv @ layer["kv_b_proj"]).reshape(
        n_seq, length, num_heads, qk_nope_head_dim + v_head_dim)
    k_nope, v = jnp.split(kv, [qk_nope_head_dim], axis=-1)
    q_rope = _rotate(q_rope, positions, inv_freq, factor)
    k_rope = _rotate(k_rope.reshape(n_seq, length, 1, qk_rope_head_dim),
                     positions, inv_freq, factor)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, q_rope.shape)], axis=-1)
    return q, k, v


class Glm4MoeLite:
    """Static configuration; ``init(key)`` returns the parameters and
    ``loss_fn`` is what `training.make_sparse_train_step` asks of a model.

    Args:
      vocab_rows: rows of the embedding and columns of the head held here.
      hidden, num_heads: the published sizes.
      q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
        v_head_dim: latent attention's five sizes (`latent_qkv`).
      layers: per layer held here ``(mixer, mlp)``, of `MIXERS` and `MLPS`.
      rope: the rotary entry of the rope width (`mellum.rotary_frequencies`).
      dense_width: the dense MLP's inner width (``intermediate_size``).
      num_experts_total, held_experts, top_k, expert_width: `ExpertLayer`'s.
      routed_scale: the config's ``routed_scaling_factor``.
      shared_width: the shared expert's inner width (``n_shared_experts *
        moe_intermediate_size``).
      bias_range: a router's selection bias is drawn uniform in
        ``+-bias_range`` (the config publishes the buffer and no value).
      norm_eps: every RMSNorm's epsilon, the two inside attention too.
      num_layers_total: the whole model's depth; the init of the matrices
        that write to the residual stream is scaled for it (default: the
        layers held), and a routed expert's down projection by
        ``1 / routed_scale`` besides.
      mesh: the embedding's device mesh (None: one device).

    As in `Mellum`, every product reads the values the layer's equations
    name, in their order (PERF.md section 6, PR 36).
    """

    def __init__(self, vocab_rows: int, hidden: int, num_heads: int,
                 q_lora_rank: int, kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int,
                 layers: Sequence[Tuple[str, str]], rope: dict,
                 dense_width: int, num_experts_total: int,
                 held_experts: Sequence[int], top_k: int, expert_width: int,
                 routed_scale: float, shared_width: int, bias_range: float,
                 norm_eps: float = 1e-5, num_layers_total: int = None,
                 mesh=None):
        layers = tuple((mixer, mlp) for mixer, mlp in layers)
        for mixer, mlp in layers:
            if mixer not in MIXERS or mlp not in MLPS:
                raise ValueError(f"layer ({mixer!r}, {mlp!r}): a mixer is one "
                                 f"of {MIXERS}, an MLP one of {MLPS}")
        self.vocab_rows, self.hidden = vocab_rows, hidden
        self.latent = dict(
            num_heads=num_heads, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rotary=rotary_frequencies(qk_rope_head_dim, rope), eps=norm_eps)
        self.layers, self.dense_width = layers, dense_width
        self.shared_width, self.bias_range = shared_width, bias_range
        self.norm_eps = norm_eps
        self.residual_std = INIT_STD / math.sqrt(
            2 * (num_layers_total or len(layers)))
        self.experts = ExpertLayer(hidden, expert_width, num_experts_total,
                                   held_experts, top_k, router="sigmoid",
                                   routed_scale=routed_scale,
                                   norm_eps=NORM_EPS)
        self.embedding = DistributedEmbedding(
            [Embedding(vocab_rows, hidden,
                       embeddings_initializer=_table_init)], mesh=mesh)
        self.mesh = mesh

    # ------------------------------------------------------------ parameters
    def _init_layer(self, key, mlp) -> dict:
        h, sizes = self.hidden, self.latent
        heads, nope, rope = (sizes["num_heads"], sizes["qk_nope_head_dim"],
                             sizes["qk_rope_head_dim"])
        kqa, kqb, kva, kvb, ko, kf, ks = jax.random.split(key, 7)

        def residual(key, shape):        # a matrix that writes to the stream
            return self.residual_std * jax.random.normal(key, shape)

        layer = {
            "input_layernorm": jnp.ones(h),
            "post_attention_layernorm": jnp.ones(h),
            "q_a_proj": _normal_init(kqa, (h, sizes["q_lora_rank"])),
            "q_a_layernorm": jnp.ones(sizes["q_lora_rank"]),
            "q_b_proj": _normal_init(
                kqb, (sizes["q_lora_rank"], heads * (nope + rope))),
            "kv_a_proj": _normal_init(
                kva, (h, sizes["kv_lora_rank"] + rope)),
            "kv_a_layernorm": jnp.ones(sizes["kv_lora_rank"]),
            "kv_b_proj": _normal_init(
                kvb, (sizes["kv_lora_rank"],
                      heads * (nope + sizes["v_head_dim"]))),
            "o_proj": residual(ko, (heads * sizes["v_head_dim"], h))}
        if mlp == "dense":
            ka, kb, kc = jax.random.split(kf, 3)
            layer.update(w1=_normal_init(ka, (h, self.dense_width)),
                         w3=_normal_init(kb, (h, self.dense_width)),
                         w2=residual(kc, (self.dense_width, h)))
        else:
            ka, kb, kc = jax.random.split(ks, 3)
            # the routed sum is multiplied by `routed_scale` on its way to
            # the stream: its writers are drawn that much smaller, so that
            # what the routed part writes at a random init is what the other
            # residual writers write (PERF.md section 6, PR 42)
            layer["experts"] = self.experts.init(
                kf, INIT_STD, self.residual_std / self.experts.routed_scale,
                bias_range=self.bias_range)
            layer["shared"] = {
                "gate": _normal_init(ka, (h, self.shared_width)),
                "up": _normal_init(kb, (h, self.shared_width)),
                "down": residual(kc, (self.shared_width, h))}
        return layer

    @spanned("model/init")
    def init(self, key) -> dict:
        ke, kh, *kl = jax.random.split(key, 2 + len(self.layers))
        return {"embedding": self.embedding.init(ke),
                "layers": [self._init_layer(k, mlp)
                           for k, (_, mlp) in zip(kl, self.layers)],
                "norm": jnp.ones(self.hidden),
                "head": _normal_init(kh, (self.hidden, self.vocab_rows))}

    # --------------------------------------------------------------- forward
    def _attention(self, layer, x, positions, document):
        """``[T, hidden] -> [T, hidden]`` of normed tokens, in
        `mellum._attend_blocks`' blocks over the whole sequence."""
        n_seq, length = positions.shape
        with stage("latent"):
            q, k, v = latent_qkv(layer, x, positions, **self.latent)
        attended = checkpoint_name(
            _attend_blocks(q, k, v, document, length), ATTENDED)
        return attended.reshape(n_seq * length, -1) @ layer["o_proj"]

    def _attend(self, layer, x, positions, document):
        with stage("attn"):
            return x + self._attention(
                layer, _rms_norm(x, layer["input_layernorm"], self.norm_eps),
                positions, document)

    def _dense_mlp(self, layer, x):
        with stage("mlp"):
            normed = _rms_norm(x, layer["post_attention_layernorm"],
                               self.norm_eps)
            return x + swiglu(normed, layer["w1"], layer["w3"], layer["w2"])

    def _sparse_mlp(self, layer, x):
        normed = _rms_norm(x, layer["post_attention_layernorm"],
                           self.norm_eps)
        routed = self.experts(layer["experts"], normed)   # its two stages
        with stage("shared"):
            shared = swiglu(normed, layer["shared"]["gate"],
                            layer["shared"]["up"], layer["shared"]["down"])
        return x + (routed + shared)

    def _mix(self, layer, x, positions, document):
        """A block's first half. The backward pass keeps the block's input
        and attention's output (`ATTENDED`, ``[T, heads * v_head_dim]``) and
        takes the rest again: the four latent products and the assembly of
        q, k and v (1 GB a layer of per-head arrays at the cell's size,
        kept for no longer than their layer's backward pass) and, block by
        block, the scores (`mellum._scores_to_values`); the forward's scores
        are not taken a third time, since their result is what is kept."""
        return jax.checkpoint(
            self._attend,
            policy=jax.checkpoint_policies.save_only_these_names(ATTENDED))(
                layer, x, positions, document)

    def _feed(self, layer, mlp, x):
        """A block's second half, taken again from its input in the backward
        pass."""
        return jax.checkpoint(self._dense_mlp if mlp == "dense"
                              else self._sparse_mlp)(layer, x)

    def hidden_states(self, params, positions, x):
        """The blocks over embedded tokens ``x [T, hidden]``."""
        document, _ = packed_mask_terms(positions)
        for layer, (_, mlp) in zip(params["layers"], self.layers):
            x = self._mix(layer, x, positions, document)
            x = self._feed(layer, mlp, x)
        return x

    def loss_fn(self, params, positions, cats, next_ids, taps=None,
                return_residuals: bool = False):
        x, res = embed_tokens(self.embedding, params["embedding"], cats, taps,
                              return_residuals)
        x = self.hidden_states(params, positions, x)
        loss = head_loss(x, params["norm"], params["head"], positions,
                         next_ids, self.norm_eps)
        return (loss, res) if return_residuals else loss

    def routing_stats(self, params, positions, cats) -> dict:
        """Per sparse layer of this chip's blocks, `ExpertLayer.
        routing_stats` of what the layer's router saw: ``{name: [sparse
        layers] f32}``. Forward only; jit it."""
        (x,) = self.embedding(params["embedding"], list(cats))
        document, _ = packed_mask_terms(positions)
        stats = []
        for layer, (_, mlp) in zip(params["layers"], self.layers):
            x = self._mix(layer, x, positions, document)
            if mlp == "sparse":
                stats.append(self.experts.routing_stats(
                    layer["experts"],
                    _rms_norm(x, layer["post_attention_layernorm"],
                              self.norm_eps)))
            x = self._feed(layer, mlp, x)
        return {name: jnp.stack([s[name] for s in stats])
                for name in stats[0]}
