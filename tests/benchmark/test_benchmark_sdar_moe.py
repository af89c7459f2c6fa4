"""The cell `sdar-30b.blockdiff-4k`'s own files: the configuration against
the numbers its source publishes, the share's parameter count, the
generator's batches and its count of the pairs the mask keeps, the
arithmetic of `blockdiff_attn_roofline`, a traced rehearsal over the cell's
readers, and where the chip's default precision rounds the program's and
the plain reference's products. The model against its plain reference is
`tests/test_sdar.py`'s."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run
from benchmark.builders import sdar_moe as builder
from benchmark.generators import block_diffusion
from benchmark.harness import blockdiff_attn_flops, spec
from benchmark.readers import blockdiff_attn_roofline
from benchmark.references import sdar_moe as reference
from product_rounding import with_rounded_products
from distributed_embeddings_tpu.models import sdar

CELL = "sdar-30b.blockdiff-4k"
CONFIG = spec.load_json("benchmark/configs/sdar-30b-a3b-chat.json")
TRAFFIC = spec.load_json("benchmark/traffic/blockdiff-4k.json")
# what the catalog's row gives under `config`
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
ATTN = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
QK_NORMS, NORMS, ROUTER, EXPERTS = 256, 4096, 262_144, 16 * 4_718_592


# ------------------------------------------------------- the configuration
def test_the_configuration_keeps_every_published_number():
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key + "_published"] == value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (4, 16, 18992)
    share = CONFIG["deployment"]["chips_sharing_a_layer"]
    assert CONFIG["num_experts"] * share == CONFIG["num_experts_published"]
    assert CONFIG["vocab_size"] * share == CONFIG["vocab_size_published"]
    assert CONFIG["num_hidden_layers_published"] % CONFIG[
        "num_hidden_layers"] == 0                         # 12 stages of 4
    assert CONFIG["deployment"]["first_layer_held"] == 0
    assert CONFIG["tokens_per_step"] * 2 == 4 * CONFIG["sequence_length"]
    # what the plain reference reads from the file is no rehearsal's to cut
    spec_read = reference.published_spec()
    assert not set(CONFIG["rehearse"]) & set(spec_read)
    assert spec_read == {"rope_theta": 1000000, "block_length": 4,
                         "num_experts_per_tok": 8, "first_expert_held": 0,
                         "rms_norm_eps": 1e-6}
    assert {"block_length", "noise_schedule", "mask_id", "prediction",
            "no_balancing_loss", "optimizer", "init_std", "no_other_module",
            "matmul_precision", "tokens_per_step"} <= set(CONFIG["assumed"])
    assert TRAFFIC["mask_eps"] == 1e-3


def test_the_builder_counts_the_parameters_and_flops_the_issue_counts():
    built = builder.build(CONFIG, None, False)
    shapes = jax.eval_shape(built.model.init, jax.random.PRNGKey(0))
    dense = built.dense_params(shapes)
    sizes = [sum(int(np.prod(a.shape)) for a in jax.tree.leaves(layer))
             for layer in dense["layers"]]
    assert ATTN == 18_874_368
    assert sizes == 4 * [ATTN + QK_NORMS + NORMS + ROUTER + EXPERTS]
    assert sizes[0] == 94_638_336
    trained = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(dense))
    assert trained == sum(sizes) + 2_048 + 38_895_616 == 417_451_008
    assert built.tables == [(18992, 2048)] and 18992 * 2048 == 38_895_616
    assert built.global_batch == 8192 and built.num_numerical == 4096
    assert built.numerical_scale == 4.0
    # a clean token's matmul flops, forward, by hand: k and v at its two
    # positions in 4 layers; q, o, the router and one held pair (8 x 16 /
    # 128) at two positions in 3 layers and one in the last; the head once
    kv, qo = 2 * 2 * 2048 * 512, 2 * 2 * 2048 * 4096
    mlp = 2 * 2048 * 128 + 3 * 2 * 2048 * 768
    forward = 8 * kv + 7 * (qo + mlp) + 2 * 2048 * 18992
    assert built.mlp_flops_per_sample == 3 * forward == 1_247_870_976
    model = built.model
    assert model.residual_std == pytest.approx(0.02 / 96 ** 0.5, rel=1e-12)
    assert (model.block, model.mask_id, model.norm_eps) == (4, 18991, 1e-6)
    assert (model.experts.router, model.experts.top_k,
            model.experts.num_experts_total) == ("softmax", 8, 128)
    assert model.experts.held == range(16)


@pytest.mark.parametrize("refused", [
    {"attention_bias": True}, {"rope_scaling": {"rope_type": "yarn"}},
    {"use_sliding_window": True}, {"mlp_only_layers": [2]},
    {"decoder_sparse_step": 2}, {"norm_topk_prob": False}])
def test_the_builder_refuses_what_the_program_does_not_run(refused):
    with pytest.raises(ValueError, match="no attention bias"):
        builder.build(dict(CONFIG, **refused), None, False)


# ------------------------------------------------------------ the generator
def test_the_generators_batches_hold_the_two_streams_and_their_weights():
    """A large seed, as the driver's are. Clean ids never the mask id; the
    noisy stream the clean one but at the masked tokens, which hold the
    mask id; a block's weight is one 1 / p of its own, at least 1 and at
    most 1 / mask_eps; about half the noisy stream masked; the same seed the
    same batches, and the record of the pairs equal to a count of the plain
    reference's mask."""
    traffic = dict(TRAFFIC, num_batches=2)
    seed = 2 ** 31 + 11
    batches = block_diffusion.generate(traffic, [(1000, 1)], 2048, 1024, 4.0,
                                       seed)
    assert len(batches) == len(block_diffusion.PAIRS) == 2
    masked_shares = []
    for (inputs, (ids,), weights), pairs in zip(batches,
                                                block_diffusion.PAIRS):
        positions, x0 = inputs[:, 0], inputs[:, 1]
        assert inputs.shape == (2, 2, 1024) and inputs.dtype == np.int32
        assert ids.shape == (4096, 1) and weights.shape == (2048,)
        streams = ids.reshape(2, 2, 1024)
        np.testing.assert_array_equal(streams[:, 1], x0)
        assert x0.max() < 999 and x0.min() >= 0
        masked = (weights > 0).reshape(2, 1024)
        np.testing.assert_array_equal(streams[:, 0] == 999, masked)
        np.testing.assert_array_equal(streams[:, 0][~masked], x0[~masked])
        assert weights[weights > 0].min() >= 1.0
        assert weights.max() <= 1.0 / TRAFFIC["mask_eps"] * (1 + 1e-6)
        block = np.cumsum(positions.reshape(-1) % 4 == 0)
        for b in np.unique(block[weights > 0])[:50]:
            assert len(np.unique(weights[(block == b) & (weights > 0)])) == 1
        masked_shares.append(masked.mean())
        by_mask = sum(int(np.asarray(reference.block_diffusion_mask(
            jnp.asarray(row), 4)).sum()) for row in positions)
        assert by_mask == 2 * pairs == 2 * block_diffusion.visible_pairs(
            positions, 4)
    assert 0.35 < np.mean(masked_shares) < 0.65
    again = block_diffusion.generate(traffic, [(1000, 1)], 2048, 1024, 4.0,
                                     seed)
    for one, two in zip(batches, again):
        np.testing.assert_array_equal(one[1][0], two[1][0])
        np.testing.assert_array_equal(one[2], two[2])
    with pytest.raises(ValueError, match="block of 2.5"):
        block_diffusion.generate(traffic, [(1000, 1)], 2048, 1024, 2.5, 0)


# --------------------------------------------------- the roofline's counts
def test_the_counts_file_gives_the_flops_and_the_reader_reads_them():
    """`blockdiff_attn_roofline` over the generator's record: the
    projections of the positions that query, key and value, and 4 x 128 x
    32 flops a visible pair of the 7 query streams of 4 layers, times 3."""
    pairs = 3_000_000
    flops = blockdiff_attn_flops.attn_flops_per_step(CONFIG, 8192, pairs)
    kv, qo = 2 * 2 * 2048 * 512, 2 * 2 * 2048 * 4096
    want = 3 * (kv * 2 * 8192 * 4 + qo * 8192 * 7 + 4 * 128 * 32 * pairs * 7)
    assert flops == want
    block_diffusion.PAIRS[:] = [2_900_000, 3_100_000]
    cell = types.SimpleNamespace(config=CONFIG, chips=1)
    ctx = types.SimpleNamespace(
        chips=[object()], steps=1, cell=cell, device_kind="TPU v5 lite",
        built=types.SimpleNamespace(global_batch=8192,
                                    mlp_flops_per_sample=1_247_870_976),
        notes=[], stage_partition={("attn", False): 40.0,
                                   ("attn", True): 80.0})
    params = spec.load_json("benchmark/layer_metrics/"
                            "blockdiff_attn_roofline.json")
    got = blockdiff_attn_roofline.read(ctx, params)
    least_ms = 1e3 * want / 197e12
    assert got == pytest.approx(100 * least_ms / 120.0, rel=1e-9)
    assert "3000000 visible pairs" in ctx.notes[0]
    assert "1247870976 flops a clean token" in ctx.notes[0]
    block_diffusion.PAIRS.clear()                # no batch made here
    assert blockdiff_attn_roofline.read(ctx, params) is None
    block_diffusion.PAIRS[:] = [pairs]
    ctx.stage_partition = {}                     # a program without scopes
    assert blockdiff_attn_roofline.read(ctx, params) is None
    block_diffusion.PAIRS.clear()


def test_a_traced_rehearsal_walks_the_cells_readers(capsys):
    """The control flow of a traced chip run on the CPU: the cell's metric
    files name readers that exist, and with no chip traced each returns
    nothing and does not raise. The cell reports the token cells' stage
    metrics and its own roofline, and not the two expert rooflines, whose
    counts read the step's batch as the positions through the experts."""
    code = run.main(["--workload", CELL, "--seed", "2147483659",
                     "--seconds", "0.2", "--trace", "1", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert code == 0
    last = json.loads(lines[-1])
    assert last["attempted"] == CONFIG["trace_steps"] and last["metrics"] == {}
    held = json.loads(next(ln for ln in lines if ln.startswith(
        "REFERENCE_CHECK ")).split(" ", 1)[1])
    assert held["ok"] is True, held
    assert len(block_diffusion.PAIRS) == TRAFFIC["num_batches"]
    listed = {m["name"] for m in spec.load_cell(CELL).per_layer}
    assert {"blockdiff_attn_roofline", "attn.stage_ms", "router.stage_ms",
            "experts.stage_ms", "head.stage_ms", "embed.lookup_stage_ms",
            "embed.apply_stage_ms", "dedup.stage_ms", "setup.init_s",
            "setup.export_s", "setup.compile_s", "setup.step_compile_s",
            "host.dispatch_ms", "device.idle_program_ms",
            "step_roofline"} <= listed
    assert not {"experts_roofline", "sparse_experts_roofline",
                "latent_roofline", "step.temp_gib"} & listed
    (metric,) = [m for m in spec.load_json("BENCHMARK.json")["per_layer"]
                 if m["name"] == "blockdiff_attn_roofline"]
    assert metric["workloads"] == [CELL] and metric["layer"] == "dense_model"


# ------------------------- where the chip's default precision rounds a product
SMALL = {"rope_theta": 10000, "block_length": 4, "num_experts_per_tok": 4,
         "first_expert_held": 4, "rms_norm_eps": 1e-6}


def small_case(seed):
    model = sdar.Sdar(
        vocab_rows=64, hidden=32, num_heads=4, num_kv_heads=2, head_dim=8,
        num_layers=3, rope={"rope_type": "default", "rope_theta": 10000},
        block=4, num_experts_total=16, held_experts=range(4, 8), top_k=4,
        expert_width=16)
    params = model.init(jax.random.PRNGKey(seed))
    params = jax.tree.map(lambda p: p * 8.0 if p.ndim > 1 else p, params)
    inputs, (ids,), _ = block_diffusion.generate(
        dict(TRAFFIC, document_median=14, document_min=3, num_batches=1),
        [(64, 1)], 96, 48, 4.0, seed)[0]
    return model, params, inputs, ids[:, 0]


def blocks_under_rounded_products(model, params, inputs, x):
    """The residual stream after each half of the model's blocks, both
    streams but the last attention's and MLP's, of the program and of the
    plain reference, every product's operands in bfloat16; and the
    reference's in f32."""
    positions = inputs[:, 0]
    n, length = positions.shape

    def program(params, x):
        document, blk = sdar.block_terms(positions, 4)
        stream = []
        for i, layer in enumerate(params["layers"]):
            x = model._attend(layer, x, positions, document, blk,
                              last=i == len(params["layers"]) - 1)
            stream.append(x)
            x = model._sparse_mlp(layer, x)
            stream.append(x)
        return stream

    def plain(params, x):
        eps, stream = SMALL["rms_norm_eps"], []
        for layer in params["layers"]:
            x = x + reference.attention(layer, x, inputs, SMALL)
            stream.append(x)
            x = x + reference.experts_held(
                layer["experts"], reference.rms_norm(
                    x, layer["post_attention_layernorm"], eps), n, SMALL)
            stream.append(x)
        # the last layer's noisy stream, which is all the program computes
        noisy = [s.reshape(n, 2, length, -1)[:, 0].reshape(n * length, -1)
                 for s in stream[-2:]]
        return stream[:-2] + noisy

    return (with_rounded_products(program)(params, x),
            with_rounded_products(plain)(params, x), plain(params, x))


def distance(a, b):
    return float(jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(b ** 2)))


def test_the_models_products_round_what_the_plain_references_round():
    """Rule (c) of the check holds a program only while its products round
    the values the reference's do (PERF.md section 6, PR 36). Under
    bfloat16 operands every half of the program's blocks stays within 1e-6
    of the reference's (f32 summation order), where the reference itself is
    over 3e-5 from its f32 values: the tiles, the two streams' shared
    projections and the grouped products read what the plain reference's
    products read."""
    model, params, inputs, ids = small_case(1)
    for layer in params["layers"]:        # norm weights that are not one
        layer["q_norm"] = jnp.linspace(0.6, 1.7, 8)
        layer["k_norm"] = jnp.linspace(1.5, 0.7, 8)
    (table,) = model.embedding.get_weights(params["embedding"])
    got, want, exact = blocks_under_rounded_products(
        model, {k: v for k, v in params.items() if k != "embedding"},
        jnp.asarray(inputs), jnp.asarray(table)[ids])
    assert distance(want[0], exact[0]) > 3e-5
    assert len(got) == 6
    assert max(distance(g, w) for g, w in zip(got, want)) < 1e-6
