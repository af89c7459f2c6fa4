"""The cell `glm47-flash.packed-4k`'s own files: the configuration against
the numbers its source publishes, the share's parameter count, the
arithmetic of its rooflines, a traced rehearsal over its readers, and where
the chip's default precision rounds the program's and the plain reference's
products. The model against its plain reference, and each of its mechanisms
left out, is `tests/test_glm4_moe_lite.py`'s; a mechanism left out under the
check itself takes the cell's widths and is read on the chip (PERF.md
section 6, PR 42)."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run
from benchmark.builders import glm4_moe_lite as builder
from benchmark.generators import packed_documents
from benchmark.harness import latent_stage_flops, sparse_stage_flops, spec
from benchmark.readers import counted_stage_roofline
from benchmark.references import glm4_moe_lite as reference
from product_rounding import with_rounded_products
from distributed_embeddings_tpu.models import glm4_moe_lite, mellum

CELL = "glm47-flash.packed-4k"
CONFIG = spec.load_json("benchmark/configs/glm-4.7-flash.json")
TRAFFIC = spec.load_json("benchmark/traffic/packed-4k.json")
# what the catalog's row gives under `config`
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}
ATTN = (1_572_864 + 768 + 3_932_160 + 1_179_648 + 512 + 4_587_520
        + 10_485_760)
NORMS, ROUTER, SHARED, EXPERTS = 4_096, 131_072, 9_437_184, 75_497_472
DENSE_MLP = 3 * 2048 * 10240


# ------------------------------------------------------- the configuration
def test_the_configuration_keeps_every_published_number():
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key + "_published"] == value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"],
            CONFIG["num_experts"], CONFIG["vocab_size"]) == (4, 0, 8, 19360)
    # the benchmark's names for two counts, and the source's beside them
    assert CONFIG["source_keys"] == {
        "num_dense_layers": "first_k_dense_replace",
        "num_experts": "n_routed_experts"}
    for ours, theirs in CONFIG["source_keys"].items():
        assert CONFIG[ours + "_published"] == PUBLISHED[theirs] == CONFIG[theirs]
    share = CONFIG["deployment"]["chips_sharing_a_layer"]
    assert CONFIG["num_experts"] * share == CONFIG["num_experts_published"]
    assert CONFIG["vocab_size"] * share == CONFIG["vocab_size_published"]
    # the layers held: the four behind the one leading dense layer
    first = CONFIG["deployment"]["first_layer_held"]
    assert first + CONFIG["num_dense_layers"] == 1 == PUBLISHED[
        "first_k_dense_replace"]
    assert builder.held_layers(CONFIG) == 4 * [("mla", "sparse")]
    with_layer_0 = dict(CONFIG, num_hidden_layers=5, num_dense_layers=1)
    assert builder.held_layers(with_layer_0) == [("mla", "dense")] + 4 * [
        ("mla", "sparse")]
    assert (CONFIG["qk_nope_head_dim"] + CONFIG["qk_rope_head_dim"]
            == CONFIG["v_head_dim"] == 256)
    assert CONFIG["tokens_per_step"] == 4 * CONFIG["sequence_length"] == 16384
    # what the plain reference reads from the file is no rehearsal's to cut
    spec_read = reference.published_spec()
    assert not set(CONFIG["rehearse"]) & set(spec_read)
    assert set(CONFIG["rehearse"]) <= {
        "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "intermediate_size", "moe_intermediate_size", "vocab_size",
        "tokens_per_step", "sequence_length"}
    assert spec_read == {
        "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
        "rope_theta": 1000000, "num_experts_per_tok": 4,
        "routed_scaling_factor": 1.8, "first_expert_held": 0,
        "rms_norm_eps": 1e-5}
    # an init constant lives in one place
    assert not {"init_std", "residual_init_std", "table_init_std"} & set(CONFIG)
    assert (mellum.INIT_STD, mellum.TABLE_STD) == (0.02, 1.0)
    assert glm4_moe_lite.NORM_EPS == 1e-20
    assert {"rotary_pairing", "no_prediction_module", "renormalisation",
            "inner_norms", "expert_bias", "optimizer", "init_std",
            "tokens_per_step"} <= set(CONFIG["assumed"])


def test_the_builder_counts_the_parameters_and_flops_the_issue_counts():
    built = builder.build(CONFIG, None, False)
    shapes = jax.eval_shape(built.model.init, jax.random.PRNGKey(0))
    dense = built.dense_params(shapes)
    sizes = [sum(int(np.prod(a.shape)) for a in jax.tree.leaves(layer))
             for layer in dense["layers"]]
    bias = 64                   # a sparse layer's buffer, which nothing trains
    assert ATTN == 21_759_232
    assert sizes == 4 * [ATTN + NORMS + ROUTER + SHARED + EXPERTS + bias]
    assert sizes[0] - bias == 106_829_056
    trained = sum(sizes) - 4 * bias + 2_048 + 39_649_280
    assert trained == 466_967_552
    assert trained + 4 * bias == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(dense))
    # the leading dense layer, left on the stage before
    assert ATTN + NORMS + DENSE_MLP == 84_677_888
    assert built.tables == [(19360, 2048)] and built.hotness == [1]
    assert 19360 * 2048 == 39_649_280
    assert built.global_batch == 16384 and built.num_numerical == 4096
    # a token's matmul flops, forward, by hand: a layer's four latent
    # products and its out-projection, the router, the shared expert, half a
    # held pair; the head
    latent = 1_572_864 + 3_932_160 + 1_179_648 + 4_587_520
    assert latent_stage_flops.latent_weights(CONFIG) == latent == 11_272_192
    layer = (2 * latent + 2 * 5120 * 2048 + 2 * 2048 * 64
             + 1.5 * 6 * 2048 * 1536)
    forward = 4 * layer + 2 * 2048 * 19360
    assert forward == 367_656_960
    assert built.mlp_flops_per_sample == 3 * forward == 1_102_970_880
    assert builder.train_flops_per_token(with_layer(0)) == 3 * (
        forward + 2 * latent + 2 * 5120 * 2048 + 6 * 2048 * 10240)
    model = built.model
    assert model.residual_std == pytest.approx(0.02 / 94 ** 0.5, rel=1e-12)
    assert model.bias_range == CONFIG["expert_bias_range"] == 0.01
    assert (model.experts.routed_scale, model.experts.norm_eps,
            model.experts.router) == (1.8, 1e-20, "sigmoid")
    assert model.shared_width == 1536 and model.latent["eps"] == 1e-5
    assert {k: model.latent[k] for k in builder.LATENT_SIZES} == {
        "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256}


def with_layer(first):
    """The configuration with published layer 0 held too."""
    return dict(CONFIG, num_hidden_layers=5, num_dense_layers=1,
                deployment=dict(CONFIG["deployment"], first_layer_held=first))


@pytest.mark.parametrize("refused", [
    {"n_group": 8}, {"topk_group": 4}, {"attention_bias": True},
    {"rope_scaling": {"rope_type": "yarn", "factor": 4}},
    {"n_shared_experts": 2}, {"n_shared_experts": 0}])
def test_the_builder_refuses_what_the_program_does_not_run(refused):
    with pytest.raises(ValueError, match="group-limited choice of experts"):
        builder.build(dict(CONFIG, **refused), None, False)


def test_the_counts_file_gives_the_issues_flops_and_the_reader_reads_them():
    latent = latent_stage_flops.latent_flops_per_step(CONFIG, 16384)
    shared = latent_stage_flops.shared_flops_per_step(CONFIG, 16384)
    assert latent == 3 * 2 * 11_272_192 * 16384 * 4 == 4_432_406_249_472
    assert shared == 3 * 2 * 3 * 2048 * 1536 * 16384 * 4 == 3_710_851_743_744
    # the experts' count reads this file's keys as they are: LFM2's number
    assert sparse_stage_flops.sparse_expert_flops_per_step(
        CONFIG, 16384) == 1_855_425_871_872
    # published layer 0 has the latent products and no shared expert
    assert latent_stage_flops.latent_flops_per_step(
        with_layer(0), 16384) == 1.25 * latent
    assert latent_stage_flops.shared_flops_per_step(
        with_layer(0), 16384) == shared
    cell = types.SimpleNamespace(config=CONFIG, chips=1)
    ctx = types.SimpleNamespace(
        chips=[object()], steps=1, cell=cell, device_kind="TPU v5 lite",
        built=types.SimpleNamespace(global_batch=16384), notes=[],
        stage_partition={("latent", False): 15.0, ("latent", True): 30.0,
                         ("shared", False): 9.4184, ("shared", True): 28.2552})
    got = counted_stage_roofline.read(ctx, spec.load_json(
        "benchmark/layer_metrics/latent_roofline.json"))
    # 4.432e12 flops over 197 TFLOP/s = 22.4995 ms
    assert got == pytest.approx(100 * 22.4995 / 45.0, rel=1e-4)
    assert "22.4995 ms" in ctx.notes[0]
    got = counted_stage_roofline.read(ctx, spec.load_json(
        "benchmark/layer_metrics/shared_roofline.json"))
    # 3.711e12 flops over 197 TFLOP/s = 18.8368 ms
    assert got == pytest.approx(50.0, rel=1e-4)
    assert "18.8368 ms" in ctx.notes[1]
    ctx.stage_partition = {}               # a program without the scopes
    for metric in ("latent_roofline", "shared_roofline"):
        assert counted_stage_roofline.read(ctx, spec.load_json(
            f"benchmark/layer_metrics/{metric}.json")) is None
    with pytest.raises(spec.SpecError, match="no_such_counts"):
        ctx.stage_partition = {("latent", False): 1.0}
        counted_stage_roofline.read(ctx, {
            "scope": "latent", "counts": "no_such_counts", "flops": "f"})


def test_a_traced_rehearsal_walks_the_cells_readers(capsys):
    """The control flow of a traced chip run on the CPU: the cell's metric
    files name readers that exist, and with no chip traced each returns
    nothing and does not raise."""
    code = run.main(["--workload", CELL, "--seed", "2147483659",
                     "--seconds", "0.2", "--trace", "1", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert code == 0
    last = json.loads(lines[-1])
    assert last["attempted"] == CONFIG["trace_steps"] and last["metrics"] == {}
    held = json.loads(next(ln for ln in lines if ln.startswith(
        "REFERENCE_CHECK ")).split(" ", 1)[1])
    assert held["ok"] is True, held
    rehearsed = next(ln for ln in lines
                     if ln.startswith("REHEARSED_LAYER_METRICS "))
    assert json.loads(rehearsed.split(" ", 1)[1]) == {}
    cell = spec.load_cell(CELL)
    listed = {m["name"] for m in cell.per_layer}
    assert {"latent.stage_ms", "shared.stage_ms", "latent_roofline",
            "shared_roofline", "sparse_experts_roofline", "attn.stage_ms",
            "router.stage_ms", "experts.stage_ms", "head.stage_ms",
            "embed.lookup_stage_ms", "embed.apply_stage_ms", "dedup.stage_ms",
            "setup.init_s", "setup.export_s", "setup.compile_s",
            "setup.step_compile_s", "host.dispatch_ms",
            "device.idle_program_ms", "step_roofline"} <= listed
    # the cell holds no dense layer, no convolution, and every held layer
    # has experts only by this file's `num_dense_layers`
    assert not {"experts_roofline", "step.temp_gib", "mlp.stage_ms",
                "shortconv.stage_ms"} & listed
    bench = spec.load_json("BENCHMARK.json")
    for name in ("latent.stage_ms", "shared.stage_ms", "latent_roofline",
                 "shared_roofline"):
        (metric,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert metric["workloads"] == [CELL]
        assert metric["layer"] == "dense_model"


# ------------------------- where the chip's default precision rounds a product
THREE = (("mla", "dense"), ("mla", "sparse"), ("mla", "sparse"))
SMALL = {"qk_nope_head_dim": 6, "qk_rope_head_dim": 4, "rope_theta": 10000,
         "num_experts_per_tok": 4, "routed_scaling_factor": 1.8,
         "first_expert_held": 4, "rms_norm_eps": 1e-5}


def small_case(seed):
    model = glm4_moe_lite.Glm4MoeLite(
        vocab_rows=64, hidden=32, num_heads=4, q_lora_rank=12, kv_lora_rank=8,
        qk_nope_head_dim=6, qk_rope_head_dim=4, v_head_dim=8, layers=THREE,
        rope={"rope_type": "default", "rope_theta": 10000}, dense_width=48,
        num_experts_total=16, held_experts=range(4, 8), top_k=4,
        expert_width=16, routed_scale=1.8, shared_width=16, bias_range=0.01)
    params = model.init(jax.random.PRNGKey(seed))
    params = jax.tree.map(lambda p: p * 8.0 if p.ndim > 1 else p, params)
    positions, cats, _ = packed_documents.generate(
        dict(TRAFFIC, document_median=14, document_min=3, num_batches=1),
        [(64, 1)], 96, 48, 0.0, seed)[0]
    return model, params, positions, cats[0][:, 0]


def blocks_under_rounded_products(model, params, positions, x):
    """The residual stream after each half of the model's blocks, of the
    program and of the plain reference, every product's operands in
    bfloat16; and the reference's in f32."""
    def program(params, x):
        document, _ = mellum.packed_mask_terms(positions)
        stream = []
        for layer, (_, mlp) in zip(params["layers"], model.layers):
            x = model._mix(layer, x, positions, document)
            stream.append(x)
            x = model._feed(layer, mlp, x)
            stream.append(x)
        return stream

    def plain(params, x):
        eps, n_seq, stream = SMALL["rms_norm_eps"], positions.shape[0], []
        for layer in params["layers"]:
            x = x + reference.latent_attention(layer, x, positions, SMALL)
            stream.append(x)
            if "experts" not in layer:
                x = x + reference.dense_ffn(layer, x, n_seq, eps)
            else:
                x = x + reference.sparse_ffn(
                    layer, reference.rms_norm(
                        x, layer["post_attention_layernorm"], eps),
                    n_seq, SMALL)
            stream.append(x)
        return stream

    return (with_rounded_products(program)(params, x),
            with_rounded_products(plain)(params, x), plain(params, x))


def distance(a, b):
    return float(jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(b ** 2)))


_latent_qkv = glm4_moe_lite.latent_qkv


def absorbed_up_projection(layer, x, positions, **sizes):
    """The mutation: the queries' two projections merged into one product
    over the norm's weight, as an inference path absorbs them: the same
    mathematics, other values rounded."""
    merged = dict(layer, q_a_layernorm=jnp.ones_like(layer["q_a_layernorm"]),
                  q_b_proj=layer["q_a_layernorm"][:, None] * layer["q_b_proj"])
    return _latent_qkv(merged, x, positions, **sizes)


@pytest.mark.parametrize("mutated", [False, True])
def test_the_models_products_round_what_the_plain_references_round(
        mutated, monkeypatch):
    """Rule (c) of the check holds a program only while its products round
    the values the reference's do (PERF.md section 6, PR 36). Under bfloat16
    operands every half of the program's blocks stays within 1e-6 of the
    reference's (f32 summation order) where the reference itself is over
    3e-5 from its f32 values; a latent block that folds the queries' norm
    weight into the up-projection is its own draw of that rounding."""
    if mutated:
        monkeypatch.setattr(glm4_moe_lite, "latent_qkv",
                            absorbed_up_projection)
    model, params, positions, ids = small_case(1)
    for layer in params["layers"]:        # a norm weight that is not one
        layer["q_a_layernorm"] = jnp.linspace(0.6, 1.7, 12)
    (table,) = model.embedding.get_weights(params["embedding"])
    got, want, exact = blocks_under_rounded_products(
        model, {k: v for k, v in params.items() if k != "embedding"},
        jnp.asarray(positions), jnp.asarray(table)[ids])
    first_half = distance(got[0], want[0])        # the first latent block
    rounding = distance(want[0], exact[0])
    assert rounding > 3e-5
    if mutated:
        assert first_half > 0.3 * rounding
        return
    assert len(got) == 6
    assert max(distance(g, w) for g, w in zip(got, want)) < 1e-6
