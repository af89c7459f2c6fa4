"""The cell `lfm2.packed-4k`'s own files: the configuration against the
numbers its source publishes, the share's parameter count, the arithmetic of
its rooflines, a traced rehearsal over its readers, a table update left out
under the cell's lr, and where the chip's default precision rounds the
program's and the plain reference's products. The model against its plain
reference is `tests/test_lfm2.py`'s."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run
from benchmark.reference import learning_rate as reference_lr
from benchmark.builders import lfm2 as builder
from benchmark.generators import packed_documents
from benchmark.harness import check, sparse_stage_flops, spec, stage_flops
from benchmark.readers import sparse_stage_roofline
from benchmark.references import lfm2 as reference
from product_rounding import with_rounded_products
from distributed_embeddings_tpu.models import lfm2, mellum

CONFIG = spec.load_json("benchmark/configs/lfm2-24b-a2b.json")
TRAFFIC = spec.load_json("benchmark/traffic/packed-4k.json")
# what the catalog's row gives under `config`, numbers and groups
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
    "layer_types": ["conv", "conv", "full_attention", "conv"]}
# the share: published layers 1-5, the last leading dense layer and one
# period behind the dense layers
PERIOD = [("full_attention", "sparse"), ("conv", "sparse"),
          ("conv", "sparse"), ("conv", "sparse")]
FIVE = [("conv", "dense")] + PERIOD
CONV, ATTN = 16_783_360, 10_485_888
EXPERTS, ROUTER, DENSE_MLP, NORMS = 75_497_472, 131_072, 72_351_744, 4_096


# ------------------------------------------------------- the configuration
def test_the_configuration_keeps_every_published_number():
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key + "_published"] == value, key
        elif key == "layer_types":
            assert CONFIG[key] == value * 10 and len(CONFIG[key]) == 40
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"],
            CONFIG["num_experts"], CONFIG["vocab_size"]) == (5, 1, 8, 8192)
    share = CONFIG["deployment"]["chips_sharing_a_layer"]
    assert CONFIG["num_experts"] * share == CONFIG["num_experts_published"]
    assert CONFIG["vocab_size"] * share == CONFIG["vocab_size_published"]
    # the layers held: the last of the published model's leading dense
    # layers and a whole period behind them
    first = CONFIG["deployment"]["first_layer_held"]
    assert first + CONFIG["num_dense_layers"] == 2 == PUBLISHED[
        "num_dense_layers"]
    assert builder.held_layers(CONFIG) == FIVE
    assert sorted(m for m, _ in PERIOD) == sorted(PUBLISHED["layer_types"])
    retreat = dict(CONFIG, num_hidden_layers=4, num_dense_layers=0,
                   deployment=dict(CONFIG["deployment"], first_layer_held=2))
    assert builder.held_layers(retreat) == PERIOD
    assert CONFIG["head_dim"] * CONFIG["num_attention_heads"] == 2048
    assert CONFIG["tokens_per_step"] == 4 * CONFIG["sequence_length"] == 16384
    assert set(CONFIG["rehearse"]) <= {
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "intermediate_size", "moe_intermediate_size", "vocab_size",
        "tokens_per_step", "sequence_length"}, (
            "the plain reference reads the rest from the file")
    # an init constant lives in one place: the bias's range here, the
    # matrices' and the table's scales in the model's module
    assert not {"init_std", "residual_init_std", "table_init_std"} & set(CONFIG)
    assert (mellum.INIT_STD, mellum.TABLE_STD) == (0.02, 1.0)
    spec_read = reference.published_spec()
    assert spec_read["layer_types"] == [m for m, _ in FIVE]
    assert spec_read["num_dense_layers"] == 1 and spec_read["head_dim"] == 64


def test_the_builder_counts_the_parameters_and_flops_the_issue_counts():
    built = builder.build(CONFIG, None, False)
    shapes = jax.eval_shape(built.model.init, jax.random.PRNGKey(0))
    dense = built.dense_params(shapes)
    sizes = [sum(int(np.prod(a.shape)) for a in jax.tree.leaves(layer))
             for layer in dense["layers"]]
    bias = 64                   # a sparse layer's buffer, which nothing trains
    assert sizes == [CONV + DENSE_MLP + NORMS] + [
        ATTN + EXPERTS + ROUTER + NORMS + bias] + 3 * [
        CONV + EXPERTS + ROUTER + NORMS + bias]
    assert (sizes[0], sizes[1] - bias, sizes[2] - bias) == (
        89_139_200, 86_118_528, 92_416_000)
    trained = sum(sizes) - 4 * bias + 2_048 + 16_777_216
    assert trained == 469_284_992
    # ISSUE 38's retreat, not taken: without published layer 1
    assert trained - 89_139_200 == 380_145_792
    assert trained + 4 * bias == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(dense))
    assert built.tables == [(8192, 2048)] and built.hotness == [1]
    assert built.global_batch == 16384 and built.num_numerical == 4096
    # a token's matmul flops, forward, by hand: four convolutions' two
    # projections, attention's four, the dense MLP's three, four routers and
    # half a held pair a sparse layer, the head
    conv, attn = 8 * 2048 ** 2, 2 * 2048 * 64 * 48 + 2 * 64 * 32 * 2048
    sparse = 2 * 2048 * 64 + 0.5 * 6 * 2048 * 1536
    forward = (4 * conv + attn + 6 * 2048 * 11776 + 4 * sparse
               + 2 * 2048 * 8192)
    assert forward == 372_244_480
    assert built.mlp_flops_per_sample == 3 * forward == 1_116_733_440
    retreat = dict(CONFIG, num_hidden_layers=4, num_dense_layers=0,
                   deployment=dict(CONFIG["deployment"], first_layer_held=2))
    assert builder.train_flops_per_token(retreat) == 3 * (
        forward - conv - 6 * 2048 * 11776) == 581_959_680
    assert built.model.residual_std == pytest.approx(0.02 / 80 ** 0.5,
                                                     rel=1e-12)
    assert built.model.bias_range == CONFIG["expert_bias_range"] == 0.01
    for refused in ({"routed_scaling_factor": 2.5}, {"use_expert_bias": False}):
        with pytest.raises(ValueError, match="routed scaling factor"):
            builder.build(dict(CONFIG, **refused), None, False)


def test_the_sparse_experts_roofline_counts_the_layers_that_have_experts():
    """`stage_flops.expert_flops_per_step` counts ``num_hidden_layers``
    layers of experts, 5/4 of the truth for a share that holds a dense
    layer: the cell brings `sparse_experts_roofline`, the same count over
    the layers that have experts, and does not list `experts_roofline`."""
    assert CONFIG["num_hidden_layers"] - CONFIG["num_dense_layers"] == 4
    # 18 x 2,048 x 1,536 x 8,192 held pairs x 4 layers with experts
    flops = sparse_stage_flops.sparse_expert_flops_per_step(CONFIG, 16384)
    assert flops == 18 * 2048 * 1536 * 8192 * 4 == 1_855_425_871_872
    assert stage_flops.expert_flops_per_step(CONFIG, 16384) == 1.25 * flops
    cell = types.SimpleNamespace(config=CONFIG, chips=1)
    ctx = types.SimpleNamespace(
        chips=[object()], steps=1, cell=cell, device_kind="TPU v5 lite",
        built=types.SimpleNamespace(global_batch=16384), notes=[],
        stage_partition={("experts", False): 4.7092, ("experts", True): 14.1276})
    got = sparse_stage_roofline.read(
        ctx, {"scope": "experts", "flops": "sparse_expert_flops_per_step"})
    # 1.855e12 flops over 197 TFLOP/s = 9.4184 ms
    assert got == pytest.approx(50.0, rel=1e-4)
    assert "9.4184 ms" in ctx.notes[0]
    ctx.stage_partition = {}               # a program without the scope
    assert sparse_stage_roofline.read(
        ctx, {"scope": "experts",
              "flops": "sparse_expert_flops_per_step"}) is None


def test_a_traced_rehearsal_walks_the_cells_readers(capsys):
    """The control flow of a traced chip run on the CPU: the cell's metric
    files name readers that exist, and with no chip traced each returns
    nothing and does not raise."""
    code = run.main(["--workload", "lfm2.packed-4k", "--seed", "2147483659",
                     "--seconds", "0.2", "--trace", "1", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert code == 0
    last = json.loads(lines[-1])
    assert last["attempted"] == CONFIG["trace_steps"] and last["metrics"] == {}
    check = json.loads(next(ln for ln in lines if ln.startswith(
        "REFERENCE_CHECK ")).split(" ", 1)[1])
    assert check["ok"] is True, check
    rehearsed = next(ln for ln in lines
                     if ln.startswith("REHEARSED_LAYER_METRICS "))
    assert json.loads(rehearsed.split(" ", 1)[1]) == {}
    cell = spec.load_cell("lfm2.packed-4k")
    listed = {m["name"] for m in cell.per_layer}
    assert {"shortconv.stage_ms", "mlp.stage_ms", "sparse_experts_roofline",
            "attn.stage_ms", "router.stage_ms", "experts.stage_ms",
            "head.stage_ms", "embed.lookup_stage_ms", "embed.apply_stage_ms",
            "dedup.stage_ms", "step_roofline"} <= listed
    # the first would count 5/4 of the experts' work; the second's reader
    # builds a click model's batch (PERF.md section 7)
    assert not {"experts_roofline", "step.temp_gib"} & listed


def test_a_table_update_left_out_is_not_correct_at_the_cells_lr():
    """The check's own comparison (`harness/check.compare`) at the cell's
    schedule (lr 0, 1.5e-6 and 3e-6 on the three checked steps: a warm-up of
    200 steps to 3e-4) and the cell's rows (std 1): the program's steps
    pass, and the same steps with the table left as it was (the probed rows
    read back unchanged) fail rule (c) several times over. At a fifth of
    that lr the rows' own rounding would hide the difference."""
    built = builder.build(CONFIG, None, True)
    config = {**CONFIG, **CONFIG["rehearse"]}
    host = packed_documents.generate(
        TRAFFIC, [(config["vocab_size"], 1)], built.global_batch,
        built.num_numerical, built.numerical_scale, 2147483659)
    assert [reference_lr(built.optimizer, i) for i in range(3)] == [
        0.0, pytest.approx(1.5e-6), pytest.approx(3e-6)]
    params = built.model.init(jax.random.PRNGKey(2147483659))
    batches = [run.stage(built, b) for b in host]
    first = [i % len(batches) for i in range(check.check_steps(built.optimizer))]
    chk = check.Check(built, params, [host[i] for i in first], batches[0][1],
                      config["matmul_precision"])
    init_fn, step_fn = built.make_step()
    state, losses = init_fn(params), []
    for i in first:
        params, state, loss = step_fn(params, state, *batches[i])
        losses.append(float(loss))
    summary = chk.finish(params, losses)
    assert summary["compared"]["row_err_over_tolerance"][0] < 1
    assert summary["touched_rows_moved"] > 0.5 * summary["touched_rows"]
    with pytest.raises(check.CheckFailed, match=r"\(c\)") as failed:
        check.compare(built, chk.ref, chk.probes, chk.sys_embs, losses,
                      chk.rows_before, chk.rows_before)
    assert failed.value.summary["compared"]["row_err_over_tolerance"][0] > 3


# ------------------------- where the chip's default precision rounds a product
SMALL = {"head_dim": 8, "layer_types": [m for m, _ in FIVE],
         "num_dense_layers": 1, "rope_theta": 10000, "num_experts_per_tok": 4,
         "routed_scaling_factor": 1, "first_expert_held": 4, "norm_eps": 1e-5}


def small_case(seed):
    model = lfm2.Lfm2(
        vocab_rows=64, hidden=32, num_heads=4, num_kv_heads=2, head_dim=8,
        layers=FIVE, rope={"rope_type": "default", "rope_theta": 10000},
        conv_taps=3, dense_width=48, num_experts_total=16,
        held_experts=range(4, 8), top_k=4, expert_width=16, bias_range=0.01)
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
        for layer, (mixer, mlp) in zip(params["layers"], model.layers):
            x = model._mix(layer, mixer, x, positions, document)
            stream.append(x)
            x = model._feed(layer, mlp, x)
            stream.append(x)
        return stream

    def plain(params, x):
        eps, stream = SMALL["norm_eps"], []
        for layer, (mixer, mlp) in zip(params["layers"], FIVE):
            if mixer == "conv":
                x = x + reference.short_convolution(layer, x, positions, eps)
            else:
                x = x + reference.attention(layer, x, positions, SMALL)
            stream.append(x)
            if mlp == "dense":
                x = x + reference.dense_ffn(layer, x, positions.shape[0], eps)
            else:
                x = x + reference.experts_held(
                    layer["experts"],
                    reference.rms_norm(x, layer["ffn_norm"], eps),
                    positions.shape[0], SMALL)
            stream.append(x)
        return stream

    return (with_rounded_products(program)(params, x),
            with_rounded_products(plain)(params, x), plain(params, x))


def distance(a, b):
    return float(jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(b ** 2)))


_short_conv = lfm2.short_conv


def conv_of_rounded_taps(v, taps, positions):
    """The mutation: the same convolution with the taps' weights rounded
    where a kernel that kept them in bfloat16 would hold them."""
    return _short_conv(v, taps.astype(jnp.bfloat16).astype(v.dtype),
                       positions)


@pytest.mark.parametrize("mutated", [False, True])
def test_the_models_products_round_what_the_plain_references_round(
        mutated, monkeypatch):
    """Rule (c) of the check holds a program only while its products round
    the values the reference's do (PERF.md section 6, PR 36). Under bfloat16
    operands every half of the program's blocks stays within 1e-6 of the
    reference's (read: 4e-9 to 1e-8, f32 summation order) where the
    reference itself is 4e-5 to 1.4e-4 from its f32 values; a convolution
    that rounds its taps is its own draw of that rounding (0.42 of it)."""
    if mutated:
        monkeypatch.setattr(lfm2, "short_conv", conv_of_rounded_taps)
    model, params, positions, ids = small_case(1)
    (table,) = model.embedding.get_weights(params["embedding"])
    got, want, exact = blocks_under_rounded_products(
        model, {k: v for k, v in params.items() if k != "embedding"},
        jnp.asarray(positions), jnp.asarray(table)[ids])
    first_half = distance(got[0], want[0])        # the first convolution
    rounding = distance(want[0], exact[0])
    assert rounding > 3e-5
    if mutated:
        assert first_half > 0.3 * rounding
        return
    # the convolutions, the dense MLP, attention, the sparse layers
    assert len(got) == 10
    assert max(distance(g, w) for g, w in zip(got, want)) < 1e-6
