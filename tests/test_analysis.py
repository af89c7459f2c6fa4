"""Static program auditor (ISSUE 10): IR layer, pass framework, legacy
parity, mutation fixtures.

The parity tests are the port's acceptance gate: the three regex-era
auditors (`hlo_op_counts`, `hlo_collective_bytes`,
`hlo_collective_overlap`) were run over the recorded program fixtures
BEFORE deletion and their outputs frozen in
tests/fixtures/hlo/expected_legacy.json — the IR-based measurements
must reproduce them EXACTLY. The fixtures cannot be regenerated against
the old code (it is gone); the JSON is the behavior contract.
"""

import gzip
import json
import os

import jax
import jax.numpy as jnp
import pytest

from distributed_embeddings_tpu.analysis import ir, passes, programs

FIXTURE_DIR = os.path.join(os.path.dirname(__file__), "fixtures", "hlo")

FIXTURES = ("meshed_step_f32", "meshed_step_bf16_weighted",
            "meshed_step_int32_ids", "meshed_step_donated",
            "unfolded_sorts", "lookahead_fused", "lookahead_prefetch",
            "serve_forward")

_WIDE_OPS = ("sort", "scatter", "gather", "all_to_all", "all_gather",
             "reduce_scatter", "while", "dot_general", "custom_call")


def _fixture(name: str) -> str:
    with gzip.open(os.path.join(FIXTURE_DIR, name + ".mlir.gz"),
                   "rt") as f:
        return f.read()


@pytest.fixture(scope="module")
def legacy_expected():
    with open(os.path.join(FIXTURE_DIR, "expected_legacy.json")) as f:
        return json.load(f)


# ------------------------------------------------------------- parity
@pytest.mark.parametrize("name", FIXTURES)
def test_legacy_parser_parity(name, legacy_expected):
    """The ported measurements reproduce the regex era bit-for-bit on
    every recorded program — op counts (default + wide op set, incl.
    the attribute-mention semantics: #stablehlo.gather<> references
    count), collective bytes by dtype, and the full overlap
    classification."""
    want = legacy_expected[name]
    mod = ir.parse_module(_fixture(name))
    assert ir.op_counts(mod) == want["op_counts"]
    assert ir.op_counts(mod, ops=_WIDE_OPS) == want["op_counts_wide"]
    assert ir.collective_bytes(mod) == want["collective_bytes"]
    assert ir.collective_overlap(mod) == want["collective_overlap"]


def test_profiling_delegates_to_ir():
    """utils.profiling keeps the public API (the audit arms
    and old tests all import it) but the implementation is the ONE IR
    parse — same outputs on a real lowered text, and Module inputs are
    accepted directly."""
    from distributed_embeddings_tpu.utils import profiling
    text = _fixture("meshed_step_f32")
    mod = ir.parse_module(text)
    assert profiling.hlo_op_counts(text) == ir.op_counts(mod)
    assert profiling.hlo_collective_bytes(text) == \
        ir.collective_bytes(mod)
    assert profiling.hlo_collective_overlap(text) == \
        ir.collective_overlap(mod)
    assert profiling.hlo_op_counts(mod) == ir.op_counts(mod)


# ------------------------------------------------------------ IR layer
def test_empty_and_garbage_modules():
    """The parser never throws: empty text, whitespace, and non-MLIR
    garbage all produce a Module that measures as zero."""
    for text in ("", "   \n\n", "not mlir at all\n{ unbalanced"):
        mod = ir.parse_module(text)
        assert mod.entry is None or mod.entry.instructions == []
        assert ir.op_counts(mod)["sort"] == 0
        assert ir.collective_bytes(mod)["total"] == {}
        assert ir.collective_overlap(mod)["collectives_total"] == 0


def test_type_parsing():
    t = ir.Type.parse("tensor<8x4xbf16>")
    assert (t.dtype, t.shape, t.nbytes) == ("bf16", (8, 4), 64)
    assert ir.Type.parse("tensor<f32>").shape == ()
    assert ir.Type.parse("tensor<f32>").nbytes == 4
    dyn = ir.Type.parse("tensor<?x4xf32>")
    assert dyn.shape == (None, 4) and dyn.nbytes == 0
    assert ir.Type.parse("!stablehlo.token").dtype is None
    # float8 element types are registered at 1 byte (ISSUE 15 — the
    # storage-dtype pass measures quantized buffers); genuinely unknown
    # element types still charge the historical 4 bytes/element
    assert ir.Type.parse("tensor<2xf8E4M3FN>").nbytes == 2
    assert ir.Type.parse("tensor<2xmystery99>").nbytes == 8


def test_instruction_structure_and_regions():
    """Multi-result instructions, region folding, attrs, arg attrs."""
    text = """
module @m {
  func.func public @main(%arg0: tensor<8xi32> {jax.buffer_donor = true}, %arg1: tensor<8xf32>) -> tensor<8xf32> {
    %0:2 = "stablehlo.sort"(%arg0, %arg1) <{dimension = 0 : i64, is_stable = true}> ({
    ^bb0(%a: tensor<i32>, %b: tensor<i32>, %c: tensor<f32>, %d: tensor<f32>):
      %cmp = stablehlo.compare LT, %a, %b : (tensor<i32>, tensor<i32>) -> tensor<i1>
      stablehlo.return %cmp : tensor<i1>
    }) : (tensor<8xi32>, tensor<8xf32>) -> (tensor<8xi32>, tensor<8xf32>)
    %1 = stablehlo.add %0#1, %arg1 : tensor<8xf32>
    return %1 : tensor<8xf32>
  }
}
"""
    mod = ir.parse_module(text)
    fn = mod.entry
    assert fn.name == "main" and fn.visibility == "public"
    assert [a.donated for a in fn.args] == [True, False]
    assert fn.donated_args[0].name == "%arg0"
    sort, add = fn.instructions
    assert sort.kind == "sort" and sort.num_results == 2
    assert ("stablehlo", "compare") in sort.region_ops
    assert "is_stable" in sort.attrs
    # the region-closing line's signature is the instruction's signature
    assert [t.dtype for t in sort.operand_types] == ["i32", "f32"]
    assert [t.dtype for t in sort.result_types] == ["i32", "f32"]
    assert add.operands == ["%0", "%arg1"]       # %0#1 -> base name
    assert fn.returns == ["%1"]
    assert fn.producers() == {"%0": 0, "%1": 1}


def test_nested_call_graph_two_deep():
    """Interprocedural summaries through a two-deep private call chain
    (jax's shmap_body-within-helper structure): the inner collective
    surfaces at the entry call site, and classification follows the
    call-site's edges."""
    text = """
module @m {
  func.func public @main(%arg0: tensor<8xf32>, %arg1: tensor<8x8xf32>) -> tensor<8xf32> {
    %0 = call @shmap_body(%arg0) : (tensor<8xf32>) -> tensor<8xf32>
    %1 = stablehlo.dot_general %arg1, %arg1, contracting_dims = [1] x [0] : (tensor<8x8xf32>, tensor<8x8xf32>) -> tensor<8x8xf32>
    return %0 : tensor<8xf32>
  }
  func.func private @shmap_body(%arg0: tensor<8xf32>) -> tensor<8xf32> {
    %0 = call @shmap_body_0(%arg0) : (tensor<8xf32>) -> tensor<8xf32>
    return %0 : tensor<8xf32>
  }
  func.func private @shmap_body_0(%arg0: tensor<8xf32>) -> tensor<8xf32> {
    %0 = "stablehlo.all_to_all"(%arg0) <{concat_dimension = 0 : i64, split_count = 8 : i64, split_dimension = 0 : i64}> : (tensor<8xf32>) -> tensor<8xf32>
    return %0 : tensor<8xf32>
  }
}
"""
    mod = ir.parse_module(text)
    assert mod.call_graph()["main"] == ["shmap_body"]
    assert mod.call_graph()["shmap_body"] == ["shmap_body_0"]
    ov = ir.collective_overlap(mod)
    # the collective two calls down is visible at main's call site, and
    # nothing orders it against the dot -> candidate
    assert ov["collectives_total"] == 1
    assert ov["overlap_candidates"] == 1
    # bytes surface from the inner function's own instruction
    assert ir.collective_bytes(mod)["total"] == {"f32": 32}


def test_recursive_call_graph_tolerated():
    """A (hand-made) call cycle must not hang or crash the summaries."""
    text = """
module @m {
  func.func public @main(%arg0: tensor<8xf32>) -> tensor<8xf32> {
    %0 = call @a(%arg0) : (tensor<8xf32>) -> tensor<8xf32>
    return %0 : tensor<8xf32>
  }
  func.func private @a(%arg0: tensor<8xf32>) -> tensor<8xf32> {
    %0 = call @a(%arg0) : (tensor<8xf32>) -> tensor<8xf32>
    return %0 : tensor<8xf32>
  }
}
"""
    assert ir.collective_overlap(text)["collectives_total"] == 0


def test_dp_only_plan_zero_collectives():
    """A data-parallel-only plan (every table under the dp threshold)
    lowers with ZERO exchange collectives — the auditor must report the
    empty program faithfully, not crash on it."""
    from distributed_embeddings_tpu.layers.dist_model_parallel import (
        DistributedEmbedding)
    from distributed_embeddings_tpu.layers.embedding import Embedding
    from distributed_embeddings_tpu.parallel.mesh import create_mesh

    mesh = create_mesh(jax.devices()[:8])
    d = DistributedEmbedding(
        [Embedding(64, 8, combiner="sum") for _ in range(2)],
        mesh=mesh, data_parallel_threshold=10**9)
    assert not d.plan.tp_buckets        # everything went dp
    p = d.init(jax.random.PRNGKey(0))
    ins = [jnp.zeros((16, 2), jnp.int32)] * 2
    text = jax.jit(lambda p, i: d.apply(p, list(i))).lower(
        p, ins).as_text()
    mod = ir.parse_module(text)
    assert ir.collective_bytes(mod)["total"] == {}
    ov = ir.collective_overlap(mod)
    assert ov["collectives_total"] == 0 == ov["overlap_candidates"]


def test_prefetch_arm_standalone_ir():
    """The lookahead prefetch arm lowered standalone (the recorded
    fixture): private shmap bodies in the call graph, all collectives
    overlap candidates (no dense compute in the arm), forward-only
    byte profile."""
    mod = ir.parse_module(_fixture("lookahead_prefetch"))
    assert any(f.startswith("shmap_body") for f in mod.functions)
    assert mod.entry.name == "main"
    ov = ir.collective_overlap(mod)
    assert ov["collectives_total"] > 0
    assert ov["overlap_candidates"] == ov["collectives_total"]
    assert ov["compute_sites"] == 0
    b = ir.collective_bytes(mod)
    assert b["float_bytes"] > 0 and b["int_bytes"] > 0


# ------------------------------------------------------ pass framework
def test_all_passes_registered():
    names = [n for n, _ in passes.list_passes()]
    assert names == ["op-counts", "collective-bytes",
                     "collective-overlap", "wire-seam", "donation",
                     "dtype-promotion", "storage-dtype",
                     "dead-dup-collective"]


@pytest.mark.parametrize("case", programs.mutation_cases(),
                         ids=lambda c: c.name)
def test_mutation_fixture_flags(case):
    """Every pass flags its seeded violation with EXACTLY the expected
    finding ids — an auditor that cannot fail is not a gate. (The same
    check gates CI through `hlo_audit.py --assert`.)"""
    mod = ir.parse_module(case.text)
    got = tuple(f.fid for f in passes.run_passes(
        mod, case.ctx, passes=[case.pass_name]))
    assert got == case.expect_fids, (case.name, got)
    # and the finding ids are stable across re-parses (allowlist key)
    again = tuple(f.fid for f in passes.run_passes(
        ir.parse_module(case.text), case.ctx,
        passes=[case.pass_name]))
    assert again == got


def test_finding_shape_and_severity():
    f = passes.run_passes(
        ir.parse_module(programs._MUT_F64),
        passes.PlanContext(program="t"),
        passes=["dtype-promotion"])[0]
    d = f.to_dict()
    assert d["severity"] == "error" and d["pass_name"] == \
        "dtype-promotion"
    assert set(d) == {"pass_name", "fid", "severity", "message",
                      "func", "line", "op"}
    assert d["func"] == "main" and d["line"] > 0


def test_context_free_run_is_silent():
    """A default PlanContext disables every bounded check: green
    programs produce zero findings, and nothing crashes on the fixture
    set."""
    ctx = passes.PlanContext(program="t", id_wire_dtypes=("auto",))
    for name in ("meshed_step_f32", "serve_forward"):
        mod = ir.parse_module(_fixture(name))
        assert passes.run_passes(mod, ctx) == []


def test_donation_pass_both_directions():
    donated = ir.parse_module(_fixture("meshed_step_donated"))
    clean = ir.parse_module(_fixture("meshed_step_f32"))
    on = passes.PlanContext(program="t", donate_expected=True)
    off = passes.PlanContext(program="t", donate_expected=False)
    assert [f.fid for f in passes.run_passes(
        donated, off, passes=["donation"])] == \
        ["donation/unexpected-donation"]
    assert passes.run_passes(donated, on, passes=["donation"]) == []
    missing = passes.run_passes(clean, on, passes=["donation"])
    assert [f.fid for f in missing] == ["donation/missing-donation"]
    assert missing[0].severity == "warning"
    assert passes.run_passes(clean, off, passes=["donation"]) == []


def test_wire_seam_attributes_real_programs():
    """The recorded real programs attribute cleanly under their actual
    plan wires, and FAIL attribution under a deliberately wrong
    context — the pass reads the plan, not the program."""
    mod = ir.parse_module(_fixture("meshed_step_f32"))
    ok = passes.PlanContext(program="t", wire_dtypes=("f32",),
                            id_wire_dtypes=("int16",))
    assert passes.run_passes(mod, ok, passes=["wire-seam"]) == []
    wrong = passes.PlanContext(program="t", wire_dtypes=("bf16",),
                               id_wire_dtypes=("int32",))
    fids = {f.fid for f in passes.run_passes(mod, wrong,
                                             passes=["wire-seam"])}
    assert "wire-seam/escape.all_to_all.f32" in fids
    assert "wire-seam/escape.all_to_all.i16" in fids


def test_expected_bytes_cross_check_on_fixture():
    """The reconciled byte model == the HLO measurement on the recorded
    bf16 weighted program (the tricky config: narrowed int16 ids at
    2 B/element on the wire, activations twice — fwd + gradient
    transpose — and the weight block forward-ONLY, because weights are
    inputs, not params)."""
    from distributed_embeddings_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(jax.devices()[:8])
    model = programs.build_model(512, 8, "sum", tables=2, mesh=mesh,
                                 exchange_wire="bf16")
    want = programs.expected_collective_bytes(
        model.embedding, [2, 2], batch=16, weighted=True, train=True)
    got = ir.collective_bytes(
        _fixture("meshed_step_bf16_weighted"))["total"]
    assert got == want


def test_bf16_sr_wire_format_not_false_flagged():
    """'bf16-sr' is a supported wire FORMAT that puts bf16 payloads on
    the wire: a plan declaring it must neither trip the
    zero-compressed-bytes contract (collective-bytes) nor fail open on
    the f32-leak check (dtype-promotion) — formats map to payload
    dtypes through the ops/wire.py seam hooks, never by string
    comparison."""
    bf16_prog = ir.parse_module(_fixture("meshed_step_bf16_weighted"))
    sr_ctx = passes.PlanContext(program="t", wire_dtypes=("bf16-sr",),
                                id_wire_dtypes=("int16",))
    # a bf16-payload program under a bf16-sr plan: clean
    assert passes.run_passes(bf16_prog, sr_ctx,
                             passes=["collective-bytes"]) == []
    assert passes.run_passes(bf16_prog, sr_ctx,
                             passes=["wire-seam"]) == []
    # a uniformly-bf16-sr plan is COMPRESSED: an f32 payload on a seam
    # collective must still flag (the check may not fail open)
    leak = ir.parse_module(programs._MUT_FREE_COLLECTIVE)
    fids = [f.fid for f in passes.run_passes(
        leak, sr_ctx, passes=["dtype-promotion"])]
    assert fids == ["dtype-promotion/f32-wire-leak.all_to_all"]


def test_duplicate_detection_ignores_channel_handles():
    """jax stamps every collective with a UNIQUE channel_handle; two
    otherwise byte-identical exchanges must still compare equal (with
    raw-attr keys the duplicate check could never fire on a real
    lowering — the 'auditor that cannot fail' failure mode)."""
    text = """
module @m {
  func.func public @main(%arg0: tensor<8xf32>) -> tensor<64xf32> {
    %0 = "stablehlo.all_gather"(%arg0) <{all_gather_dim = 0 : i64, channel_handle = #stablehlo.channel_handle<handle = 1, type = 1>}> : (tensor<8xf32>) -> tensor<64xf32>
    %1 = "stablehlo.all_gather"(%arg0) <{all_gather_dim = 0 : i64, channel_handle = #stablehlo.channel_handle<handle = 2, type = 1>}> : (tensor<8xf32>) -> tensor<64xf32>
    %2 = stablehlo.add %0, %1 : tensor<64xf32>
    return %2 : tensor<64xf32>
  }
}
"""
    fids = [f.fid for f in passes.run_passes(
        ir.parse_module(text), passes.PlanContext(program="t"),
        passes=["dead-dup-collective"])]
    assert fids == ["dead-dup-collective/duplicate.all_gather"]
    # ...while genuinely different collectives (operands differ) on the
    # real recorded program stay clean
    real = ir.parse_module(_fixture("meshed_step_f32"))
    assert passes.run_passes(real, passes.PlanContext(program="t"),
                             passes=["dead-dup-collective"]) == []


def test_program_matrix_modules_preparsed():
    """Each matrix program is parsed exactly once: the Program carries
    its Module, and the driver runs passes on it directly."""
    progs = programs.program_matrix()
    for prog in progs:
        assert isinstance(prog.module, ir.Module)
        assert prog.module.source == prog.text


# ------------------------------------------------------ driver / matrix
def test_audit_driver_matrix_green_and_mutations_flag():
    """The acceptance gate run the way CI runs it: the full program
    matrix passes every applicable pass with an EMPTY allowlist, and
    every mutation fixture is flagged. (~15 s: one lowering per
    program, shared across passes.)"""
    import importlib.util as ilu
    spec = ilu.spec_from_file_location(
        "det_hlo_audit_t", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "hlo_audit.py"))
    ha = ilu.module_from_spec(spec)
    spec.loader.exec_module(ha)
    assert ha.load_baseline() == set()      # the healthy state: empty
    records, failures = ha.run_matrix(set())
    assert failures == [], failures
    assert {r["program"] for r in records} == {
        "monolithic_f32", "monolithic_bf16", "vocab_slack_step",
        "monolithic_tiled", "pallas_strategy_step",
        "lookahead_prefetch", "lookahead_fused", "serve_forward",
        "quantized_store_serve", "quantized_hbm_serve"}
    mrecords, mfailures = ha.run_mutations()
    assert mfailures == [], mfailures
    assert len(mrecords) == len(programs.mutation_cases())
