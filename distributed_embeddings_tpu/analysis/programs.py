"""The audited program matrix + mutation fixtures (ISSUE 10).

This is the jax-heavy half of the analysis package: it builds the
standard programs the static auditor proves invariants over —
monolithic train step, lookahead fused + prefetch, serve forward,
vocab-slack plan — lowered ONCE each over an 8-virtual-device mesh
(``program_matrix``: one lowering per program, shared by every pass —
the <=60s CI budget lives or dies on that cache), plus the legacy
per-arm audit entry points, plus ``mutation_cases()``: for every pass,
a program that deliberately violates its invariant and MUST produce
exactly the expected finding.
An auditor that cannot fail is not a gate.

``expected_collective_bytes`` is the reconciled byte model (ISSUE 10
satellite): ONE formula turning ``exchange_padding_report``'s per-group
accounting into the exact per-device payload bytes the lowered
program's collectives must measure — id wire at the NARROWED dtype
(int16 buckets charge 2 bytes, matching the i16 operand the HLO
carries), activations twice in a train step (forward + gradient
transpose), weights once (weights are INPUTS, not params: no gradient
flows back through the weight exchange, so a train step moves the
weight block forward-only). tests/test_wire.py asserts HLO == model on
every wire config; the collective-bytes pass asserts it on every audit.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional

from . import ir
from .passes import PlanContext

__all__ = [
    "ensure_world", "build_model", "head_params",
    "expected_collective_bytes", "Program", "program_matrix",
    "mutation_cases", "MutationCase",
    "audit_tapped_step", "audit_exchange_bytes",
    "audit_lookahead_overlap", "wire_byte_arms",
    "WIRE_BYTE_MIN_REDUCTION",
]


def ensure_world(n: int = 8) -> int:
    """Request >= n virtual CPU devices (meshed lowerings emit real
    collectives only at world > 1). Must run before the backend
    initializes; returns the device count actually available."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    import jax
    try:
        jax.config.update("jax_num_cpu_devices", n)
    except Exception:  # noqa: BLE001 - backend already up / older jax
        pass
    return len(jax.devices())


def build_model(vocab: int, width: int, combiner: str, hot_rows: int = 0,
                tables: int = 1, mesh=None, exchange_wire=None,
                dense_head: bool = False, vocab_slack: int = 0,
                weighted: bool = False, gpu_embedding_size=None,
                storage_dtype=None):
    """Minimal tapped model (the shape make_sparse_train_step expects)
    around a DistributedEmbedding — THE one copy of this harness, shared
    by the audit program matrix and the legacy sort/byte/overlap arms.

    ``dense_head=True`` puts a real matmul between the embedding outputs
    and the loss (params gain a ``head`` kernel, built by
    ``head_params``). The overlap passes classify collectives by
    dependency on dot ops — without a dot in the module the metric is
    vacuous — and a dense head is what the pipeline overlaps against in
    the first place. ``weighted=True`` feeds (ids, uniform-weights)
    tuples so the weight-exchange wire lowers too."""
    import jax.numpy as jnp
    from ..layers.dist_model_parallel import DistributedEmbedding
    from ..layers.embedding import Embedding

    class _Tapped:
        def __init__(self, emb):
            self.embedding = emb

        def loss_fn(self, p, numerical, cats, labels, taps=None,
                    return_residuals=False):
            ins = ([(c, jnp.ones(c.shape, jnp.float32)) for c in cats]
                   if weighted else list(cats))
            out = self.embedding(p["embedding"], ins, taps=taps,
                                 return_residuals=return_residuals)
            outs, res = out if return_residuals else (out, None)
            x = jnp.concatenate([o.reshape(o.shape[0], -1) for o in outs],
                                axis=1)
            if dense_head:
                pred = (x.astype(jnp.float32) @ p["head"])[:, 0]
            else:
                pred = jnp.sum(x, axis=1)
            loss = jnp.mean((pred - labels.reshape(-1)) ** 2)
            return (loss, res) if return_residuals else loss

    emb = DistributedEmbedding(
        [Embedding(vocab, width, combiner=combiner) for _ in range(tables)],
        mesh=mesh, hot_rows=hot_rows, exchange_wire=exchange_wire,
        vocab_slack=vocab_slack or None,
        gpu_embedding_size=gpu_embedding_size,
        storage_dtype=storage_dtype)
    return _Tapped(emb)


def head_params(tables: int, width: int, hotness: int, combiner: str):
    """The replicated dense-head kernel matching ``build_model``'s
    ``dense_head=True`` loss (one output column)."""
    import jax.numpy as jnp
    per = width * (1 if combiner else hotness)
    return jnp.zeros((tables * per, 1), jnp.float32)


# -------------------------------------------------- reconciled byte model
def expected_collective_bytes(emb, hotness, batch: int,
                              weighted: bool = False,
                              train: bool = True) -> Dict[str, int]:
    """Exact per-device collective payload bytes by StableHLO dtype for
    one lowered PADDED-path program over this layer's plan — the
    model-side twin of ``ir.collective_bytes`` (see module docstring for
    the fwd/bwd accounting). Returns {} at world 1 (no collectives).
    Only the padded exchange is modeled: the ragged emulation moves
    world x the payload through its all_gathers by construction, which
    is a path choice, not a wire property."""
    world = emb.world_size
    if world <= 1:
        return {}
    rep = emb.exchange_padding_report(hotness=hotness)
    out: Dict[str, int] = {}

    def add(dtype: str, n: int):
        if n:
            out[dtype] = out.get(dtype, 0) + n

    from ..ops import wire as wire_ops
    for g in rep["groups"]:
        # formats -> payload element types through the seam hooks, so
        # 'bf16-sr' models as the bf16 it actually puts on the wire
        id_dtype = wire_ops.seam_id_dtypes(g["id_wire_dtype"])[0]
        f_dtype = wire_ops.seam_float_dtypes(g["wire_dtype"])[0]
        id_b = wire_ops.id_wire_itemsize(g["id_wire_dtype"])
        wire_b = wire_ops.wire_itemsize(g["wire_dtype"])
        # report fields are per GLOBAL sample over the fleet; one
        # device's operand is the fleet volume x batch / world
        add(id_dtype, batch * g["exchanged_ids"] * id_b // world)
        acts = batch * g["act_bytes"] // world
        add(f_dtype, acts * (2 if train else 1))
        if weighted:
            add(f_dtype, batch * g["weight_bytes_if_weighted"] // world)
    return out


# --------------------------------------------------------- program matrix
@dataclasses.dataclass
class Program:
    """One lowered program + the plan context its invariants are checked
    against. Lowered AND parsed exactly once — ``module`` is the shared
    parse every pass (and the matrix's own cross-program bounds) runs
    on; ``text`` is kept for fixtures/debugging."""

    name: str
    text: str
    ctx: PlanContext
    module: "ir.Module" = None
    # driver hint: passes to SKIP for this program (e.g. overlap on a
    # program with no dense compute, where the metric is vacuous)
    skip_passes: tuple = ()

    def __post_init__(self):
        if self.module is None:
            self.module = ir.parse_module(self.text)


def _lower_step(model, optimizer: str, donate: bool, batch: int,
                hotness: int, tables: int, strategy: str = "auto"):
    import jax
    import jax.numpy as jnp
    from ..training import make_sparse_train_step
    emb = model.embedding
    init_fn, step_fn = make_sparse_train_step(
        model, optimizer, lr=0.01, donate=donate, strategy=strategy)
    params = {"embedding": emb.init(jax.random.PRNGKey(0))}
    if hasattr(model, "_head_width"):
        params["head"] = model._head_width
    state = init_fn(params)
    num = jnp.zeros((batch, 1), jnp.float32)
    cats = [jnp.zeros((batch, hotness), jnp.int32) for _ in range(tables)]
    lab = jnp.zeros((batch,), jnp.float32)
    kw = {"donate_argnums": (0, 1)} if donate else {}
    return jax.jit(step_fn, **kw).lower(
        params, state, num, cats, lab), params, cats


def _plan_wires(emb):
    """(float wire formats, id wire formats, folded sort bound, groups)
    of a layer's plan — the PlanContext ingredients."""
    key = tuple((2, False) for _ in range(len(
        emb.strategy.input_groups[1])))
    groups, _ = emb._exchange_groups_for_key(key)
    wires = tuple(sorted({b.wire_dtype for b in emb.plan.tp_buckets}))
    id_wires = tuple(sorted({b.id_wire_dtype
                             for b in emb.plan.tp_buckets}))
    return wires or ("f32",), id_wires or ("int32",), len(groups)


def program_matrix(vocab: int = 4096, width: int = 16, tables: int = 4,
                   batch: int = 32, hotness: int = 2,
                   optimizer: str = "adagrad",
                   world: int = 8) -> List[Program]:
    """Lower the standard program matrix over a `world`-device mesh —
    ONE lowering per program, every pass runs on the shared parse.

    Programs: monolithic train step (f32 + bf16 wire), lookahead
    fused + prefetch, serve forward, vocab-slack plan (int32 id wire —
    the big-vocab end of the id-narrowing gate)."""
    import jax
    import jax.numpy as jnp
    from ..parallel.mesh import create_mesh
    from ..schedule import LookaheadEngine
    from ..training import default_donate

    devs = jax.devices()
    if len(devs) < world:
        raise RuntimeError(
            f"program matrix needs {world} devices, have {len(devs)} — "
            "call ensure_world() before the backend initializes")
    mesh = create_mesh(devs[:world])
    donate = default_donate()
    programs: List[Program] = []

    def steps(name, wire, vocab_, slack=0, weighted=False,
              strategy="auto", sort_bound=None):
        model = build_model(vocab_, width, "sum", tables=tables,
                            mesh=mesh, exchange_wire=wire,
                            dense_head=True, vocab_slack=slack,
                            weighted=weighted)
        emb = model.embedding
        model._head_width = head_params(tables, width, hotness, "sum")
        lowered, _, _ = _lower_step(model, optimizer, donate, batch,
                                    hotness, tables, strategy=strategy)
        wires, id_wires, n_groups = _plan_wires(emb)
        ctx = PlanContext(
            program=name, wire_dtypes=wires, id_wire_dtypes=id_wires,
            sort_bound=(n_groups if sort_bound is None else sort_bound),
            donate_expected=donate,
            overlap={"max_candidates": 0},
            expected_bytes=expected_collective_bytes(
                emb, [hotness] * tables, batch, weighted=weighted,
                train=True))
        programs.append(Program(name=name, text=lowered.as_text(),
                                ctx=ctx))
        return model, emb

    # 1+2: the monolithic step at both float wires (the bf16 arm is the
    # compiled form of the 2.0x wire claim; exact bytes, not a ratio)
    model, emb = steps("monolithic_f32", "f32", vocab)
    steps("monolithic_bf16", "bf16", vocab, weighted=True)

    # 3: vocab-slack plan (ISSUE 7's growth rows; big vocab -> int32 id
    # wire, so both narrowing verdicts are represented in the matrix)
    steps("vocab_slack_step", "f32", 40_000, slack=256)

    # 3b+3c (ISSUE 12): the monolithic model under the tiled and the
    # fused pallas scatter strategies. The tiled arm is the baseline the
    # fused arm is measured against: the pallas arm's sort bound is the
    # tiled lowering's MEASURED sort count (zero extra sorts — its dedup
    # must consume the folded forward sort, never add one), and both
    # arms carry the exact padding-report byte model (zero collective
    # deltas — the update strategy must not change what moves on the
    # wire; the collective-bytes pass asserts compiled == model exactly
    # on each). tools/hlo_audit.py's mutation fixture
    # 'pallas-arm-extra-sort' proves this arm can fail.
    steps("monolithic_tiled", "f32", vocab, strategy="tiled")
    tiled_sorts = ir.op_counts(programs[-1].module, ops=("sort",))["sort"]
    steps("pallas_strategy_step", "f32", vocab, strategy="pallas",
          sort_bound=tiled_sorts)

    # 4+5: lookahead fused + prefetch from the SAME model as the
    # monolithic arm — the fused step's prefetch collectives must all be
    # overlap candidates, the monolithic arm pinned zero above, and the
    # fused lowering must add no sorts over the monolithic bound
    params = {"embedding": emb.init(jax.random.PRNGKey(0)),
              "head": head_params(tables, width, hotness, "sum")}
    engine = LookaheadEngine(model, optimizer, lr=0.01, donate=False)
    state = engine.init(params)
    num = jnp.zeros((batch, 1), jnp.float32)
    cats = [jnp.zeros((batch, hotness), jnp.int32) for _ in range(tables)]
    lab = jnp.zeros((batch,), jnp.float32)
    b0 = (num, cats, lab)
    pre_text = engine.lower_prefetch(params, cats).as_text()
    fused_text = engine.lower_fused(params, state, b0, b0).as_text()
    wires, id_wires, n_groups = _plan_wires(emb)
    # cross-program bounds come from the already-parsed modules — no
    # program is parsed twice anywhere in an audit run
    pre_module = ir.parse_module(pre_text)
    pre_total = ir.collective_overlap(pre_module)["collectives_total"]
    mono_sorts = ir.op_counts(programs[0].module, ops=("sort",))["sort"]
    programs.append(Program(
        name="lookahead_prefetch", text=pre_text, module=pre_module,
        ctx=PlanContext(
            program="lookahead_prefetch", wire_dtypes=wires,
            id_wire_dtypes=id_wires, sort_bound=n_groups,
            overlap={"all_candidates": True},
            expected_bytes=expected_collective_bytes(
                emb, [hotness] * tables, batch, train=False))))
    programs.append(Program(
        name="lookahead_fused", text=fused_text,
        ctx=PlanContext(
            program="lookahead_fused", wire_dtypes=wires,
            id_wire_dtypes=id_wires,
            # PR 2 gate carried over: the staged restructure must add
            # ZERO sorts vs the monolithic lowering of the same model
            sort_bound=mono_sorts,
            overlap={"min_candidates": pre_total})))

    # 6: serve forward — the apply-only program InferenceEngine jits;
    # forward-only bytes, no dense compute (overlap is vacuous -> skip)
    import jax as _jax
    sp = {"embedding": emb.init(_jax.random.PRNGKey(0))}
    serve_text = _jax.jit(
        lambda p, i: emb.apply(p["embedding"], list(i))).lower(
        sp, cats).as_text()
    programs.append(Program(
        name="serve_forward", text=serve_text,
        ctx=PlanContext(
            program="serve_forward", wire_dtypes=wires,
            id_wire_dtypes=id_wires, sort_bound=n_groups,
            donate_expected=False,
            expected_bytes=expected_collective_bytes(
                emb, [hotness] * tables, batch, train=False)),
        skip_passes=("collective-overlap",)))

    # 7: quantized-storage serve forward (ISSUE 15) — an offloaded
    # bucket at storage_dtype='int8': the lowered program must carry i8
    # row buffers, every one attributable to the declared dtype (the
    # storage-dtype pass is vacuous on programs 1-6, which declare
    # ('f32',) and must lower ZERO quantized buffers). Byte model
    # skipped: the offloaded activation return is a GSPMD resharding,
    # not a seam collective, so expected_collective_bytes does not
    # model this program; the wire-seam pass still polices every
    # collective payload it does emit.
    # per-RANK element budget (offload flags on post-slicing per-rank
    # configs): under it every table offloads into one quantized bucket
    q_model = build_model(vocab, width, "sum", tables=tables, mesh=mesh,
                          gpu_embedding_size=(vocab * width) // world,
                          storage_dtype="int8")
    q_emb = q_model.embedding
    assert q_emb.quantized_buckets, \
        "quantized_store_serve: budget failed to offload any bucket"
    q_sp = {"embedding": q_emb.init(_jax.random.PRNGKey(0))}
    q_text = _jax.jit(
        lambda p, i: q_emb.apply(p["embedding"], list(i))).lower(
        q_sp, cats).as_text()
    q_wires, q_id_wires, q_groups = _plan_wires(q_emb)
    programs.append(Program(
        name="quantized_store_serve", text=q_text,
        ctx=PlanContext(
            program="quantized_store_serve", wire_dtypes=q_wires,
            id_wire_dtypes=q_id_wires, sort_bound=q_groups,
            donate_expected=False,
            storage_dtypes=tuple(sorted(
                {b.storage_dtype for b in q_emb.plan.tp_buckets}))),
        skip_passes=("collective-overlap",)))

    # 8: HBM-resident quantized serve forward (ISSUE 17) — the same
    # int8 declaration with NO offload budget, so every bucket stays
    # device-resident and quantizes under the lifted planner gate. The
    # i8 payload tables and their f32 per-row scales enter the jitted
    # program as params and decode at gather time, so the lowering must
    # carry i8 buffers attributable to the declaration — and the
    # declared-but-f32 direction of the storage-dtype pass proves the
    # declaration actually reached the compiled program (a plan that
    # says 'int8' over an all-f32 lowering now flags instead of
    # silently shipping 4x the HBM).
    h_model = build_model(vocab, width, "sum", tables=tables, mesh=mesh,
                          storage_dtype="int8")
    h_emb = h_model.embedding
    assert h_emb.quantized_buckets and not any(
        b.offload for b in h_emb.plan.tp_buckets), \
        "quantized_hbm_serve: expected device-resident quantized buckets"
    h_sp = {"embedding": h_emb.init(_jax.random.PRNGKey(0))}
    h_text = _jax.jit(
        lambda p, i: h_emb.apply(p["embedding"], list(i))).lower(
        h_sp, cats).as_text()
    h_wires, h_id_wires, h_groups = _plan_wires(h_emb)
    programs.append(Program(
        name="quantized_hbm_serve", text=h_text,
        ctx=PlanContext(
            program="quantized_hbm_serve", wire_dtypes=h_wires,
            id_wire_dtypes=h_id_wires, sort_bound=h_groups,
            donate_expected=False,
            storage_dtypes=tuple(sorted(
                {b.storage_dtype for b in h_emb.plan.tp_buckets}))),
        skip_passes=("collective-overlap",)))
    return programs


# ------------------------------------------------------ mutation fixtures
@dataclasses.dataclass
class MutationCase:
    """A program that deliberately violates ONE invariant. The driver
    runs only ``pass_name`` over it and must get exactly
    ``expect_fids`` — proof the gate can fail."""

    name: str
    pass_name: str
    text: str
    ctx: PlanContext
    expect_fids: tuple


_MUT_TWO_SORTS = """
module @m {
  func.func public @main(%arg0: tensor<8xf32>) -> tensor<8xf32> {
    %0 = "stablehlo.sort"(%arg0) <{dimension = 0 : i64, is_stable = true}> ({
    ^bb0(%a0: tensor<f32>, %b0: tensor<f32>):
      %c0 = stablehlo.compare LT, %a0, %b0 : (tensor<f32>, tensor<f32>) -> tensor<i1>
      stablehlo.return %c0 : tensor<i1>
    }) : (tensor<8xf32>) -> tensor<8xf32>
    %1 = "stablehlo.sort"(%0) <{dimension = 0 : i64, is_stable = true}> ({
    ^bb0(%a1: tensor<f32>, %b1: tensor<f32>):
      %c1 = stablehlo.compare LT, %a1, %b1 : (tensor<f32>, tensor<f32>) -> tensor<i1>
      stablehlo.return %c1 : tensor<i1>
    }) : (tensor<8xf32>) -> tensor<8xf32>
    return %1 : tensor<8xf32>
  }
}
"""

_MUT_BF16_ON_F32_WIRE = """
module @m {
  func.func public @main(%arg0: tensor<8x4xf32>) -> tensor<8x4xf32> {
    %0 = stablehlo.convert %arg0 : (tensor<8x4xf32>) -> tensor<8x4xbf16>
    %1 = "stablehlo.all_to_all"(%0) <{concat_dimension = 0 : i64, split_count = 8 : i64, split_dimension = 0 : i64}> : (tensor<8x4xbf16>) -> tensor<8x4xbf16>
    %2 = stablehlo.convert %1 : (tensor<8x4xbf16>) -> tensor<8x4xf32>
    return %2 : tensor<8x4xf32>
  }
}
"""

_MUT_FREE_COLLECTIVE = """
module @m {
  func.func public @main(%arg0: tensor<8xf32>, %arg1: tensor<8x8xf32>) -> tensor<8xf32> {
    %0 = "stablehlo.all_to_all"(%arg0) <{concat_dimension = 0 : i64, split_count = 8 : i64, split_dimension = 0 : i64}> : (tensor<8xf32>) -> tensor<8xf32>
    %1 = stablehlo.dot_general %arg1, %arg1, contracting_dims = [1] x [0] : (tensor<8x8xf32>, tensor<8x8xf32>) -> tensor<8x8xf32>
    return %0 : tensor<8xf32>
  }
}
"""

_MUT_SERIAL_COLLECTIVE = """
module @m {
  func.func public @main(%arg0: tensor<8x8xf32>) -> tensor<8x8xf32> {
    %0 = "stablehlo.all_to_all"(%arg0) <{concat_dimension = 0 : i64, split_count = 8 : i64, split_dimension = 0 : i64}> : (tensor<8x8xf32>) -> tensor<8x8xf32>
    %1 = stablehlo.dot_general %0, %arg0, contracting_dims = [1] x [0] : (tensor<8x8xf32>, tensor<8x8xf32>) -> tensor<8x8xf32>
    return %1 : tensor<8x8xf32>
  }
}
"""

_MUT_F64 = """
module @m {
  func.func public @main(%arg0: tensor<8xf32>) -> tensor<8xf32> {
    %0 = stablehlo.convert %arg0 : (tensor<8xf32>) -> tensor<8xf64>
    %1 = stablehlo.add %0, %0 : tensor<8xf64>
    %2 = stablehlo.convert %1 : (tensor<8xf64>) -> tensor<8xf32>
    return %2 : tensor<8xf32>
  }
}
"""

_MUT_DUP_COLLECTIVE = """
module @m {
  func.func public @main(%arg0: tensor<8xf32>) -> tensor<64xf32> {
    %0 = "stablehlo.all_gather"(%arg0) <{all_gather_dim = 0 : i64}> : (tensor<8xf32>) -> tensor<64xf32>
    %1 = "stablehlo.all_gather"(%arg0) <{all_gather_dim = 0 : i64}> : (tensor<8xf32>) -> tensor<64xf32>
    %2 = stablehlo.add %0, %1 : tensor<64xf32>
    return %2 : tensor<64xf32>
  }
}
"""

_MUT_QUANT_BUFFER = """
module @m {
  func.func public @main(%arg0: tensor<8x4xf32>) -> tensor<8x4xf32> {
    %0 = stablehlo.convert %arg0 : (tensor<8x4xf32>) -> tensor<8x4xi8>
    %1 = stablehlo.convert %0 : (tensor<8x4xi8>) -> tensor<8x4xf32>
    return %1 : tensor<8x4xf32>
  }
}
"""

_MUT_F32_UNDER_INT8_DECL = """
module @m {
  func.func public @main(%arg0: tensor<8x4xf32>, %arg1: tensor<2xi32>) -> tensor<2x4xf32> {
    %0 = "stablehlo.gather"(%arg0, %arg1) {dimension_numbers = #stablehlo.gather<offset_dims = [1], collapsed_slice_dims = [0], start_index_map = [0], index_vector_dim = 1>, slice_sizes = array<i64: 1, 4>} : (tensor<8x4xf32>, tensor<2xi32>) -> tensor<2x4xf32>
    return %0 : tensor<2x4xf32>
  }
}
"""

_MUT_DEAD_COLLECTIVE = """
module @m {
  func.func public @main(%arg0: tensor<8xf32>) -> tensor<8xf32> {
    %0 = "stablehlo.all_gather"(%arg0) <{all_gather_dim = 0 : i64}> : (tensor<8xf32>) -> tensor<64xf32>
    %1 = stablehlo.add %arg0, %arg0 : tensor<8xf32>
    return %1 : tensor<8xf32>
  }
}
"""


def _lower_naked_collective() -> str:
    """A REAL jax lowering of a naked `lax.all_to_all` around the seam —
    an f32 payload in a program whose plan declares a bf16 wire, the
    exact seam escape the wire-seam pass exists to catch (and the
    Python-side twin of tools/lint_invariants.py's 'naked-collective'
    AST rule, which would flag this source before it ever lowered)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from .. import compat
    from ..parallel.mesh import create_mesh

    mesh = create_mesh(jax.devices()[:8])
    f = compat.shard_map(
        # the seeded violation itself — lint: allow(naked-collective)
        lambda x: lax.all_to_all(x, "mp", split_axis=0, concat_axis=0),
        mesh=mesh, in_specs=P("mp"), out_specs=P("mp"))
    return jax.jit(f).lower(jnp.zeros((64, 4), jnp.float32)).as_text()


def _lower_donated() -> str:
    """A REAL donated lowering (jax.buffer_donor arg attrs) for the
    donation-policy mutation."""
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda x: x + 1.0, donate_argnums=0).lower(
        jnp.zeros((16, 16), jnp.float32)).as_text()


def mutation_cases() -> List[MutationCase]:
    """One seeded violation per pass (two for overlap/dead-dup: both
    failure directions). Each must produce EXACTLY its expected finding
    ids when its pass runs — asserted in CI by `hlo_audit.py --assert`
    (mutations run by default; `--skip-mutations` opts out) and by
    tests/test_analysis.py."""
    bf16_ctx = PlanContext(program="mutation", wire_dtypes=("bf16",),
                           id_wire_dtypes=("int16",))
    return [
        MutationCase(
            name="two-sorts-over-bound", pass_name="op-counts",
            text=_MUT_TWO_SORTS,
            ctx=PlanContext(program="mutation", sort_bound=1),
            expect_fids=("op-counts/sort-over-bound",)),
        MutationCase(
            name="pallas-arm-extra-sort", pass_name="op-counts",
            text=_MUT_TWO_SORTS,
            # the ISSUE 12 pallas-strategy arm's gate, seeded violated: a
            # fused step that re-sorts past the tiled baseline's measured
            # count must flag (blind-gate discipline — the arm can fail)
            ctx=PlanContext(program="pallas_strategy_step", sort_bound=1),
            expect_fids=("op-counts/sort-over-bound",)),
        MutationCase(
            name="bf16-bytes-on-f32-wire", pass_name="collective-bytes",
            text=_MUT_BF16_ON_F32_WIRE,
            ctx=PlanContext(program="mutation", wire_dtypes=("f32",)),
            expect_fids=("collective-bytes/bf16-in-f32-program",)),
        MutationCase(
            name="free-collective-in-sequential-contract",
            pass_name="collective-overlap", text=_MUT_FREE_COLLECTIVE,
            ctx=PlanContext(program="mutation",
                            overlap={"max_candidates": 0}),
            expect_fids=("collective-overlap/unexpected-candidates",)),
        MutationCase(
            name="serialized-prefetch", pass_name="collective-overlap",
            text=_MUT_SERIAL_COLLECTIVE,
            ctx=PlanContext(program="mutation",
                            overlap={"min_candidates": 1}),
            expect_fids=("collective-overlap/candidates-under-bound",)),
        MutationCase(
            name="naked-lax-all-to-all", pass_name="wire-seam",
            text=_lower_naked_collective(), ctx=bf16_ctx,
            expect_fids=("wire-seam/escape.all_to_all.f32",)),
        MutationCase(
            name="donated-under-donation-off-policy",
            pass_name="donation", text=_lower_donated(),
            ctx=PlanContext(program="mutation", donate_expected=False),
            expect_fids=("donation/unexpected-donation",)),
        MutationCase(
            name="forced-f64-upcast", pass_name="dtype-promotion",
            text=_MUT_F64, ctx=PlanContext(program="mutation"),
            expect_fids=("dtype-promotion/f64",)),
        MutationCase(
            name="f32-leak-on-bf16-wire", pass_name="dtype-promotion",
            text=_MUT_FREE_COLLECTIVE, ctx=bf16_ctx,
            expect_fids=("dtype-promotion/f32-wire-leak.all_to_all",)),
        MutationCase(
            # ISSUE 15: an int8 buffer in a program whose plan declares
            # only f32 storage — a row table quantized outside the
            # ops/wire.py storage seam (the blind-gate fixture of the
            # storage-dtype pass)
            name="quantized-buffer-under-f32-storage",
            pass_name="storage-dtype", text=_MUT_QUANT_BUFFER,
            ctx=PlanContext(program="mutation",
                            storage_dtypes=("f32",)),
            expect_fids=("storage-dtype/undeclared.i8",)),
        MutationCase(
            # ISSUE 17 (inverse direction): the plan declares int8
            # storage but every buffer in the lowered program is f32 —
            # an HBM-resident table whose quantization was silently
            # dropped (the declared ~4x HBM saving never compiled in)
            name="declared-int8-but-f32-buffers",
            pass_name="storage-dtype", text=_MUT_F32_UNDER_INT8_DECL,
            ctx=PlanContext(program="mutation",
                            storage_dtypes=("f32", "int8")),
            expect_fids=("storage-dtype/declared-but-f32.i8",)),
        MutationCase(
            name="self-duplicated-collective",
            pass_name="dead-dup-collective", text=_MUT_DUP_COLLECTIVE,
            ctx=PlanContext(program="mutation"),
            expect_fids=("dead-dup-collective/duplicate.all_gather",)),
        MutationCase(
            name="dead-fanout-collective",
            pass_name="dead-dup-collective", text=_MUT_DEAD_COLLECTIVE,
            ctx=PlanContext(program="mutation"),
            expect_fids=("dead-dup-collective/dead.all_gather",)),
    ]


# ------------------------------------------------------------ legacy arms
# Per-arm audit entry points predating the pass matrix, kept because
# their bounds are shape-parameterized in ways the fixed matrix is not
# (30M-row vocabs, tiled lookup, hot shards). They run on the same IR
# measurements as the passes.

def audit_tapped_step(vocab: int = 30_000_000, width: int = 8,
                      batch: int = 8, hotness: int = 4,
                      optimizer: str = "adagrad", strategy: str = "sort",
                      lookup_path: Optional[str] = None, fold: bool = True,
                      combiner: str = "sum", hot_rows: int = 0) -> dict:
    """Lower one tapped sparse train step (abstract avals — no giant
    table is materialized) and count its StableHLO ops. Returns the
    counts plus the exchange-group count the sort bound is measured
    against (one canonical sort per group, +1 per group for the tiled
    forward's inverse-permute; hot_rows adds ZERO — the PR 4 gate)."""
    import jax
    import jax.numpy as jnp
    from ..training import make_sparse_train_step

    prev = os.environ.get("DET_LOOKUP_PATH")
    try:
        if lookup_path is None:
            os.environ.pop("DET_LOOKUP_PATH", None)
        else:
            os.environ["DET_LOOKUP_PATH"] = lookup_path
        model = build_model(vocab, width, combiner, hot_rows=hot_rows)
        emb = model.embedding
        init_fn, step_fn = make_sparse_train_step(
            model, optimizer, lr=0.01, strategy=strategy, fold_sort=fold)
        params = jax.eval_shape(
            lambda: {"embedding": emb.init(jax.random.PRNGKey(0))})
        state = jax.eval_shape(init_fn, params)
        num = jax.ShapeDtypeStruct((batch, 1), jnp.float32)
        cats = [jax.ShapeDtypeStruct((batch, hotness), jnp.int32)]
        lab = jax.ShapeDtypeStruct((batch,), jnp.float32)
        lowered = jax.jit(step_fn).lower(params, state, num, cats, lab)
        counts = ir.op_counts(lowered.as_text())
        key = ((hotness, False),)
        groups, _ = emb._exchange_groups_for_key(key)
        n_groups = len(groups)
    finally:
        if prev is None:
            os.environ.pop("DET_LOOKUP_PATH", None)
        else:
            os.environ["DET_LOOKUP_PATH"] = prev
    # the bound the fold ships under: one canonical sort per exchange
    # group, plus the tiled/fused forward gather's inverse-permute sort
    # (the one residual sort — scatter-free inversion needs a second
    # sort op; the fused gather->combine consumes the same artifact)
    bound = n_groups * (2 if lookup_path in ("tiled", "fused") else 1)
    return {
        "optimizer": optimizer, "strategy": strategy,
        "lookup_path": lookup_path or "default", "fold": fold,
        "hot_rows": hot_rows,
        "n_exchange_groups": n_groups, "sort_bound": bound,
        **{f"hlo_{k}": v for k, v in counts.items()},
    }


def audit_exchange_bytes(wire: str = "f32", vocab: int = 4096,
                         width: int = 32, tables: int = 8, batch: int = 16,
                         hotness: int = 2, optimizer: str = "adagrad",
                         world: int = 8) -> dict:
    """Lower the tapped sparse train step over a `world`-device mesh at
    one exchange-wire format and return its collective-byte accounting
    (plus the per-group padding-report byte fields, so the static claim
    and the compiled HLO can be cross-checked in one record)."""
    import jax
    import jax.numpy as jnp
    from ..parallel.mesh import create_mesh
    from ..training import make_sparse_train_step

    devs = jax.devices()
    if len(devs) < world:
        return {"wire": wire, "skipped":
                f"need {world} devices for the meshed lowering, "
                f"have {len(devs)}"}
    mesh = create_mesh(devs[:world])
    model = build_model(vocab, width, "sum", tables=tables, mesh=mesh,
                        exchange_wire=wire)
    emb = model.embedding
    init_fn, step_fn = make_sparse_train_step(model, optimizer, lr=0.01)
    params = {"embedding": emb.init(jax.random.PRNGKey(0))}
    state = init_fn(params)
    num = jnp.zeros((batch, 1), jnp.float32)
    cats = [jnp.zeros((batch, hotness), jnp.int32) for _ in range(tables)]
    lab = jnp.zeros((batch,), jnp.float32)
    text = jax.jit(step_fn).lower(params, state, num, cats,
                                  lab).as_text()
    mod = ir.parse_module(text)
    bytes_ = ir.collective_bytes(mod)
    rep = emb.exchange_padding_report(hotness=[hotness] * tables)
    return {
        "wire": wire, "optimizer": optimizer, "world": world,
        "vocab": vocab, "width": width, "tables": tables, "batch": batch,
        "hotness": hotness,
        "collective_float_bytes": bytes_["float_bytes"],
        "collective_int_bytes": bytes_["int_bytes"],
        "collective_bytes_by_dtype": bytes_["total"],
        "expected_bytes_by_dtype": expected_collective_bytes(
            emb, [hotness] * tables, batch),
        "report_act_bytes": rep["act_bytes"],
        "report_act_bytes_f32": rep["act_bytes_f32"],
        "report_act_wire_reduction": round(rep["act_wire_reduction"], 3),
        "report_exchanged_bytes": rep["exchanged_bytes"],
        "report_true_bytes": rep["true_bytes"],
        "id_narrowed_groups": rep["id_narrowed_groups"],
        **{f"hlo_{k}": v for k, v in ir.op_counts(mod).items()},
    }


def audit_lookahead_overlap(vocab: int = 4096, width: int = 32,
                            tables: int = 4, batch: int = 64,
                            hotness: int = 2, optimizer: str = "adagrad",
                            world: int = 8, stale_ok: bool = False) -> dict:
    """Lower the lookahead engine's FUSED staged step over a
    `world`-device mesh and prove, on the dependency graph of the
    StableHLO, that batch N+1's exchange collectives carry NO data
    dependency on batch N's dense compute (ISSUE 9) — the static twin of
    an ICI/MXU overlap measurement, checkable without hardware.
    Three lowerings, one record: the fused step, the standalone prefetch
    (defines the collective count the candidates must cover), and the
    monolithic baseline (must audit to ZERO candidates and pins the
    zero-extra-sorts bound)."""
    import jax
    import jax.numpy as jnp
    from ..parallel.mesh import create_mesh
    from ..schedule import LookaheadEngine
    from ..training import make_sparse_train_step

    devs = jax.devices()
    if len(devs) < world:
        return {"arm": "lookahead_overlap", "skipped":
                f"need {world} devices for the meshed lowering, "
                f"have {len(devs)}"}
    mesh = create_mesh(devs[:world])
    model = build_model(vocab, width, "sum", tables=tables, mesh=mesh,
                        dense_head=True)
    emb = model.embedding
    params = {"embedding": emb.init(jax.random.PRNGKey(0)),
              "head": head_params(tables, width, hotness, "sum")}
    engine = LookaheadEngine(model, optimizer, lr=0.01,
                             stale_ok=stale_ok, donate=False)
    state = engine.init(params)
    num = jnp.zeros((batch, 1), jnp.float32)
    cats = [jnp.zeros((batch, hotness), jnp.int32) for _ in range(tables)]
    lab = jnp.zeros((batch,), jnp.float32)
    b0 = (num, cats, lab)

    fused_txt = engine.lower_fused(params, state, b0, b0).as_text()
    pre_txt = engine.lower_prefetch(params, cats).as_text()
    init2, step2 = make_sparse_train_step(model, optimizer, lr=0.01,
                                          donate=False)
    base_txt = jax.jit(step2).lower(params, init2(params), num, cats,
                                    lab).as_text()

    fused_ov = ir.collective_overlap(fused_txt)
    pre_ov = ir.collective_overlap(pre_txt)
    base_ov = ir.collective_overlap(base_txt)
    fused_sorts = ir.op_counts(fused_txt)["sort"]
    base_sorts = ir.op_counts(base_txt)["sort"]
    rec = {
        "arm": "lookahead_overlap", "optimizer": optimizer,
        "world": world, "vocab": vocab, "width": width, "tables": tables,
        "batch": batch, "hotness": hotness, "stale_ok": stale_ok,
        "fused_collectives": fused_ov["collectives_total"],
        "fused_overlap_candidates": fused_ov["overlap_candidates"],
        "fused_candidates_by_op": fused_ov["candidates_by_op"],
        "prefetch_collectives": pre_ov["collectives_total"],
        "baseline_collectives": base_ov["collectives_total"],
        "baseline_overlap_candidates": base_ov["overlap_candidates"],
        "fused_sorts": fused_sorts, "baseline_sorts": base_sorts,
        "extra_sorts": fused_sorts - base_sorts,
    }
    rec["over_bound"] = bool(
        rec["prefetch_collectives"] == 0
        or rec["fused_overlap_candidates"] < rec["prefetch_collectives"]
        or rec["baseline_overlap_candidates"] != 0
        or rec["extra_sorts"] > 0)
    return rec


# minimum float-collective-byte shrink the bf16 wire must show vs f32 on
# the same lowered step — the wire moves half the bits, so the compiled
# ratio is 2.0 minus whatever small float traffic is not behind the seam
WIRE_BYTE_MIN_REDUCTION = 1.9


def wire_byte_arms(**kw) -> list:
    """The f32-vs-bf16 collective-byte A/B records (+ derived reduction
    stamped on the bf16 record)."""
    base = audit_exchange_bytes(wire="f32", **kw)
    comp = audit_exchange_bytes(wire="bf16", **kw)
    if "skipped" not in comp and "skipped" not in base:
        fb = base["collective_float_bytes"]
        cb = comp["collective_float_bytes"]
        comp["float_bytes_reduction_vs_f32"] = (
            round(fb / cb, 3) if cb else None)
        comp["min_reduction_required"] = WIRE_BYTE_MIN_REDUCTION
        base["bf16_collective_bytes"] = (
            base["collective_bytes_by_dtype"].get("bf16", 0))
    return [base, comp]
