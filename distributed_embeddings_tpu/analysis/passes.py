"""Static-analysis pass framework over the StableHLO IR (ISSUE 10).

A pass is ``(Module, PlanContext) -> list[Finding]``: it proves one
repo invariant about a LOWERED program and reports violations as typed
findings with a severity, an op location, and a STABLE finding id
(content-derived — op kind + dtype + rule, never a line number — so the
checked-in allowlist ``tools/audit_baseline.json`` diffs like a
snapshot across recompiles).

The catalog (docs/analysis.md has the long form, and every pass carries
a mutation fixture that CI proves it flags — an auditor that cannot
fail is not a gate):

  op-counts            sort mentions <= the plan's folded bound (PR 2)
  collective-bytes     measured payload bytes == the padding-report
                       model, per dtype; zero bf16 bytes in an f32-wire
                       program (PR 5)
  collective-overlap   dependency classification of every collective vs
                       the dense compute matches the program's schedule
                       contract (PR 8)
  wire-seam            every exchange collective's payload dtype is
                       attributable to a plan group's declared
                       wire_dtype/id_wire_dtype — an unattributed
                       collective is a seam escape (new)
  donation             input-output aliasing vs the default_donate()
                       policy — the PR 5 XLA:CPU donation+cache
                       miscompile class, statically detectable (new)
  dtype-promotion      no f64 anywhere; no f32 payload feeding a seam
                       collective in an all-bf16-wire program (new)
  dead-dup-collective  no two collectives with identical operand SSA
                       sources + attrs; no collective whose result has
                       empty transitive fan-out (new)
  storage-dtype        every quantized (i8/f8*) buffer in the program is
                       attributable to a plan bucket's declared
                       storage_dtype — an i8 tensor in an all-f32-storage
                       program is a storage-seam escape (ISSUE 15)
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import ir

__all__ = ["Finding", "PlanContext", "register_pass", "run_passes",
           "list_passes", "PASS_REGISTRY"]


@dataclasses.dataclass
class Finding:
    """One invariant violation in one lowered program."""

    pass_name: str
    fid: str                      # stable id, allowlist key
    severity: str                 # 'error' | 'warning'
    message: str
    func: str = ""                # function the finding anchors to
    line: int = 0                 # source line (display only, NOT in fid)
    op: str = ""                  # op mnemonic involved

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class PlanContext:
    """What the PLAN says the lowered program must look like — the
    second input of every pass. Built by the driver
    (``analysis.programs`` / ``tools/hlo_audit.py``) from the model's
    plan plus the program's build parameters; ``None`` fields disable
    the corresponding check (a context-free pass run is a no-op, not a
    failure)."""

    program: str = "program"
    platform: str = "cpu"
    # declared float/id wire formats over the plan's exchange groups
    # (ops/wire.py seam hooks translate them to StableHLO dtypes)
    wire_dtypes: Tuple[str, ...] = ("f32",)
    id_wire_dtypes: Tuple[str, ...] = ("int32",)
    # the ragged CPU emulation moves its i32 split metadata through
    # all_gathers (ops/wire.py ragged_exchange); padded-path programs
    # leave this False so a stray i32 collective cannot hide behind it
    ragged_emulation: bool = False
    # declared at-rest storage dtypes over the plan's tp buckets
    # (ISSUE 15); ('f32',) declares NO quantized buffer anywhere — the
    # storage-dtype pass flags every i8/f8 tensor it then finds
    storage_dtypes: Tuple[str, ...] = ("f32",)
    sort_bound: Optional[int] = None
    donate_expected: Optional[bool] = None
    # {"max_candidates": n} | {"min_candidates": n} |
    # {"all_candidates": True} — see collective-overlap
    overlap: Optional[dict] = None
    # exact per-device payload bytes by dtype, usually from
    # analysis.programs.expected_collective_bytes
    expected_bytes: Optional[Dict[str, int]] = None


PASS_REGISTRY: "Dict[str, Tuple[Callable, str]]" = {}


def register_pass(name: str, doc: str):
    def deco(fn):
        PASS_REGISTRY[name] = (fn, doc)
        return fn
    return deco


def list_passes() -> List[Tuple[str, str]]:
    return [(name, doc) for name, (_, doc) in PASS_REGISTRY.items()]


def run_passes(module, ctx: PlanContext,
               passes: Optional[Sequence[str]] = None) -> List[Finding]:
    """Run the selected passes (default: all, registration order) over
    one parsed module. Accepts raw StableHLO text or a lowered object;
    parse once, reuse the Module across passes."""
    mod = module if isinstance(module, ir.Module) else \
        ir.parse_module(module)
    names = list(passes) if passes is not None else list(PASS_REGISTRY)
    findings: List[Finding] = []
    for name in names:
        fn, _ = PASS_REGISTRY[name]
        findings.extend(fn(mod, ctx))
    return findings


# ------------------------------------------------------------ the passes
@register_pass("op-counts",
               "sort mentions <= the plan's folded sort bound (PR 2)")
def op_counts_pass(mod: ir.Module, ctx: PlanContext) -> List[Finding]:
    if ctx.sort_bound is None:
        return []
    n = ir.op_counts(mod, ops=("sort",))["sort"]
    if n <= ctx.sort_bound:
        return []
    return [Finding(
        pass_name="op-counts", fid="op-counts/sort-over-bound",
        severity="error", op="sort",
        message=(f"{n} stablehlo.sort mentions, plan bound is "
                 f"{ctx.sort_bound} (one canonical sort per exchange "
                 f"group; docs/perf_model.md 'Sort folding')"))]


@register_pass("collective-bytes",
               "collective payload bytes == the padding-report model, "
               "per dtype; zero bf16 bytes on the f32 wire (PR 5)")
def collective_bytes_pass(mod: ir.Module,
                          ctx: PlanContext) -> List[Finding]:
    measured = ir.collective_bytes(mod)
    out: List[Finding] = []
    # declared wire FORMATS ('f32'/'bf16'/'bf16-sr') map to payload
    # element types through the seam hooks — 'bf16-sr' puts bf16 on the
    # wire, so the zero-compressed-bytes contract only binds plans whose
    # formats all decode to f32
    floats, _ = _allowed_payload_dtypes(ctx)
    if "bf16" not in floats and measured["total"].get("bf16", 0):
        out.append(Finding(
            pass_name="collective-bytes",
            fid="collective-bytes/bf16-in-f32-program",
            severity="error", op="*",
            message=(f"{measured['total']['bf16']} bf16 collective "
                     "payload bytes in a program whose plan declares no "
                     "bf16 wire — the f32 default's bit-exactness "
                     "contract moves ZERO compressed bytes")))
    if ctx.expected_bytes is not None:
        for dtype in sorted(set(ctx.expected_bytes)
                            | set(measured["total"])):
            want = ctx.expected_bytes.get(dtype, 0)
            got = measured["total"].get(dtype, 0)
            if want != got:
                out.append(Finding(
                    pass_name="collective-bytes",
                    fid=f"collective-bytes/model-mismatch.{dtype}",
                    severity="error", op="*",
                    message=(f"{dtype} collective payload: HLO measures "
                             f"{got} bytes/device, the "
                             f"exchange_padding_report model says {want} "
                             "— the static claim and the compiled "
                             "program disagree")))
    return out


@register_pass("collective-overlap",
               "dependency classification of collectives vs dense "
               "compute matches the schedule contract (PR 8)")
def collective_overlap_pass(mod: ir.Module,
                            ctx: PlanContext) -> List[Finding]:
    if not ctx.overlap:
        return []
    ov = ir.collective_overlap(mod)
    out: List[Finding] = []
    cand, total = ov["overlap_candidates"], ov["collectives_total"]
    if "max_candidates" in ctx.overlap and \
            cand > ctx.overlap["max_candidates"]:
        out.append(Finding(
            pass_name="collective-overlap",
            fid="collective-overlap/unexpected-candidates",
            severity="error", op="*",
            message=(f"{cand} overlap candidates, contract allows "
                     f"<= {ctx.overlap['max_candidates']} (a sequential "
                     "program's collectives must all sit on the dense "
                     "critical path — the metric's honesty anchor)")))
    want_min = ctx.overlap.get("min_candidates")
    if ctx.overlap.get("all_candidates"):
        want_min = total
    if want_min is not None and cand < want_min:
        out.append(Finding(
            pass_name="collective-overlap",
            fid="collective-overlap/candidates-under-bound",
            severity="error", op="*",
            message=(f"{cand}/{total} collectives are overlap "
                     f"candidates, schedule contract requires >= "
                     f"{want_min} (a prefetch collective acquired a "
                     "data dependency on the dense compute)")))
    return out


def _allowed_payload_dtypes(ctx: PlanContext) -> Tuple[set, set]:
    """(float dtypes, int dtypes) the plan's seam may legally put on an
    exchange collective — read from ops/wire.py so the pass and the
    seam cannot drift."""
    from ..ops import wire as wire_ops
    floats = {d for w in ctx.wire_dtypes
              for d in wire_ops.seam_float_dtypes(w)}
    ints = {d for w in ctx.id_wire_dtypes
            for d in wire_ops.seam_id_dtypes(w)}
    if ctx.ragged_emulation:
        ints |= set(wire_ops.RAGGED_METADATA_DTYPES)
    return floats, ints


@register_pass("wire-seam",
               "every exchange collective's payload dtype is "
               "attributable to a declared wire format (new)")
def wire_seam_pass(mod: ir.Module, ctx: PlanContext) -> List[Finding]:
    floats, ints = _allowed_payload_dtypes(ctx)
    escapes: Dict[Tuple[str, str], List[ir.Instruction]] = {}
    for _, inst in mod.walk():
        for kind, t in inst.collective_payloads():
            if not t.dtype:
                continue
            ok = t.dtype in floats if t.dtype.startswith(("f", "bf")) \
                else t.dtype in ints
            if not ok:
                escapes.setdefault((kind, t.dtype), []).append(inst)
    out = []
    for (kind, dtype), insts in sorted(escapes.items()):
        out.append(Finding(
            pass_name="wire-seam", fid=f"wire-seam/escape.{kind}.{dtype}",
            severity="error", op=kind, line=insts[0].line,
            message=(f"{len(insts)} {kind} collective(s) move a {dtype} "
                     f"payload no plan group declares (float wires "
                     f"{sorted(floats)}, id wires {sorted(ints)}) — an "
                     "exchange outside the ops/wire.py seam")))
    return out


@register_pass("donation",
               "input-output aliasing table vs the default_donate() "
               "policy — the PR 5 CPU miscompile class (new)")
def donation_pass(mod: ir.Module, ctx: PlanContext) -> List[Finding]:
    if ctx.donate_expected is None:
        return []
    entry = mod.entry
    if entry is None:
        return []
    donated = entry.donated_args
    if donated and not ctx.donate_expected:
        names = [a.name for a in donated]
        return [Finding(
            pass_name="donation", fid="donation/unexpected-donation",
            severity="error", func=entry.name, line=entry.line,
            message=(f"{len(donated)} donated/aliased arg(s) "
                     f"{names[:4]} but the donation policy for this "
                     f"build is OFF (platform={ctx.platform}): the "
                     "caller's arrays would be consumed by the step"))]
    if ctx.donate_expected and not donated:
        return [Finding(
            pass_name="donation", fid="donation/missing-donation",
            severity="warning", func=entry.name, line=entry.line,
            message=("donation policy is ON but no argument carries "
                     "jax.buffer_donor/tf.aliasing_output — the step "
                     "updates out of place (double table HBM)"))]
    return []


@register_pass("dtype-promotion",
               "no f64 anywhere; no f32 payload on a seam collective "
               "in an all-bf16-wire program (new)")
def dtype_promotion_pass(mod: ir.Module,
                         ctx: PlanContext) -> List[Finding]:
    out: List[Finding] = []
    f64_sites: List[Tuple[str, ir.Instruction]] = []
    for fn, inst in mod.walk():
        if any(t.dtype == "f64"
               for t in inst.operand_types + inst.result_types):
            f64_sites.append((fn.name, inst))
    if f64_sites:
        fn0, i0 = f64_sites[0]
        out.append(Finding(
            pass_name="dtype-promotion", fid="dtype-promotion/f64",
            severity="error", func=fn0, line=i0.line, op=i0.kind,
            message=(f"{len(f64_sites)} op(s) carry f64 values (first: "
                     f"{i0.kind} in @{fn0}) — nothing in this system "
                     "computes at f64; an accidental weak_type/np "
                     "promotion doubles HBM and halves MXU throughput")))
    # the f32-feeding-a-collective check only has meaning when the plan
    # is UNIFORMLY compressed: a mixed plan legitimately moves f32 on
    # its f32-wire groups (the wire-seam pass attributes those).
    # Formats map through the seam hooks so 'bf16-sr' counts as
    # compressed — comparing format STRINGS would fail open on it
    floats, _ = _allowed_payload_dtypes(ctx)
    if floats == {"bf16"}:
        hits: Dict[str, int] = {}
        for _, inst in mod.walk():
            for kind, t in inst.collective_payloads():
                if t.dtype == "f32":
                    hits[kind] = hits.get(kind, 0) + 1
        for kind in sorted(hits):
            out.append(Finding(
                pass_name="dtype-promotion",
                fid=f"dtype-promotion/f32-wire-leak.{kind}",
                severity="error", op=kind,
                message=(f"{hits[kind]} {kind} collective(s) move f32 "
                         "payloads in an all-bf16-wire program — an "
                         "encode was dropped, the declared uncompressed "
                         "set (hot/loss psum, combiner-None) never "
                         "lowers to this op")))
    return out


@register_pass("storage-dtype",
               "every quantized (i8/f8*) buffer is attributable to a "
               "declared bucket storage_dtype (ISSUE 15)")
def storage_dtype_pass(mod: ir.Module, ctx: PlanContext) -> List[Finding]:
    """The wire-seam discipline applied to MEMORY: quantized element
    types may appear in a lowered program only where a plan bucket
    declared that storage dtype (`ops/wire.seam_storage_dtypes` maps
    the declarations, so pass and codec cannot drift). In the default
    all-f32-storage program the allowed set is EMPTY — any i8/f8
    tensor is a buffer quantized outside the seam (or a stray integer
    narrowing masquerading as one), exactly the class of silent
    numerics change this gate exists to catch."""
    from ..ops import wire as wire_ops
    allowed = {d for s in ctx.storage_dtypes
               for d in wire_ops.seam_storage_dtypes(s)}
    hits: Dict[Tuple[str, str], List[ir.Instruction]] = {}
    present: set = set()
    for _, inst in mod.walk():
        for t in inst.operand_types + inst.result_types:
            if t.dtype in ir.QUANTIZED_STORAGE_DTYPES:
                present.add(t.dtype)
                if t.dtype not in allowed:
                    hits.setdefault((t.dtype, inst.kind), []).append(inst)
    out: List[Finding] = []
    # ---- inverse direction (ISSUE 17, HBM-resident buffers): a plan
    # that DECLARES a quantized storage dtype whose seam element type
    # appears in NO buffer of the lowered program. The declaration was
    # dropped on the floor — the table lowered as plain f32, so the
    # promised ~4x HBM saving silently never materialized (the mirror
    # failure of the undeclared case; both directions are blind-gated
    # by tools/hlo_audit.py mutation fixtures).
    for dtype in sorted(allowed - present):
        out.append(Finding(
            pass_name="storage-dtype",
            fid=f"storage-dtype/declared-but-f32.{dtype}",
            severity="error", op="module",
            message=(f"plan declares a storage dtype lowering to {dtype} "
                     f"(declared: {sorted(ctx.storage_dtypes)}) but no op "
                     f"in the program carries {dtype} values — the bucket "
                     "lowered as f32, the declared quantized residency "
                     "never reached the compiled program")))
    by_dtype: Dict[str, int] = {}
    first: Dict[str, ir.Instruction] = {}
    for (dtype, _), insts in sorted(hits.items()):
        by_dtype[dtype] = by_dtype.get(dtype, 0) + len(insts)
        first.setdefault(dtype, insts[0])
    for dtype in sorted(by_dtype):
        i0 = first[dtype]
        out.append(Finding(
            pass_name="storage-dtype",
            fid=f"storage-dtype/undeclared.{dtype}",
            severity="error", op=i0.kind, line=i0.line,
            message=(f"{by_dtype[dtype]} op(s) carry {dtype} values but "
                     f"no plan bucket declares a storage dtype lowering "
                     f"to {dtype} (declared: "
                     f"{sorted(ctx.storage_dtypes)}) — a buffer "
                     "quantized outside the ops/wire.py storage seam")))
    return out


@register_pass("dead-dup-collective",
               "no duplicate collectives over identical operands; no "
               "collective with empty transitive fan-out (new)")
def dead_dup_pass(mod: ir.Module, ctx: PlanContext) -> List[Finding]:
    out: List[Finding] = []
    dup_counts: Dict[str, int] = {}
    dead_counts: Dict[str, int] = {}
    for fn in mod.functions.values():
        producers = fn.producers()
        # ---- duplicates: same op, same operand SSA sources, same attrs.
        # jax stamps every collective with a UNIQUE channel_handle, so
        # the handle must be stripped from the key — comparing raw attrs
        # would make two byte-identical exchanges always look distinct
        # and the check could never fire on a real lowering
        seen: Dict[Tuple, str] = {}
        for inst in fn.instructions:
            if inst.kind not in ir.COLLECTIVE_OPS:
                continue
            attrs = re.sub(
                r'channel_handle\s*=\s*#stablehlo\.channel_handle<[^>]*>,?',
                "", inst.attrs)
            key = (inst.kind, tuple(inst.operands),
                   re.sub(r'\s+', " ", attrs))
            if key in seen:
                dup_counts[inst.kind] = dup_counts.get(inst.kind, 0) + 1
            else:
                seen[key] = inst.results[0] if inst.results else ""
        # ---- dead: liveness from the function's terminator operands
        live = set()
        stack = [producers[r] for r in fn.returns if r in producers]
        while stack:
            i = stack.pop()
            if i in live:
                continue
            live.add(i)
            stack.extend(producers[r] for r in fn.instructions[i].refs
                         if r in producers)
        for i, inst in enumerate(fn.instructions):
            if inst.is_collective() and fn.returns and i not in live:
                dead_counts[inst.kind] = dead_counts.get(inst.kind, 0) + 1
    for kind in sorted(dup_counts):
        out.append(Finding(
            pass_name="dead-dup-collective",
            fid=f"dead-dup-collective/duplicate.{kind}",
            severity="error", op=kind,
            message=(f"{dup_counts[kind]} {kind} collective(s) repeat "
                     "an identical (operands, attrs) exchange already "
                     "performed in the same function — CSE the result "
                     "instead of paying the wire twice")))
    for kind in sorted(dead_counts):
        out.append(Finding(
            pass_name="dead-dup-collective",
            fid=f"dead-dup-collective/dead.{kind}",
            severity="error", op=kind,
            message=(f"{dead_counts[kind]} {kind} collective(s) have "
                     "empty transitive fan-out (nothing on the path to "
                     "the function's results consumes them) — dead wire "
                     "traffic left behind by a restructure")))
    return out
