"""Wire formats for the dp<->mp exchange collectives (ISSUE 5).

Every float collective in the embedding forward/backward — the mp->dp
combined-activation `all_to_all` (layers/dist_model_parallel.py
`_tp_bucket_exchange`), its autodiff transpose moving gradients dp->mp,
the dp->mp weight exchange (padded and ragged), and the row-sliced path's
`psum_scatter`/`all_gather` pair — moves f32 in the reference stack. On
TPU the standard mixed-precision lever is a **bf16 wire format with f32
local math**: encode to bf16 immediately before the collective, decode
immediately after, so the only numerics change is ONE round-to-nearest
per wire crossing while every gather/combine/update stays f32. That
exactly halves the dominant exchange bytes (the `[world, B, f, w]`
activation blocks) without touching the int id wire.

Formats:
  * ``f32``      — identity. The default; callers early-return to the
                   plain `lax` collective, so the lowered program is
                   byte-identical to the pre-wire-seam code.
  * ``bf16``     — round-to-nearest-even bf16 on the wire, both
                   directions.
  * ``bf16-sr``  — bf16 forward; **stochastically rounded** bf16 for the
                   gradient direction. SR spreads the rounding over both
                   neighbors with distance-proportional probability, so
                   ACROSS the many distinct gradient values of a step the
                   wire error centers on zero instead of carrying RNE's
                   systematic bias (the classic low-precision-training
                   argument). The randomness is a counter-less hash of
                   (lane position, value bits) — deterministic per trace,
                   no PRNG key plumbing through the collective seam; the
                   flip side is that the SAME value at the SAME lane
                   rounds the same way every step, so per-coordinate
                   zero-mean over time is NOT guaranteed (pass a
                   different ``salt`` per step if that matters).

The gradient direction is wrapped in `jax.custom_vjp` so the transpose
collective compresses with the *gradient* wire format and local math
stays f32 on both sides — in particular `wire_psum_scatter` re-expresses
the reduce-scatter as encode -> all_to_all -> decode -> f32 local sum, so
cross-device ACCUMULATION never happens in bf16 (a plain bf16
`psum_scatter` would round once per ring hop).

Int id wire: `encode_ids`/`decode_ids` narrow int32 ids to int16 where
the planner proves every value that can legally cross the wire fits
(`parallel/plan.py` sets ``TPBucket.id_wire_dtype`` — the same
prove-the-key-space-fits gate style as PR 4's int32-key-overflow check).
Encoding CLIPS to the int16 range: the planner gate guarantees every
valid id and the hot sentinel sit strictly below the clip ceiling, so an
out-of-range user id stays out-of-range after the round-trip and the
downstream clamp/drop semantics are bit-identical to the int32 wire.
"""

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "WIRE_FORMATS",
    "ID_WIRE_FORMATS",
    "STORE_DTYPES",
    "default_exchange_wire",
    "default_id_wire",
    "default_store_dtype",
    "default_delta_dtype",
    "resolve_wire",
    "resolve_store_dtype",
    "fp8_supported",
    "wire_itemsize",
    "id_wire_itemsize",
    "store_itemsize",
    "store_scale_bytes",
    "delta_row_bytes",
    "snapshot_row_bytes",
    "encode_rows",
    "decode_rows",
    "encode_rows_np",
    "decode_rows_np",
    "store_decode_bound",
    "seam_storage_dtypes",
    "encode_fwd",
    "encode_bwd",
    "stochastic_round_bf16",
    "encode_ids",
    "decode_ids",
    "int16_id_wire_ok",
    "wire_all_to_all",
    "wire_all_gather",
    "wire_psum_scatter",
    "wire_all_to_all_t",
    "wire_psum_scatter_t",
    "wire_id_all_to_all",
    "wire_id_all_gather",
    "ragged_exchange",
    "seam_float_dtypes",
    "seam_id_dtypes",
    "RAGGED_METADATA_DTYPES",
]

WIRE_FORMATS = ("f32", "bf16", "bf16-sr")
ID_WIRE_FORMATS = ("int32", "int16")

# storage dtypes of rows AT REST (ISSUE 15): the wire seam extended to
# memory. 'f32' is the bit-exact default (every storage path
# early-returns to the pre-seam arrays/files); 'int8' stores a row as
# int8 payload + ONE f32 per-row scale (scale = amax/127 — symmetric
# linear quantization, the classic row-wise scheme); 'fp8' stores
# float8_e4m3fn payload + per-row scale (scale = amax/448, the e4m3
# finite max) where the backend ships the dtype. One codec covers every
# row store that rides the train-to-serve spine: cold/offloaded bucket
# tables (decode at gather time), `store/` delta + snapshot stream
# payloads, and the vocab demotion stash.
STORE_DTYPES = ("f32", "int8", "fp8")

# quantization grids: payload magnitudes the per-row scale normalizes to
INT8_AMAX = 127.0
FP8_AMAX = 448.0          # float8_e4m3fn largest finite value

# clip ceiling of the int16 id wire; the planner admits a bucket only when
# every legal wire value (valid ids AND the hot sentinel rows_max) is
# strictly below it, so clipped out-of-range ids can never alias either
INT16_ID_MAX = 2**15 - 1


def default_exchange_wire() -> str:
    """The ``DET_EXCHANGE_WIRE`` default for the float exchange wire
    ('f32' unless overridden); an explicit ``exchange_wire=`` constructor argument always wins."""
    return resolve_wire(os.environ.get("DET_EXCHANGE_WIRE", ""))


def default_id_wire() -> str:
    """``DET_ID_WIRE``: 'auto' (default) lets the planner narrow the id
    wire to int16 per bucket where the key space provably fits; 'int32'
    forces the full-width id wire everywhere."""
    v = os.environ.get("DET_ID_WIRE", "auto")
    if v not in ("auto", "int32"):
        raise ValueError(
            f"DET_ID_WIRE={v!r}: expected 'auto' or 'int32'")
    return v


def resolve_wire(name: Optional[str]) -> str:
    """Validate/normalize a wire-format name (None -> 'f32')."""
    if name is None or name == "":
        return "f32"
    if name not in WIRE_FORMATS:
        raise ValueError(
            f"unknown exchange wire format {name!r}; expected one of "
            f"{WIRE_FORMATS}")
    return name


def wire_itemsize(name: str) -> int:
    """Bytes per element the float wire moves (accounting)."""
    return 4 if resolve_wire(name) == "f32" else 2


def id_wire_itemsize(name: str) -> int:
    return 2 if name == "int16" else 4


# ------------------------------------------------------- storage codec
def default_store_dtype() -> str:
    """The ``DET_STORE_DTYPE`` environment default for the at-rest row
    storage dtype ('f32' unless overridden); an explicit
    ``storage_dtype=`` constructor argument always wins. Per-bucket
    eligibility (only cold/offloaded buckets quantize) is decided at
    plan lowering time, like the exchange wire."""
    return resolve_store_dtype(os.environ.get("DET_STORE_DTYPE", ""))


def default_delta_dtype() -> str:
    """``DET_DELTA_DTYPE``: payload dtype of published `store/` delta and
    snapshot stream files ('f32' default — byte-identical files to the
    pre-seam container). Independent of the table storage dtype: a
    fleet can stream int8 deltas to serving replicas whose tables are
    f32-resident, and vice versa."""
    return resolve_store_dtype(os.environ.get("DET_DELTA_DTYPE", ""))


def resolve_store_dtype(name: Optional[str]) -> str:
    """Validate/normalize a storage-dtype name (None -> 'f32')."""
    if name is None or name == "":
        return "f32"
    if name not in STORE_DTYPES:
        raise ValueError(
            f"unknown storage dtype {name!r}; expected one of "
            f"{STORE_DTYPES}")
    if name == "fp8" and not fp8_supported():
        raise ValueError(
            "storage dtype 'fp8' requested but this backend ships no "
            "float8_e4m3fn (jax.numpy / ml_dtypes too old) — use 'int8' "
            "or 'f32'")
    return name


def fp8_supported() -> bool:
    """True when the toolchain ships float8_e4m3fn end to end (jnp for
    the device codec, ml_dtypes for the host/stream codec)."""
    if not hasattr(jnp, "float8_e4m3fn"):
        return False
    try:
        import ml_dtypes  # noqa: F401
        return hasattr(ml_dtypes, "float8_e4m3fn")
    except ImportError:
        return False


def store_itemsize(name: str) -> int:
    """Bytes per element a row payload occupies at rest."""
    return 4 if resolve_store_dtype(name) == "f32" else 1


def store_scale_bytes(name: str) -> int:
    """Per-row scale overhead bytes (one f32 per quantized row)."""
    return 0 if resolve_store_dtype(name) == "f32" else 4


def delta_row_bytes(width: int, dtype: str) -> int:
    """Bytes ONE published delta row costs at `dtype`: the 8-byte int64
    flat key + the width-element payload + the per-row scale. THE shared
    byte model: `exchange_padding_report`'s `delta_bytes_per_step`, the
    store's publish accounting, and the bench's measured-vs-model
    reconciliation all charge through this one formula (the
    `expected_collective_bytes` discipline applied to the stream)."""
    return 8 + width * store_itemsize(dtype) + store_scale_bytes(dtype)


def snapshot_row_bytes(width: int, dtype: str) -> int:
    """Bytes one snapshot table row costs at `dtype` (no key — snapshots
    carry whole tables in row order)."""
    return width * store_itemsize(dtype) + store_scale_bytes(dtype)


def store_decode_bound(rows, dtype: str, sr: bool = False):
    """Per-element absolute error bound of one encode/decode round trip
    at `dtype`, given the f32 `rows` ([..., width]): int8 RNE rounds to
    the nearest grid point (half a step, amax/254 per row; a full step
    amax/127 under SR), fp8-e4m3 keeps 3 mantissa bits (relative 2^-4 of
    the row amax after scaling). 0.0 at f32 — the bit-exact contract.
    Returns a [...]-shaped per-row bound (numpy)."""
    import numpy as np
    rows = np.asarray(rows, np.float32)
    amax = np.max(np.abs(rows), axis=-1)
    dtype = resolve_store_dtype(dtype)
    if dtype == "f32":
        return np.zeros_like(amax)
    if dtype == "int8":
        return amax / INT8_AMAX * (1.0 if sr else 0.5)
    return amax * (2.0 ** -4) * (2.0 if sr else 1.0)


def _row_scale(amax, grid_amax: float):
    """Per-row scale from the row amax; zero rows take scale 1 so the
    round trip reproduces exact zeros."""
    return jnp.where(amax > 0, amax / grid_amax, 1.0)


def encode_rows(rows: jax.Array, store_dtype: str, sr: bool = False,
                salt: int = 0x85EBCA6B):
    """f32 rows [..., width] -> (payload [..., width], scale [..., 1]).

    'f32' is the identity (scale is None — callers on the default path
    never materialize a scale array, the bit-exact early return).
    'int8': symmetric per-row linear quantization; `sr=True` rounds
    stochastically with the SAME keyless (lane, value-bits, salt) hash
    as `stochastic_round_bf16` — the training write-back path, so the
    quantization error of repeated updates centers on zero across
    values instead of accumulating RNE bias. 'fp8': e4m3 cast after the
    per-row rescale (e4m3's own RNE; SR is int8-only — 3 mantissa bits
    leave no headroom for the hash trick)."""
    store_dtype = resolve_store_dtype(store_dtype)
    if store_dtype == "f32":
        return rows, None
    rows = rows.astype(jnp.float32)
    amax = jnp.max(jnp.abs(rows), axis=-1, keepdims=True)
    if store_dtype == "int8":
        scale = _row_scale(amax, INT8_AMAX)
        y = rows / scale
        if sr:
            bits = lax.bitcast_convert_type(y, jnp.uint32)
            idx = lax.iota(jnp.uint32, y.size).reshape(y.shape)
            h = bits ^ (idx * jnp.uint32(2654435761) + jnp.uint32(salt))
            h = (h ^ (h >> 15)) * jnp.uint32(0x2C1B3C6D)
            h = (h ^ (h >> 12)) * jnp.uint32(0x297A2D39)
            h = h ^ (h >> 15)
            u = (h & jnp.uint32(0xFFFF)).astype(jnp.float32) / 65536.0
            q = jnp.floor(y + u)
        else:
            q = jnp.rint(y)
        payload = jnp.clip(q, -INT8_AMAX, INT8_AMAX).astype(jnp.int8)
        return payload, scale
    scale = _row_scale(amax, FP8_AMAX)
    payload = (rows / scale).astype(jnp.float8_e4m3fn)
    return payload, scale


def decode_rows(payload: jax.Array, scale, store_dtype: str) -> jax.Array:
    """(payload, scale) -> f32 rows; the gather-time decode. 'f32' is
    the identity."""
    if resolve_store_dtype(store_dtype) == "f32":
        return payload
    return payload.astype(jnp.float32) * scale


def encode_rows_np(rows, store_dtype: str, sr: bool = False,
                   salt: int = 0x85EBCA6B):
    """Host-side (numpy) twin of `encode_rows`. Default RNE (published
    stream/stash bytes must be deterministic and reproducible);
    ``sr=True`` is the touched-rows host APPLY's write-back (ISSUE 17) —
    the identical keyless (lane, value-bits, salt) hash as the device
    encoder, int8 only (fp8's own RNE cast, as on device)."""
    import numpy as np
    store_dtype = resolve_store_dtype(store_dtype)
    rows = np.asarray(rows, np.float32)
    if store_dtype == "f32":
        return rows, None
    amax = np.max(np.abs(rows), axis=-1, keepdims=True) \
        if rows.size else np.zeros(rows.shape[:-1] + (1,), np.float32)
    if store_dtype == "int8":
        scale = np.where(amax > 0, amax / INT8_AMAX, 1.0).astype(np.float32)
        with np.errstate(invalid="ignore"):
            y = (rows / scale).astype(np.float32)
            if sr and y.size:
                bits = y.view(np.uint32)
                idx = np.arange(y.size, dtype=np.uint32).reshape(y.shape)
                with np.errstate(over="ignore"):
                    h = bits ^ (idx * np.uint32(2654435761)
                                + np.uint32(salt))
                    h = (h ^ (h >> np.uint32(15))) * np.uint32(0x2C1B3C6D)
                    h = (h ^ (h >> np.uint32(12))) * np.uint32(0x297A2D39)
                    h = h ^ (h >> np.uint32(15))
                u = (h & np.uint32(0xFFFF)).astype(np.float32) / 65536.0
                q = np.floor(y + u)
            else:
                q = np.rint(y)
        payload = np.clip(q, -INT8_AMAX, INT8_AMAX).astype(np.int8)
        return payload, scale
    import ml_dtypes
    scale = np.where(amax > 0, amax / FP8_AMAX, 1.0).astype(np.float32)
    payload = (rows / scale).astype(ml_dtypes.float8_e4m3fn)
    return payload, scale


def decode_rows_np(payload, scale, store_dtype: str):
    import numpy as np
    if resolve_store_dtype(store_dtype) == "f32":
        return np.asarray(payload, np.float32)
    payload = np.asarray(payload)
    if store_dtype == "fp8":
        import ml_dtypes
        if payload.dtype != np.dtype(ml_dtypes.float8_e4m3fn):
            # .npz containers round-trip the custom float8 dtype as raw
            # 1-byte void — same bits, lost descriptor; view it back
            payload = payload.view(ml_dtypes.float8_e4m3fn)
    return payload.astype(np.float32) * np.asarray(scale, np.float32)


# ------------------------------------------------------------- encoders
def encode_fwd(x: jax.Array, wire: str) -> jax.Array:
    """Forward-direction wire encode (deterministic RNE for bf16*)."""
    if wire == "f32":
        return x
    return x.astype(jnp.bfloat16)


def encode_bwd(g: jax.Array, wire: str) -> jax.Array:
    """Gradient-direction wire encode ('bf16-sr' -> stochastic round)."""
    if wire == "f32":
        return g
    if wire == "bf16-sr":
        return stochastic_round_bf16(g)
    return g.astype(jnp.bfloat16)


def stochastic_round_bf16(x: jax.Array, salt: int = 0x9E3779B9) -> jax.Array:
    """f32 -> bf16 with stochastic rounding: P(round up) equals the
    fractional distance to the upper representable neighbor, so over an
    ensemble of distinct values the rounding error centers on zero
    (E[sr(X)] == E[X] when the hash is exercised across many values).

    The random source is a hash of (flat lane index, value bits, salt) —
    no PRNG key crosses the collective seam, and the result is
    deterministic for a given (array, salt), which keeps traced programs
    reproducible. The trade: a value that REPEATS at the same lane
    rounds identically every time, so the zero-mean property is across
    values/lanes, not per coordinate over steps — mix a per-step
    ``salt`` in if per-coordinate unbiasedness over time is required.
    Non-finite and non-f32 inputs fall back to the deterministic cast
    (adding noise bits to an inf/NaN pattern would corrupt it)."""
    if x.dtype != jnp.float32:
        return x.astype(jnp.bfloat16)
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    # cheap integer mix (xxhash-style avalanche) of position ^ value bits
    idx = lax.iota(jnp.uint32, x.size).reshape(x.shape)
    h = bits ^ (idx * jnp.uint32(2654435761) + jnp.uint32(salt))
    h = (h ^ (h >> 15)) * jnp.uint32(0x2C1B3C6D)
    h = (h ^ (h >> 12)) * jnp.uint32(0x297A2D39)
    h = h ^ (h >> 15)
    rnd = h & jnp.uint32(0xFFFF)
    up = ((bits + rnd) >> 16).astype(jnp.uint16)
    sr = lax.bitcast_convert_type(up, jnp.bfloat16)
    return jnp.where(jnp.isfinite(x), sr, x.astype(jnp.bfloat16))


def int16_id_wire_ok(max_wire_value: int) -> bool:
    """True when every legal wire value (valid pre-offset ids and the
    sentinel) sits STRICTLY below the int16 clip ceiling — the
    planner-side gate for narrowing one bucket's id wire."""
    return 0 <= max_wire_value < INT16_ID_MAX


def encode_ids(ids: jax.Array, id_wire: str) -> jax.Array:
    """Narrow an int id block for the wire. Clipping (not wrapping) keeps
    out-of-range ids out of range: the planner gate puts every legal
    value strictly below INT16_ID_MAX, so a clipped invalid id can alias
    neither a valid row nor the hot sentinel."""
    if id_wire != "int16":
        return ids
    return jnp.clip(ids, -2**15, INT16_ID_MAX).astype(jnp.int16)


def decode_ids(ids: jax.Array, id_wire: str,
               dtype=jnp.int32) -> jax.Array:
    if id_wire != "int16":
        return ids
    return ids.astype(dtype)


# -------------------------------------------------- wrapped collectives
@functools.lru_cache(maxsize=None)
def _wired_all_to_all(axis: str, wire: str, dtype_name: str):
    """custom_vjp all_to_all (split 0 / concat 0): wire-encoded operand
    both directions, output decoded back to the caller's dtype. The
    split0/concat0 all_to_all is its own transpose, so the bwd rule is
    the same collective over the gradient wire."""
    out_dtype = jnp.dtype(dtype_name)

    def run(x, enc):
        y = enc(x, wire)
        y = lax.all_to_all(y, axis, split_axis=0, concat_axis=0)
        return y.astype(out_dtype)

    @jax.custom_vjp
    def f(x):
        return run(x, encode_fwd)

    def fwd(x):
        return run(x, encode_fwd), None

    def bwd(_, g):
        return (run(g, encode_bwd),)

    f.defvjp(fwd, bwd)
    return f


def wire_all_to_all(x: jax.Array, axis: str, wire: str) -> jax.Array:
    """`lax.all_to_all(split 0 / concat 0)` behind the wire seam.

    'f32' returns the plain collective — the lowered program is
    byte-identical to pre-seam code (the bit-exactness contract of the
    default path). Other formats compress the operand on the wire and
    decode to the input dtype; the autodiff transpose compresses the
    gradient with the format's gradient encoder."""
    wire = resolve_wire(wire)
    if wire == "f32":
        return lax.all_to_all(x, axis, split_axis=0, concat_axis=0)
    return _wired_all_to_all(axis, wire, x.dtype.name)(x)


@functools.lru_cache(maxsize=None)
def _wired_all_gather(axis: str, wire: str, dtype_name: str, world: int):
    """custom_vjp tiled all_gather over axis 0. The transpose of a tiled
    all_gather is a tiled psum_scatter; it is expressed here as
    encode -> all_to_all -> decode -> f32-local sum so cross-device
    accumulation never happens at wire precision."""
    out_dtype = jnp.dtype(dtype_name)

    @jax.custom_vjp
    def f(x):
        y = lax.all_gather(encode_fwd(x, wire), axis, axis=0, tiled=True)
        return y.astype(out_dtype)

    def fwd(x):
        return f(x), None

    def bwd(_, g):                       # g: [B, ...] -> [B_l, ...]
        h = encode_bwd(g, wire)
        h = h.reshape((world, g.shape[0] // world) + g.shape[1:])
        h = lax.all_to_all(h, axis, split_axis=0, concat_axis=0)
        return (h.astype(out_dtype).sum(axis=0),)

    f.defvjp(fwd, bwd)
    return f


def wire_all_gather(x: jax.Array, axis: str, wire: str,
                    world: int) -> jax.Array:
    """Tiled `lax.all_gather` over axis 0 behind the wire seam (the
    row-sliced path's weight broadcast)."""
    wire = resolve_wire(wire)
    if wire == "f32":
        return lax.all_gather(x, axis, axis=0, tiled=True)
    return _wired_all_gather(axis, wire, x.dtype.name, world)(x)


@functools.lru_cache(maxsize=None)
def _wired_psum_scatter(axis: str, wire: str, dtype_name: str, world: int):
    """custom_vjp tiled psum_scatter over dim 0, wire-compressed:
    fwd = encode -> all_to_all -> decode -> f32-local sum over sources
    (same wire volume as the reduce-scatter ring, but every ADD runs at
    the caller's precision); bwd = the transpose, a tiled all_gather of
    the wire-encoded gradient."""
    out_dtype = jnp.dtype(dtype_name)

    @jax.custom_vjp
    def f(x):                            # x: [B, ...] -> [B_l, ...]
        y = encode_fwd(x, wire)
        y = y.reshape((world, x.shape[0] // world) + x.shape[1:])
        y = lax.all_to_all(y, axis, split_axis=0, concat_axis=0)
        return y.astype(out_dtype).sum(axis=0)

    def fwd(x):
        return f(x), None

    def bwd(_, g):
        h = lax.all_gather(encode_bwd(g, wire), axis, axis=0, tiled=True)
        return (h.astype(out_dtype),)

    f.defvjp(fwd, bwd)
    return f


def wire_psum_scatter(x: jax.Array, axis: str, wire: str,
                      world: int) -> jax.Array:
    """Tiled `lax.psum_scatter` over dim 0 behind the wire seam (the
    row-sliced path's partial-sum return)."""
    wire = resolve_wire(wire)
    if wire == "f32":
        return lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
    return _wired_psum_scatter(axis, wire, x.dtype.name, world)(x)


# ------------------------------------------------- explicit transposes
# The lookahead drain stage (ISSUE 9, schedule/lookahead.py) moves the
# dense stage's activation cotangents dp->mp OUTSIDE autodiff: the
# forward exchange ran one step earlier in the prefetch stage, in a
# different traced region, so the gradient transpose must be invoked
# explicitly. These are the exact bwd rules of the custom_vjp wrappers
# above, exported as plain functions — 'f32' lowers to the identical
# lax collective JAX's own transpose rules emit for the monolithic
# step, which is what makes lookahead=1 bit-exact against it.

def wire_all_to_all_t(g: jax.Array, axis: str, wire: str) -> jax.Array:
    """Transpose of `wire_all_to_all`: the split0/concat0 all_to_all is
    its own transpose, over the GRADIENT wire encoding."""
    wire = resolve_wire(wire)
    if wire == "f32":
        return lax.all_to_all(g, axis, split_axis=0, concat_axis=0)
    y = lax.all_to_all(encode_bwd(g, wire), axis,
                       split_axis=0, concat_axis=0)
    return y.astype(g.dtype)


def wire_psum_scatter_t(g: jax.Array, axis: str, wire: str,
                        world: int) -> jax.Array:
    """Transpose of `wire_psum_scatter`: a tiled all_gather of the
    wire-encoded gradient (the reduce-scatter's transpose)."""
    del world  # kept for signature symmetry with wire_psum_scatter
    wire = resolve_wire(wire)
    if wire == "f32":
        return lax.all_gather(g, axis, axis=0, tiled=True)
    h = lax.all_gather(encode_bwd(g, wire), axis, axis=0, tiled=True)
    return h.astype(g.dtype)


# ------------------------------------------------------ id-wire exchanges
# Int ids carry no gradient, so these are plain (not custom_vjp)
# collectives behind the encode/decode pair — but they ARE exchange
# collectives, and the repo invariant (ISSUE 10, tools/lint_invariants.py
# 'naked-collective') is that every one of those lives in this module:
# the static wire-seam audit (analysis/passes.py) attributes every
# lowered collective's payload dtype to a plan group's declared format,
# and an id exchange assembled inline at a call site is exactly the kind
# of seam escape it exists to catch.

def wire_id_all_to_all(ids: jax.Array, axis: str, id_wire: str) -> jax.Array:
    """dp->mp id-block `all_to_all` (split 0 / concat 0) behind the id
    wire seam: int16 on the wire where the planner proved the key space
    fits (lossless — see `encode_ids` clip semantics), the caller's
    dtype on both sides."""
    return decode_ids(
        lax.all_to_all(encode_ids(ids, id_wire), axis,
                       split_axis=0, concat_axis=0),
        id_wire, ids.dtype)


def wire_id_all_gather(ids: jax.Array, axis: str, id_wire: str) -> jax.Array:
    """Tiled id `all_gather` over axis 0 behind the id wire seam (the
    row-sliced path's id broadcast)."""
    return decode_ids(
        lax.all_gather(encode_ids(ids, id_wire), axis, axis=0,
                       tiled=True),
        id_wire, ids.dtype)


def ragged_exchange(operand, output, in_off, send_sz, out_off, recv_sz,
                    axis: str, native: bool):
    """One true-splits all-to-all: sends `send_sz[d]` rows of `operand`
    (starting at `in_off[d]`) to each device d, landing at `out_off[d]` in
    d's `output`; `recv_sz[s]` rows arrive from each source s. This is the
    reference's `hvd.alltoall(x, splits)` contract
    (dist_model_parallel.py:134, :211): wire bytes are the true nnz, not
    the padded block.

    native=True lowers to `lax.ragged_all_to_all` (TPU; XLA:CPU has no
    lowering — see tools/tpu_ragged_check.py). native=False runs a
    semantics-exact emulation from equal-shaped collectives (all_gather +
    masked gather) so the FULL exchange path — metadata, layouts,
    reassembly — is executable and equivalence-tested on the CPU mesh;
    only the op itself differs, and that op runs on the chips in
    `chip_smoke.py --chips 4` (tools/tpu_ragged_check.py isolates it).

    The OPERAND must already be wire-encoded by the caller (the bucket's
    float or id format); the emulation's three metadata all_gathers move
    int32 offsets/sizes — `RAGGED_METADATA_DTYPES`, the one int32
    collective payload the wire-seam audit admits beyond the declared id
    wires when a program takes the emulated ragged path."""
    if native:
        return lax.ragged_all_to_all(operand, output, in_off, send_sz,
                                     out_off, recv_sz, axis_name=axis)
    ops = lax.all_gather(operand, axis)            # [world, S, inner]
    g_in = lax.all_gather(in_off, axis)            # [world, world]
    g_send = lax.all_gather(send_sz, axis)
    g_out = lax.all_gather(out_off, axis)
    me = lax.axis_index(axis)
    n_out = output.shape[0]
    i = jnp.arange(n_out)
    starts = g_out[:, me]                          # my chunk starts, per src
    # receive extent honors BOTH sides' metadata (sender's send_sz and my
    # recv_sz), so a wrong recv_sz corrupts the emulation the same way it
    # would corrupt the native op — CPU tests catch it
    sizes = jnp.minimum(g_send[:, me], recv_sz)
    src0 = g_in[:, me]
    m = ((i[None, :] >= starts[:, None])
         & (i[None, :] < (starts + sizes)[:, None]))   # [world, n_out]
    valid = jnp.any(m, axis=0)
    s_idx = jnp.argmax(m, axis=0)
    src_row = jnp.clip(src0[s_idx] + i - starts[s_idx], 0,
                       operand.shape[0] - 1)
    gathered = ops[s_idx, src_row]
    return jnp.where(valid[:, None], gathered, output)


# --------------------------------------------- static-audit attribution
# Pass-readable byte/dtype attribution hooks (ISSUE 10): the wire-seam
# and dtype-promotion passes (analysis/passes.py) read the legal
# StableHLO payload element types off the SAME module that implements
# the encodings, so the audit and the seam cannot drift. NOT attributed
# here by design: cross-device ACCUMULATIONS (hot-shard psum, loss
# psum) lower to `all_reduce`, which is outside the audited exchange
# collective set — they are the declared-uncompressed remainder.

# the ragged emulation's offset/size metadata all_gathers (see
# `ragged_exchange`) — int32 regardless of the bucket's id wire
RAGGED_METADATA_DTYPES = ("i32",)


def seam_float_dtypes(wire: str):
    """StableHLO element types a float exchange at `wire` may put on a
    collective ('f32' early-returns to the plain lax collective; every
    compressed format crosses as bf16)."""
    return ("f32",) if resolve_wire(wire) == "f32" else ("bf16",)


def seam_id_dtypes(id_wire: str):
    """StableHLO element types the id wire at `id_wire` may put on a
    collective ('auto' covers both: the planner narrows per bucket)."""
    if id_wire == "int16":
        return ("i16",)
    if id_wire == "int32":
        return ("i32",)
    return ("i16", "i32")


def seam_storage_dtypes(store_dtype: str):
    """StableHLO element types a bucket's at-rest storage at
    `store_dtype` may put in a lowered program ('f32' declares NOTHING
    quantized: an i8/f8 buffer in an all-f32-storage program is a seam
    escape the storage-dtype pass flags). Read by analysis/passes.py
    off this module so the audit and the codec cannot drift."""
    store_dtype = resolve_store_dtype(store_dtype)
    if store_dtype == "int8":
        return ("i8",)
    if store_dtype == "fp8":
        return ("f8E4M3FN",)
    return ()
