"""Single-device embedding layers (TPU-native, functional).

API mirror of the reference's Embedding / ConcatOneHotEmbedding / IntegerLookup
(reference: distributed_embeddings/python/layers/embedding.py:50-281), redesigned
as explicit-parameter functional modules: a layer object holds static config
only; ``init(key)`` returns a params pytree and ``__call__(params, inputs)``
is a pure function, so everything composes with jit / pjit / shard_map /
autodiff with no framework magic.
"""

import os
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from distributed_embeddings_tpu.ops import embedding_ops
from distributed_embeddings_tpu.utils.initializers import get_initializer


class Embedding:
    """Turns indices into fixed-size vectors, with optional built-in combine.

    Mirrors reference Embedding (embedding.py:50-170): a keras Embedding
    unified with embedding_lookup_sparse. Supported inputs when combiner is
    set: N-D dense ids, 2-D RaggedIds, 2-D SparseIds.

    Args:
      input_dim: vocabulary size.
      output_dim: embedding width.
      embeddings_initializer: initializer spec (see utils.initializers).
      combiner: None | 'sum' | 'mean'.
      use_custom_kernel: route the multi-hot path through the Pallas fused
        kernel when available (the reference's custom-CUDA-kernel toggle,
        embedding.py:80). XLA-native path otherwise.
      dtype: parameter dtype.
    """

    def __init__(self,
                 input_dim: int,
                 output_dim: int,
                 embeddings_initializer="uniform",
                 combiner: Optional[str] = None,
                 use_custom_kernel: bool = True,
                 dtype=jnp.float32,
                 name: Optional[str] = None):
        if input_dim <= 0 or output_dim <= 0:
            raise ValueError(
                f"Both input_dim and output_dim should be positive, "
                f"found {input_dim} and {output_dim}")
        if combiner not in (None, "sum", "mean"):
            raise ValueError(f"Unsupported combiner {combiner}")
        self.input_dim = input_dim
        self.output_dim = output_dim
        self.embeddings_initializer = embeddings_initializer
        self.combiner = combiner
        self.use_custom_kernel = use_custom_kernel
        self.dtype = dtype
        self.name = name

    def init(self, key) -> dict:
        init_fn = get_initializer(self.embeddings_initializer)
        return {
            "embeddings": init_fn(key, (self.input_dim, self.output_dim), self.dtype)
        }

    def __call__(self, params: dict, inputs):
        table = params["embeddings"]
        ids = inputs
        if isinstance(ids, (embedding_ops.RaggedIds, embedding_ops.SparseIds)):
            return embedding_ops.embedding_lookup(table, ids, combiner=self.combiner)
        ids = jnp.asarray(ids)
        out_shape = None
        if ids.ndim == 1:
            if self.combiner is not None:
                raise ValueError(
                    "1D input with combiner is ambiguous. Please create batch dimension.")
            ids = ids.reshape(-1, 1)
            out_shape = (-1, self.output_dim)
        elif ids.ndim > 2:
            # reduce over last dim only (reference embedding.py:124-138)
            if self.combiner is not None:
                out_shape = (-1,) + tuple(ids.shape[1:-1]) + (self.output_dim,)
            else:
                out_shape = (-1,) + tuple(ids.shape[1:]) + (self.output_dim,)
            ids = ids.reshape(-1, ids.shape[-1])
        if (self.combiner is not None and ids.ndim == 2 and ids.shape[1] > 1
                and self._pallas_enabled()):
            from distributed_embeddings_tpu.ops import pallas_lookup
            out = pallas_lookup.fused_embedding_lookup(
                table, ids, combiner=self.combiner)
        else:
            out = embedding_ops.embedding_lookup(table, ids,
                                                 combiner=self.combiner)
        if out_shape is not None:
            out = out.reshape(out_shape)
        return out

    def _pallas_enabled(self) -> bool:
        """Custom kernels compile only on real TPU; elsewhere the XLA path is
        both the fallback and the numerics reference (interpret mode is for
        tests, far too slow for training)."""
        if not self.use_custom_kernel:
            return False
        from distributed_embeddings_tpu.ops import pallas_lookup
        if os.environ.get("DET_FORCE_PALLAS", "0") == "1":
            return True
        return pallas_lookup.is_tpu_backend()

    def compute_output_shape(self, input_shape):
        if self.combiner is None:
            return tuple(input_shape) + (self.output_dim,)
        return tuple(input_shape[:-1]) + (self.output_dim,)

    def get_config(self) -> dict:
        return {
            "input_dim": self.input_dim,
            "output_dim": self.output_dim,
            "embeddings_initializer": self.embeddings_initializer,
            "combiner": self.combiner,
            "use_custom_kernel": self.use_custom_kernel,
            "dtype": self.dtype,
            "name": self.name,
        }

    @classmethod
    def from_config(cls, config: dict) -> "Embedding":
        config = dict(config)
        # accept stock-keras-style configs (reference embedding.py:163-170)
        config.pop("mask_zero", None)
        config.pop("input_length", None)
        config.pop("embeddings_regularizer", None)
        config.pop("activity_regularizer", None)
        config.pop("embeddings_constraint", None)
        return cls(**config)


class ConcatOneHotEmbedding:
    """Many one-hot tables fused into one tall table; a single offset gather.

    Mirror of reference ConcatOneHotEmbedding (embedding.py:173-198).
    """

    def __init__(self, feature_sizes: Sequence[int], embedding_width: int,
                 embeddings_initializer="uniform", dtype=jnp.float32):
        self.feature_sizes = list(feature_sizes)
        self.embedding_width = embedding_width
        self.embeddings_initializer = embeddings_initializer
        self.dtype = dtype
        self._offsets_np = np.concatenate([[0], np.cumsum(feature_sizes)])

    def init(self, key) -> dict:
        init_fn = get_initializer(self.embeddings_initializer)
        shape = (int(self._offsets_np[-1]), self.embedding_width)
        return {"params": init_fn(key, shape, self.dtype)}

    def __call__(self, params: dict, inputs):
        offsets = jnp.asarray(self._offsets_np[:-1], dtype=jnp.int32)
        offset_ids = jnp.asarray(inputs) + offsets
        return jnp.take(params["params"], offset_ids, axis=0)


class IntegerLookup:
    """Maps raw int64 keys to contiguous indices, building vocab on the fly.

    Mirror of reference IntegerLookup (embedding.py:202-281). The reference's
    GPU backend is a cuCollections hash map living in device memory
    (embedding_lookup_kernels.cu:383-516); TPUs have no device-side dynamic
    hash table, so the TPU-native design runs the hash on the TPU-VM host —
    a C++ open-addressing table (native/hashmap.cpp, loaded via ctypes), with
    a pure-numpy twin on request — and keeps the device side a plain gather. Index 0 is
    reserved for OOV, matching the reference (embedding.py:219-220).

    This layer is stateful host-side preprocessing: call it outside jit (like
    a tf.data transform), or via `as_callback()` inside jit.

    Reserved keys: the two most negative int64 values (INT64_MIN and
    INT64_MIN+1 — the native map's empty/tombstone slot sentinels) are
    never bound; they translate to OOV (0) on every path, on both
    backends. No realistic hash or id space reaches them.
    """

    def __init__(self, max_tokens: int, use_native: Optional[bool] = None):
        max_tokens = int(max_tokens)
        self.max_tokens = max_tokens
        self.capacity = max_tokens + 1
        if use_native is None:
            use_native = os.environ.get("DET_DISABLE_NATIVE", "0") != "1"
        if use_native:
            # builds native/_det_native.so on demand; a failed build raises
            # (ask for the numpy backend explicitly: use_native=False or
            # DET_DISABLE_NATIVE=1 — orders of magnitude fewer keys/sec)
            from distributed_embeddings_tpu.native import (
                hashmap as native_hashmap)
            backend = native_hashmap.NativeIntegerLookup(self.capacity)
        else:
            backend = _NumpyIntegerLookup(self.capacity)
        self._backend = backend

    @property
    def native(self) -> bool:
        """True when the C++ open-addressing backend is active."""
        return not isinstance(self._backend, _NumpyIntegerLookup)

    def __call__(self, inputs):
        arr = np.asarray(inputs, dtype=np.int64)
        flat = arr.reshape(-1)
        if self.native:
            # the native backend probes in parallel (O(n), multi-thread)
            # and its ordered sequential insert phase keeps first-
            # appearance id assignment with duplicates in the batch, so
            # it takes the raw stream — a numpy pre-unique would
            # serialize everything behind an O(n log n) sort
            out = self._backend.lookup_or_insert(flat)
        else:
            # numpy fallback: per-batch unique before the per-key dict
            # loop (the reference's CPU backend does exactly this,
            # embedding.py:246-252) — power-law id streams are duplicate-
            # heavy, so hashing |unique| << N keys wins. np.unique sorts;
            # reorder by first appearance so insertion ids (and
            # get_vocabulary order) match the sequential contract.
            uniq, first_idx, inv = np.unique(flat, return_index=True,
                                             return_inverse=True)
            if len(uniq) < len(flat):
                order = np.argsort(first_idx, kind="stable")
                out_u = self._backend.lookup_or_insert(uniq[order])
                rank = np.empty_like(order)
                rank[order] = np.arange(len(order))
                out = out_u[rank][inv]
            else:
                out = self._backend.lookup_or_insert(flat)
        if not self.native:
            # the dedup above hides duplicate occurrences from the numpy
            # backend; count the full stream here (the native backend
            # counts per occurrence inside its probe)
            self._backend.add_counts(out)
        res = out.reshape(arr.shape)
        if isinstance(inputs, jax.Array):
            return jnp.asarray(res)
        return res

    def lookup(self, inputs):
        """Query-only lookup (no vocabulary growth); unknown keys -> 0."""
        arr = np.asarray(inputs, dtype=np.int64)
        out = self._backend.lookup(arr.reshape(-1))
        return out.reshape(arr.shape)

    def as_callback(self, inputs: jax.Array) -> jax.Array:
        """Run the host hash under jit via io_callback (ordered: mutates state)."""
        import jax.experimental

        out_dtype = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32

        def host_fn(x):
            out = np.asarray(self.__call__(np.asarray(x)))
            return out.astype(out_dtype)

        return jax.experimental.io_callback(
            host_fn, jax.ShapeDtypeStruct(inputs.shape, out_dtype), inputs,
            ordered=True)

    def counts(self) -> np.ndarray:
        """Per-index access frequencies: [capacity] int64, index 0 = OOV.

        counts()[i] is how many times translated index i was produced by
        `__call__`/`lookup_or_insert` — the natural frequency source for
        hot-row admission (`DistributedEmbedding.hot_keys_from_counts`
        consumes exactly this, truncated to the table's input_dim). The
        native backend counts with relaxed atomics in its parallel probe;
        the numpy fallback counts per batch."""
        return self._backend.counts()

    def erase(self, keys) -> np.ndarray:
        """Unbind keys from the vocabulary (ISSUE 7 eviction): each key's
        index is released and will be REUSED by a later insertion (LIFO),
        so a bounded table can follow an unbounded, drifting key space.
        Returns the freed index per key (0 = key was not bound). A later
        `lookup` of an erased key returns 0 (OOV) again, and its
        frequency count resets — a future tenant of the index must not
        inherit it."""
        arr = np.asarray(keys, dtype=np.int64)
        return self._backend.erase(arr.reshape(-1)).reshape(arr.shape)

    def free_slots(self) -> np.ndarray:
        """Erased (reusable) indices in reuse order — together with
        `get_vocabulary` this is the full binding state eviction-aware
        checkpoints round-trip."""
        return np.asarray(self._backend.free_slots(), np.int64)

    def get_vocabulary(self):
        """Keys in insertion (lookup-index) order, with -1 in the OOV slot
        (reference embedding.py:255-281 returns [-1] + keys). Erased
        indices appear as None holes (their positions must keep later
        keys index-aligned) until reused."""
        hole = np.iinfo(np.int64).min
        return [-1] + [None if k == hole else k
                       for k in self._backend.keys_in_index_order()]

    @property
    def size(self) -> int:
        """Live vocabulary size including the OOV slot (erases shrink)."""
        return self._backend.size + 1  # + OOV slot


class _NumpyIntegerLookup:
    """Pure-python fallback backend: dict-based, OOV (full table) -> 0.
    Mirrors the native contract including erase: freed indices reused
    LIFO before new ones are minted past the high-water mark, and the
    two RESERVED key values (the native map's slot sentinels,
    INT64_MIN and INT64_MIN+1) map to OOV without ever being stored —
    a dict would happily hold them, but the backends must agree."""

    _HOLE = np.iinfo(np.int64).min
    _RESERVED = (np.iinfo(np.int64).min, np.iinfo(np.int64).min + 1)

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._map = {}
        self._counts = np.zeros((capacity,), np.int64)
        self._free = []           # erased indices, reuse order (LIFO)
        self._high = 0            # highest index ever assigned

    @property
    def size(self) -> int:
        return len(self._map)

    def lookup_or_insert(self, keys: np.ndarray) -> np.ndarray:
        out = np.zeros(keys.shape, dtype=np.int64)
        m = self._map
        cap = self.capacity - 1  # index 0 reserved for OOV
        for i, k in enumerate(keys.tolist()):
            if k in self._RESERVED:
                out[i] = 0
                continue
            idx = m.get(k)
            if idx is None:
                if len(m) < cap:
                    if self._free:
                        idx = self._free.pop()
                    else:
                        self._high += 1
                        idx = self._high
                    m[k] = idx
                else:
                    idx = 0
            out[i] = idx
        return out

    def erase(self, keys: np.ndarray) -> np.ndarray:
        out = np.zeros(keys.shape, dtype=np.int64)
        for i, k in enumerate(keys.tolist()):
            idx = self._map.pop(k, None)
            if idx is not None:
                out[i] = idx
                self._free.append(idx)
                self._counts[idx] = 0
        return out

    def free_slots(self) -> np.ndarray:
        return np.asarray(self._free, np.int64)

    def add_counts(self, indices: np.ndarray) -> None:
        """Per-OCCURRENCE frequency accounting (the class-level caller
        passes the full pre-dedup index stream, mirroring the native
        backend's in-probe counting)."""
        np.add.at(self._counts, indices.reshape(-1), 1)

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        out = np.zeros(keys.shape, dtype=np.int64)
        m = self._map
        for i, k in enumerate(keys.tolist()):
            out[i] = m.get(k, 0)
        return out

    def keys_in_index_order(self):
        out = [self._HOLE] * self._high
        for k, idx in self._map.items():
            out[idx - 1] = k
        return out

    def counts(self) -> np.ndarray:
        return self._counts.copy()
