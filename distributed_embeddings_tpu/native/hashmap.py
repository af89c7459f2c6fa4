"""ctypes wrapper for the native IntegerLookup hash map (hashmap.cpp)."""

import numpy as np

from distributed_embeddings_tpu.native import loader


class NativeIntegerLookup:
    """Host hash map: int64 keys -> contiguous indices (0 reserved for OOV).

    Backend for layers.embedding.IntegerLookup — the TPU-VM-host replacement
    for the reference's cuCollections GPU map (embedding_lookup_kernels.cu:383-516).
    """

    def __init__(self, capacity: int):
        import threading
        self._lib = loader.load()
        self.capacity = int(capacity)
        self._handle = self._lib.il_create(self.capacity)
        # ctypes releases the GIL during native calls; the C++ map's
        # internal probe threads assume no concurrent WRITER (phase-2
        # insert). Serialize whole calls so multi-threaded data pipelines
        # sharing one layer stay race-free (intra-call parallelism is
        # unaffected).
        self._call_lock = threading.Lock()

    def __del__(self):
        try:
            if getattr(self, "_handle", None):
                self._lib.il_destroy(self._handle)
                self._handle = None
        except Exception:  # noqa: BLE001 - interpreter teardown
            pass

    @property
    def size(self) -> int:
        # locked like the mutating calls: an ingestion worker may be inside
        # phase-2 insert (non-atomic ++size) while a consumer thread polls
        # progress (e.g. the examples' vocab log line)
        with self._call_lock:
            return int(self._lib.il_size(self._handle))

    def lookup_or_insert(self, keys: np.ndarray) -> np.ndarray:
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        out = np.empty(keys.shape, dtype=np.int64)
        with self._call_lock:
            self._lib.il_lookup_or_insert(
                self._handle, keys.ctypes.data, keys.size, out.ctypes.data)
        return out

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        out = np.empty(keys.shape, dtype=np.int64)
        with self._call_lock:
            self._lib.il_lookup(
                self._handle, keys.ctypes.data, keys.size, out.ctypes.data)
        return out

    def erase(self, keys: np.ndarray) -> np.ndarray:
        """Unbind keys: returns the freed index per key (0 = was not
        bound). Freed indices are reused by later lookup_or_insert calls
        (LIFO) before new indices are minted."""
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        out = np.empty(keys.shape, dtype=np.int64)
        with self._call_lock:
            self._lib.il_erase(
                self._handle, keys.ctypes.data, keys.size, out.ctypes.data)
        return out

    def free_slots(self) -> np.ndarray:
        """Erased (reusable) indices, in reuse order — the binding-table
        free-list the vocab checkpoint round-trips."""
        with self._call_lock:
            n = int(self._lib.il_free_count(self._handle))
            out = np.empty((n,), dtype=np.int64)
            if n:
                self._lib.il_export_free(self._handle, out.ctypes.data)
        return out

    def keys_in_index_order(self):
        # one lock for the count read AND the export: racing an insert
        # could otherwise memcpy keys_by_index mid-realloc. The export is
        # high-water sized (== size pre-erase); erased indices hole as
        # INT64_MIN and are kept so positions stay 1-based-index-aligned.
        with self._call_lock:
            n = int(self._lib.il_high_water(self._handle))
            out = np.empty((n,), dtype=np.int64)
            if n:
                self._lib.il_export_keys(self._handle, out.ctypes.data)
        return out.tolist()

    def counts(self) -> np.ndarray:
        out = np.zeros((self.capacity,), dtype=np.int64)
        with self._call_lock:
            self._lib.il_export_counts(self._handle, out.ctypes.data)
        return out
