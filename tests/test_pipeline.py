"""utils/pipeline.py — bounded background ingestion pipeline lifecycle.

The contract under test (ISSUE 3): worker exceptions surface at the
consumer (after the already-staged items drain, within one batch), shutdown
joins every worker thread (no leaks across pipeline lifetimes),
backpressure caps in-flight memory, and pipelined output is bit-identical
to serial iteration order. Plus the prefetch_to_device tail-behavior fix
(drain staged entries, then raise)."""

import threading
import time

import numpy as np
import pytest

from distributed_embeddings_tpu.utils.pipeline import (
    IngestPipeline, SerialPipeline, staged_batches)
from distributed_embeddings_tpu.utils.prefetch import prefetch_to_device


def _batches(n, seed=0):
    rng = np.random.RandomState(seed)
    for _ in range(n):
        yield (rng.rand(8, 3).astype(np.float32),
               rng.randint(0, 100, (8, 2)).astype(np.int64))


def _stages():
    return [
        ("preprocess", lambda b: (b[0] * 2.0, b[1].astype(np.int32))),
        ("stage", lambda b: (b[0].copy(), b[1] + 1)),
    ]


def test_pipelined_bit_identical_to_serial():
    serial = list(SerialPipeline(_batches(7), _stages()))
    with IngestPipeline(_batches(7), _stages(), depth=2) as pipe:
        pipelined = list(pipe)
    assert len(serial) == len(pipelined) == 7
    for (sn, si), (pn, pi) in zip(serial, pipelined):
        np.testing.assert_array_equal(sn, pn)   # exact — same bits
        np.testing.assert_array_equal(si, pi)
        assert sn.dtype == pn.dtype and si.dtype == pi.dtype


def test_source_exception_surfaces_after_drain():
    def bad_source():
        yield from _batches(3)
        raise ValueError("disk on fire")

    pipe = IngestPipeline(bad_source(), _stages(), depth=2)
    got = []
    with pytest.raises(ValueError, match="disk on fire"):
        for item in pipe:
            got.append(item)
    # every batch produced before the failure was drained first
    assert len(got) == 3
    assert all(not t.is_alive() for t in pipe._threads)


def test_stage_exception_surfaces_within_one_batch():
    calls = {"n": 0}

    def flaky(b):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("bad decode")
        return b

    pipe = IngestPipeline(_batches(10), [("flaky", flaky)], depth=1)
    got = []
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="bad decode"):
        for item in pipe:
            got.append(item)
    # the 2 items preprocessed before the failure arrive, then the error —
    # promptly (no hang, no timeout-length stall)
    assert len(got) == 2
    assert time.monotonic() - t0 < 5.0


def test_close_joins_all_threads_no_leak():
    before = {t for t in threading.enumerate()}
    # exhaustion closes implicitly
    pipe = IngestPipeline(_batches(4), _stages(), depth=2)
    list(pipe)
    # close() mid-stream joins too
    pipe2 = IngestPipeline(_batches(100), _stages(), depth=2)
    next(iter(pipe2))
    pipe2.close()
    leaked = [t for t in threading.enumerate()
              if t not in before and t.is_alive()]
    assert not leaked, f"leaked ingestion threads: {leaked}"
    # idempotent
    pipe.close()
    pipe2.close()


def test_backpressure_bounds_in_flight_batches():
    pulled = {"n": 0}

    def counting_source():
        while True:
            pulled["n"] += 1
            yield np.zeros((4,), np.float32)

    depth, nstages = 2, 2
    pipe = IngestPipeline(counting_source(),
                          [("a", lambda x: x), ("b", lambda x: x)],
                          depth=depth)
    next(iter(pipe))
    deadline = time.monotonic() + 1.0
    while time.monotonic() < deadline:
        time.sleep(0.05)   # consumer stalls; workers must block, not grow
    # bound: one item per queue slot + one in each worker's hands + the
    # consumed one; anything near this is fine — the test is that it does
    # NOT keep growing unboundedly while the consumer stalls
    bound = (nstages + 1) * depth + nstages + 2
    assert pulled["n"] <= bound, (pulled["n"], bound)
    pipe.close()


def test_empty_source_and_no_stages():
    assert list(IngestPipeline(iter(()), [("s", lambda x: x)])) == []
    # no stages: a pure background reader
    assert list(IngestPipeline(iter([1, 2, 3]), [])) == [1, 2, 3]


def test_stage_summaries_account_every_stage():
    pipe = IngestPipeline(_batches(5), _stages(), depth=2)
    list(pipe)
    s = pipe.stage_summaries()
    assert set(s) == {"read", "preprocess", "stage"}
    assert all(v["count"] == 5 for v in s.values())
    assert pipe.bottleneck() in s


def test_staged_batches_serial_vs_pipelined_parity():
    import jax.numpy as jnp
    data = [(np.full((2, 2), i, np.float32),) for i in range(5)]
    serial = list(staged_batches(iter(data), pipelined=False))
    pipe = staged_batches(iter(data), pipelined=True)
    pipelined = list(pipe)
    for (s,), (p,) in zip(serial, pipelined):
        assert isinstance(p, jnp.ndarray)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(p))


def test_duplicate_stage_names_rejected():
    with pytest.raises(ValueError):
        IngestPipeline(iter(()), [("x", id), ("x", id)])
    with pytest.raises(ValueError):
        IngestPipeline(iter(()), [("read", id)])   # reserved


# ---------------------------------------------------------------- prefetch
def test_prefetch_drains_staged_then_raises():
    staged = []

    def bad_source():
        yield 1
        yield 2
        raise OSError("pread failed")

    it = prefetch_to_device(bad_source(), size=4,
                            stage=lambda x: staged.append(x) or x * 10)
    got = []
    with pytest.raises(OSError, match="pread failed"):
        for v in it:
            got.append(v)
    # both staged batches were yielded BEFORE the error surfaced
    assert got == [10, 20]
    assert staged == [1, 2]


def test_prefetch_happy_path_order():
    it = prefetch_to_device(iter(range(5)), size=2, stage=lambda x: x + 100)
    assert list(it) == [100, 101, 102, 103, 104]
