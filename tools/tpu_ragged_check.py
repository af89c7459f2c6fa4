"""Does this backend lower `lax.ragged_all_to_all`? (single-chip check)

The true-splits exchange (reference dist_model_parallel.py:169-288 —
`hvd.alltoall` with per-destination `splits` paying exactly nnz) maps to
`lax.ragged_all_to_all` on TPU. XLA:CPU has no lowering, so the virtual
mesh cannot test the op itself. This script answers the half that needs
only one real chip: does the TPU backend compile AND execute the op with
correct semantics on a 1-device mesh? (`chip_smoke.py --chips 4` runs the
whole exchange across four.)

Run on the chip: `chiprun -- python tools/tpu_ragged_check.py`.
"""

import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def main():
    d = jax.devices()
    assert d and d[0].platform != "cpu", f"cpu fallback: {d}"
    print("devices", d, flush=True)
    mesh = Mesh(np.array(d[:1]), ("x",))
    n = 16

    def body(x):
        out = jnp.full((n,), -1.0, x.dtype)
        in_off = jnp.array([0], jnp.int32)
        send = jnp.array([5], jnp.int32)
        out_off = jnp.array([2], jnp.int32)
        recv = jnp.array([5], jnp.int32)
        return lax.ragged_all_to_all(x, out, in_off, send, out_off, recv,
                                     axis_name="x")

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("x"),),
                              out_specs=P("x")))
    # f32 AND int32: the distributed forward's ragged path
    # (DET_RAGGED_EXCHANGE) moves int32 ids
    for dtype in (jnp.float32, jnp.int32):
        x = jnp.arange(n, dtype=dtype)
        t0 = time.perf_counter()
        got = np.asarray(jax.block_until_ready(f(x)))
        dt = time.perf_counter() - t0
        want = np.full((n,), -1.0, np.float32).astype(dtype)
        want[2:7] = np.arange(5).astype(dtype)
        np.testing.assert_array_equal(got, want)
        print(f"ragged_all_to_all[{jnp.dtype(dtype).name}]: LOWERS + "
              f"CORRECT on {d[0].platform} (compile+run {dt:.1f}s)",
              flush=True)


if __name__ == "__main__":
    main()
