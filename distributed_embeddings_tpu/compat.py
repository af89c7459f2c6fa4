"""Backend seams of the one supported installation (jax / jaxlib 0.9.0).

  * ``shard_map`` is ``jax.shard_map`` with this package's default
    (``check_vma=False``) spelled once.
  * Host memory: both backends the repo runs on expose ``pinned_host`` next
    to ``device`` (the TPU, and XLA:CPU where the tests run), and a plain
    array lands in ``device``. ``host_memory_kind`` returns the space table
    offload stages into, or None on a backend without one (offload then
    stays off).
  * ``assemble_like`` rebuilds an array from per-device shards without
    losing its host memory space on one device.
"""

from typing import Optional

import jax

__all__ = ["shard_map", "host_memory_kind", "default_memory_kind",
           "assemble_like"]


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def host_memory_kind(device) -> Optional[str]:
    """``pinned_host`` where the device can address it, else None."""
    kinds = {m.kind for m in device.addressable_memories()}
    return "pinned_host" if "pinned_host" in kinds else None


def default_memory_kind(device) -> str:
    """The memory space a plain array lands in on `device`. Lets tests
    assert offload placement without hardcoding a backend's space names."""
    return device.default_memory().kind


def assemble_like(global_ref: jax.Array, shards) -> jax.Array:
    """Rebuild an array laid out like `global_ref` from new per-device
    shards, each ``jax.device_put`` like `global_ref`'s shard on its device.
    A single-device array IS its one shard: under a SingleDeviceSharding
    jax 0.9.0's make_array_from_single_device_arrays (like ``shard.data``)
    returns a "device"-typed array for pinned_host data, and the next
    traced host-region gather then refuses to mix it with host ids."""
    if (isinstance(global_ref.sharding, jax.sharding.SingleDeviceSharding)
            and len(shards) == 1):
        return shards[0]
    return jax.make_array_from_single_device_arrays(
        global_ref.shape, global_ref.sharding, shards)
