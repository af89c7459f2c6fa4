"""Compile a cell's training step at its real size for a DESCRIBED TPU
(no chip attached, nothing runs) and print what the compiler says it needs.

    JAX_PLATFORMS=cpu python3 -m benchmark.tools.describe_chip --workload <name>

A rehearsal before a chip call (`on-chip-measurement` guide, section 2): a
step that does not fit the chip's memory, or that the chip's compiler
refuses, shows here at no chip time. It prints ``memory_analysis()`` per
device and counts the collectives, sorts, scatters and gathers of the compiled
program. A compile that passes is not a chip run and gives no time.

The batch is the cell's own: the shapes and dtypes of the first batch its
generator makes (numerical features and ``[B, 1]`` f32 clicks, or document
boundaries and ``[T]`` int32 next ids; no data is kept), lowered through
``step_fn.lower``, the handle of the very ``jax.jit`` a run dispatches to.

The library asks ``jax.default_backend()`` where a TPU takes another branch
than the CPU, and here it would see the CPU: this script answers "tpu" for
it, as ``tests/test_chip_compile.py`` does. The topology is described inside
``main``, never at import.
"""

import argparse
import json
import os
import re
import sys

from benchmark.harness import spec

COUNTED = ("ragged-all-to-all", "all-to-all", "all-reduce", "all-gather",
           "reduce-scatter", "collective-permute", "custom-call", "sort",
           "scatter", "gather")


def mesh_param_shapes(cell, built, mesh):
    """Shapes and shardings of a meshed model's parameters. Its `init`
    stages each rank's shard on that rank's device, which a described device
    cannot hold: the dense part comes from a mesh-less twin's `init`, the
    buckets from the plan's stacked [world, rows_max, width]."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    emb = built.model.embedding
    if emb.strategy.dp_configs:
        raise NotImplementedError("data-parallel tables on a mesh")
    rep = NamedSharding(mesh, P())
    twin = spec.plugin("builders", cell.config["builder"]).build(
        cell.config, None, False)
    shapes = jax.eval_shape(twin.model.init, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
        {k: v for k, v in shapes.items() if k != "embedding"})
    world = mesh.devices.size
    shardings = emb.param_shardings()

    def stacked(plans, kind):
        return [jax.ShapeDtypeStruct((world, max(p.rows_max, 1), p.width),
                                     jnp.float32, sharding=shardings[kind][i])
                for i, p in enumerate(plans)]

    params["embedding"] = {"dp": [], "tp": stacked(emb.plan.tp_buckets, "tp"),
                           "row": stacked(emb.plan.row_tables, "row")}
    return params


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from distributed_embeddings_tpu.ops import pallas_tiled
    from distributed_embeddings_tpu.parallel.mesh import create_mesh

    # a compile for a described device cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    devices = list(topo.devices)[:cell.chips]
    jax.default_backend = lambda: "tpu"
    if hasattr(pallas_tiled, "_BACKEND_INTERPRET"):
        pallas_tiled._BACKEND_INTERPRET = None

    mesh = create_mesh(devices) if cell.chips > 1 else None
    built = spec.plugin("builders", cell.config["builder"]).build(
        cell.config, mesh, False)
    init_fn, step_fn = built.make_step()
    if mesh is None:
        one = SingleDeviceSharding(devices[0])

        def place(tree):
            return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=one), tree)

        params = place(jax.eval_shape(built.model.init,
                                      jax.random.PRNGKey(0)))
        opt_state = place(jax.eval_shape(init_fn, params))
        batch_sharding = one
    else:
        batch_sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
        params = mesh_param_shapes(cell, built, mesh)
        # a stacked leaf of the sparse state is sharded like its bucket
        opt_state = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=batch_sharding if s.ndim == 3
                else NamedSharding(mesh, P())),
            jax.eval_shape(init_fn, params))

    # one batch of the cell's generator, for its shapes and dtypes alone
    inputs, cats, labels = spec.plugin(
        "generators", cell.traffic["generator"]).generate(
            dict(cell.traffic, num_batches=1),
            [(built.tables[t][0], h)
             for t, h in zip(built.table_map, built.hotness)],
            built.global_batch, built.num_numerical, built.numerical_scale,
            0)[0]
    inputs, cats, labels = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=batch_sharding),
        (inputs, built.shape_ids(cats), labels))
    with mesh or Mesh(devices[:1], ("one",)):
        compiled = step_fn.lower(params, opt_state, inputs, cats,
                                 labels).compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    counts = {name: len(re.findall(
        rf"= [^=\n]*\b{re.escape(name)}(?:-start)?\(", text))
        for name in COUNTED}
    gib = 2.0 ** 30
    print(json.dumps({
        "workload": cell.name, "topology": args.topology,
        "devices": len(devices),
        "per_device_GiB": {
            "arguments": m.argument_size_in_bytes / gib,
            "outputs": m.output_size_in_bytes / gib,
            "aliased": m.alias_size_in_bytes / gib,
            "temporaries": m.temp_size_in_bytes / gib,
            "live": (m.argument_size_in_bytes + m.output_size_in_bytes
                     + m.temp_size_in_bytes - m.alias_size_in_bytes) / gib},
        "hlo_ops": {k: v for k, v in counts.items() if v},
        "tpu_custom_call": "tpu_custom_call" in text}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
