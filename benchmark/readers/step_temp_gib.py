"""GiB of temporaries the compiled train step keeps beside its arguments:
``memory_analysis().temp_size_in_bytes`` of the program's own jitted step,
lowered through the handle ``step_fn.lower`` (the ``lower`` of the very
``jax.jit`` object the cell's loop dispatched to, with its donation) for the
shapes and shardings the cell ran, as ``benchmark/tools/describe_chip.py``
builds them. After the cell's own compile this one is a cache load. Per
device; arguments, outputs, aliased and live bytes go to a note.

None unless a chip was traced and this process's devices are of the traced
kind: a CPU's temporaries say nothing of the chip's. None too where the
step has no handle (a program from before it).
"""

import time
from contextlib import nullcontext


def step_shapes(ctx, init_fn):
    """(params, opt_state, numerical, cats, labels) as ShapeDtypeStructs
    placed as the cell places its arrays. On one chip they carry no
    sharding, like the arrays the loop passes, which no one committed to a
    device: a sharding on them is another program to the compile cache."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    built, mesh = ctx.built, ctx.built.mesh
    if mesh is None:
        batch_sharding = None
        params = jax.eval_shape(built.model.init, jax.random.PRNGKey(0))
        opt_state = jax.eval_shape(init_fn, params)
    else:
        from benchmark.tools.describe_chip import mesh_param_shapes

        batch_sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
        params = mesh_param_shapes(ctx.cell, built, mesh)
        # a stacked leaf of the sparse state is sharded like its bucket
        opt_state = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=batch_sharding if s.ndim == 3
                else NamedSharding(mesh, P())),
            jax.eval_shape(init_fn, params))

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=batch_sharding)

    batch = built.global_batch
    cats = [S((batch,) if built.ids_1d and h == 1 else (batch, h), jnp.int32)
            for h in built.hotness]
    return (params, opt_state, S((batch, built.num_numerical), jnp.float32),
            cats, S((batch, 1), jnp.float32))


def read(ctx, params):
    if not ctx.chips:
        return None
    import jax

    if jax.devices()[0].device_kind != ctx.device_kind:
        return None
    init_fn, step_fn = ctx.built.make_step()
    if not hasattr(step_fn, "lower"):
        print("TRACE step.temp_gib: the step has no `lower` handle",
              flush=True)
        return None
    shapes = step_shapes(ctx, init_fn)
    t0 = time.perf_counter()
    with ctx.built.mesh or nullcontext():
        compiled = step_fn.lower(*shapes).compile()
    took = time.perf_counter() - t0
    m, gib = compiled.memory_analysis(), 2.0 ** 30
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    ctx.notes.append(
        f"step.temp_gib: {step_fn.name} lowered through its handle and "
        f"compiled or loaded in {took:.1f} s; GiB per device: arguments "
        f"{m.argument_size_in_bytes / gib:.3f}, outputs "
        f"{m.output_size_in_bytes / gib:.3f}, aliased "
        f"{m.alias_size_in_bytes / gib:.3f}, temporaries "
        f"{m.temp_size_in_bytes / gib:.3f}, live {live / gib:.3f}")
    return m.temp_size_in_bytes / gib
