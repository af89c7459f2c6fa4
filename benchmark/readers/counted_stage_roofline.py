"""`stage_roofline.py` over the counts of the module the metric's own file
names: the least time the chip's matrix unit could take for a stage scope's
products (``params["flops"]``, a function of
``benchmark/harness/<params["counts"]>.py`` over the configuration's shapes,
over the published bf16 peak) over the device time the trace charges to the
scope (``params["scope"]``, both passes), in percent. None where the trace
holds no such scope: a CPU rehearsal, or a program from before it."""

from benchmark.harness import roofline, spec
from benchmark.readers import stage_ms


def read(ctx, params):
    took_ms = stage_ms.read(ctx, {"scope": params["scope"], "pass": "any"})
    if not took_ms:
        return None
    flops = getattr(spec.plugin("harness", params["counts"]), params["flops"])(
        ctx.cell.config, ctx.built.global_batch / ctx.cell.chips)
    peak = roofline.chip_peaks(ctx.device_kind)["bf16_tflop_per_s"] * 1e12
    least_ms = 1e3 * flops / peak
    ctx.notes.append(f"{params['scope']} roofline ({params['counts']}): "
                     f"{flops:.4g} flops a step, at least {least_ms:.4f} ms, "
                     f"took {took_ms:.4f} ms")
    return 100.0 * least_ms / took_ms
