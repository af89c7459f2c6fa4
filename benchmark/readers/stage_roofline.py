"""The least time the chip's matrix unit could take for the products of one
stage scope (``params["flops"]``, a function of ``harness/stage_flops.py``
over the configuration's shapes, over the published bf16 peak) over the
device time the trace charges to that scope (``params["scope"]``, both
passes; ``readers/stage_ms.py``), in percent. None where the trace holds no
such scope: a CPU rehearsal, or a program from before it."""

from benchmark.harness import roofline, stage_flops
from benchmark.readers import stage_ms


def read(ctx, params):
    took_ms = stage_ms.read(ctx, {"scope": params["scope"], "pass": "any"})
    if not took_ms:
        return None
    flops = getattr(stage_flops, params["flops"])(
        ctx.cell.config, ctx.built.global_batch / ctx.cell.chips)
    peak = roofline.chip_peaks(ctx.device_kind)["bf16_tflop_per_s"] * 1e12
    least_ms = 1e3 * flops / peak
    ctx.notes.append(f"{params['scope']} roofline: {flops:.4g} flops a step, "
                     f"at least {least_ms:.4f} ms, took {took_ms:.4f} ms")
    return 100.0 * least_ms / took_ms
