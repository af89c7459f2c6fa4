"""The least time the chip's matrix unit could take for a block-diffusion
model's attention in one step (``harness/blockdiff_attn_flops.py`` over the
configuration's shapes and the pairs the mask keeps, over the published
bf16 peak) over the device time the trace charges to stage ``attn``, both
passes, in percent.

The pairs are the mean over the batches the cell's generator made in this
process (`benchmark/generators/block_diffusion.PAIRS`): a traced run takes
``trace_steps`` steps, one of each pre-staged batch. None where the trace
holds no ``attn`` scope (a CPU rehearsal, a program from before it) or no
batch was made here."""

from benchmark.generators import block_diffusion
from benchmark.harness import blockdiff_attn_flops, roofline
from benchmark.readers import stage_ms


def read(ctx, params):
    took_ms = stage_ms.read(ctx, {"scope": params["scope"], "pass": "any"})
    if not took_ms or not block_diffusion.PAIRS:
        return None
    pairs = sum(block_diffusion.PAIRS) / len(block_diffusion.PAIRS)
    tokens = ctx.built.global_batch / ctx.cell.chips
    flops = blockdiff_attn_flops.attn_flops_per_step(ctx.cell.config, tokens,
                                                     pairs)
    mlp = ctx.built.mlp_flops_per_sample * tokens
    peak = roofline.chip_peaks(ctx.device_kind)["bf16_tflop_per_s"] * 1e12
    least_ms = 1e3 * flops / peak
    ctx.notes.append(
        f"blockdiff_attn_roofline: {pairs:.8g} visible pairs of one "
        f"stream's queries a layer (mean of {len(block_diffusion.PAIRS)} "
        f"batches), {flops:.6g} flops a step, "
        f"at least {least_ms:.4f} ms, took {took_ms:.4f} ms; step_roofline "
        f"counts {ctx.built.mlp_flops_per_sample} flops a clean token, "
        f"{mlp:.6g} a step")
    return 100.0 * least_ms / took_ms
