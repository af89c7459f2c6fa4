"""The least time the chip could take for its share of one step
(``harness.roofline``: looked-up rows over peak HBM bandwidth, or the dense
model's matmul flops over peak bf16, whichever is larger) over the device
time the step took, in percent."""

from benchmark.harness import roofline
from benchmark.readers import step_device_ms


def least_step(ctx):
    """(seconds, which bound) for one chip's share of a step."""
    b = ctx.built
    widths = [b.tables[t][1] for t in b.table_map]
    emb_bytes = roofline.embedding_bytes_per_sample(
        widths, b.hotness, b.optimizer["kind"])
    return roofline.least_step_s(
        roofline.chip_peaks(ctx.device_kind), b.global_batch / ctx.cell.chips,
        emb_bytes, b.mlp_flops_per_sample)


def read(ctx, params):
    took_ms = step_device_ms.read(ctx, params)
    if not took_ms:
        return None
    least_s, bound = least_step(ctx)
    ctx.notes.append(f"step_roofline: the least step is {least_s * 1e3:.4f} "
                     f"ms, bound by {bound}")
    return 100.0 * least_s * 1e3 / took_ms
