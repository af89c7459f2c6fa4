"""Device-busy ms per step: the union of the intervals in which an
operation ran, over the traced steps, the mean over the traced chips."""


def read(ctx, params):
    if not ctx.chips:
        return None
    return (sum(chip.busy_ns for chip in ctx.chips) / len(ctx.chips)
            / ctx.steps * 1e-6)
