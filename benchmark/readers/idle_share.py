"""Share of the traced window in which no operation ran, on the chip that
idled most, in percent. The window runs from the loop's first dispatch to
the end of its last fetch."""


def read(ctx, params):
    if not ctx.chips:
        return None
    return 100.0 * max(1.0 - chip.busy_ns / chip.window_ns
                       for chip in ctx.chips)
