"""Share of the exchange's time (a collective running on the core, or in
flight beside it) during which the core ran nothing else, in percent, over
the traced chips together."""


def read(ctx, params):
    total = sum(chip.exchange_ns for chip in ctx.chips)
    if not total:
        return None
    return 100.0 * sum(chip.exposed_ns for chip in ctx.chips) / total
