"""``memory_stats()["peak_bytes_in_use"]`` after the traced window, on the
fullest chip, in GiB. It counts the arrays the process held at its fullest
moment, set-up included."""


def read(ctx, params):
    if not ctx.memory_peak_bytes:
        return None
    return ctx.memory_peak_bytes / 2 ** 30
