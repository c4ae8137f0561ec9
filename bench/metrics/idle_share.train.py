"""Share of the traced training window in which the device ran no
operation: 100 × (1 − busy / window), busy being the union of the
device's op intervals (``bench/lib/trace.py``)."""


def read(ctx):
    red = ctx.reduced
    if red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
