"""Seconds per FL round: the window's wall time, ending in
``block_until_ready``, over the rounds it completed (evaluation at the
cell's cadence included)."""


def read(ctx):
    return ctx["window_s"] / ctx["window_rounds"]
