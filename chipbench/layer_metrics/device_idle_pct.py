"""Share of the traced sub-window in which rank 0's device ran nothing."""

NAME = "device_idle_pct"


def compute(ctx):
    dev = ctx.rank0_device()
    if dev is None or dev.window_s <= 0 or dev.busy_s <= 0:
        return None
    return 100.0 * (1.0 - dev.busy_s / dev.window_s)
