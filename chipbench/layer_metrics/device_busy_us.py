"""Device time one collective costs: the union of the intervals in which
any operation ran on rank 0's device during the traced sub-window, over
the collectives completed in it. Name-free: whatever the device ran for
the call counts, kernels and the XLA ops around them alike."""

NAME = "device_busy_us"


def compute(ctx):
    dev = ctx.rank0_device()
    if dev is None or ctx.traced_calls <= 0 or dev.busy_s <= 0:
        return None
    return dev.busy_s / ctx.traced_calls * 1e6
