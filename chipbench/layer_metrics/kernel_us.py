"""Device time of the library's own kernels per collective: seconds of
the ops on rank 0's device whose name carries ``mv2t_`` (every
``pallas_call`` of ops/ is named so), inside the traced sub-window, over
the collectives completed in it. The XLA ops around the kernel (staging
copies, relayouts) keep XLA's names and are left out:
``device_busy_us`` less this is what staging costs the device."""

from . import phase

NAME = "kernel_us"


def compute(ctx):
    ops = phase.kernel_ops(ctx)
    if not ops or ctx.traced_calls <= 0:
        return None
    return sum(e - s for s, e in ops) / ctx.traced_calls * 1e6
