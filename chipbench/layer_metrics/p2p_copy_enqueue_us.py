"""The host's enqueue of the receiver-owned copy of one message: rank
0's ``dev_p2p_copy`` span (the launch of the copy program, not the
copy's time on the device, which ``device_busy_us`` holds)."""

from . import phase

NAME = "p2p_copy_enqueue_us"


def compute(ctx):
    return phase.span_us(ctx, "dev_p2p_copy")
