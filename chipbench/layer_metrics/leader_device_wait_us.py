"""The slot leader's wait for the device: rank 0's ``dev_device_wait``
span (``HBMSlotChannel._leader``'s ``block_until_ready``, the one leader
that blocks; the mesh channel records no such span)."""

from . import phase

NAME = "leader_device_wait_us"


def compute(ctx):
    return phase.span_us(ctx, "dev_device_wait")
