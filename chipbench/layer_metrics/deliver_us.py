"""Handing the result back: rank 0's ``dev_deliver`` span
(``coll/device.py:_deliver``: a flat view for a device-resident caller,
a copy to the host for a host ``recvbuf``)."""

from . import phase

NAME = "deliver_us"


def compute(ctx):
    return phase.span_us(ctx, "dev_deliver")
