"""The send side of one message on the device point-to-point lane:
rank 0's ``dev_send`` span (pt2pt/protocol.py: the receiver-owned copy
enqueued, the envelope handed to the partner's engine)."""

from . import phase

NAME = "p2p_send_us"


def compute(ctx):
    return phase.span_us(ctx, "dev_send")
