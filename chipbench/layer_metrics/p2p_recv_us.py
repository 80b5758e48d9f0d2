"""From posting a receive on the device point-to-point lane to its
completion: rank 0's ``dev_recv`` span, the partner's lateness
included."""

from . import phase

NAME = "p2p_recv_us"


def compute(ctx):
    return phase.span_us(ctx, "dev_recv")
