"""The library's share of one point-to-point call: rank 0's
``mpi:<call>`` B to E (profile.py's interceptor around ``comm.sendrecv``):
posting the receive, the send side's work, and the wait for the
partner's message; the caller's wait for the device comes after it.
``None`` of a program that records no ``dev_send`` span: it has no
device point-to-point lane, and what its ``mpi:`` span times is
another path."""

from ..context import paired_spans
from . import phase

NAME = "p2p_call_us"


def compute(ctx):
    events = ctx.spans.get(0, [])
    if not phase.closed(events, "dev_send"):
        return None
    lo, hi = ctx.window_mono
    return phase.median_us(ctx, [
        e - b for b, e in paired_spans(events, "mpi", ctx.collective.NAME)
        if b >= lo and e <= hi])
