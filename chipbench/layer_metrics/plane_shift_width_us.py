"""How exactly the device plane can be laid against the host plane: the
width of the interval of shifts of rank 0's device plane under which
every program run of the traced sub-window starts after rank 0's launch
began to enqueue it and ends before the runtime saw it done (the start
of its completion event). Both bounds are the runtime's stamps on the
host plane (``hostplane``), and a run on the device plane is paired
with its enqueue by the flow id both carry (``xprof.plane_shift``), not
by counting. ``rounds.shift_bounds``, which brackets an op by rank 0's
recorder stamps and its return from the wait, reads 230-730 us wide.
``None`` where the trace pairs nothing (a client that writes no enqueue
or completion event) or no shift fits."""

from . import hostplane

NAME = "plane_shift_width_us"


def fit(ctx):
    """``(low, high)`` in seconds, or ``None``."""
    tb = hostplane.tables(ctx)
    if tb is None:
        return None
    dev = ctx.rank0_device()
    calls = {seq: call for seq, call in tb.calls.get(0, {}).items()
             if dev.lo <= call.begin <= dev.hi}
    return hostplane.xprof.plane_shift(calls, tb.runs(dev.ordinal),
                                       dev.ordinal)


def compute(ctx):
    got = fit(ctx)
    return None if got is None else (got[1] - got[0]) * 1e6
