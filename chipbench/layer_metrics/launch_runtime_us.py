"""The runtime's own part of the leader's dispatch: ``leader_dispatch_us``
less what of the ``dev_dispatch`` span lies outside the runtime's launch
events. On rank 0's line of the trace's host plane the launch events (the
jitted call, entry to return, stamped by jaxlib in C++) inside the call's
``dev_dispatch`` span, the recorder's two stamps put on the trace's axis
by the tie (``hostplane``); one event a call where the call is one
program, as in every cell. Per call of the traced sub-window the span
less its launch events is Python's (the program's lookup, the frame, the
arguments; ROADMAP A1(b)); its median is taken from
``leader_dispatch_us``, the whole window's median, so the two readings
are of one set of calls: under the profiler a four-chip launch runs
about 100 us longer than in the rest of the window, Python's part does
not. A call whose ``dev_dispatch`` E says ``built`` is left out."""

import statistics

from . import hostplane, leader_dispatch_us

NAME = "launch_runtime_us"


def compute(ctx):
    tb = hostplane.tables(ctx)
    dispatch = leader_dispatch_us.compute(ctx)
    if tb is None or dispatch is None:
        return None
    outside = [(e - b) - sum(t - s for s, t in events)
               for _seq, (b, e), events in tb.launches()]
    if not outside:
        return None
    return dispatch - statistics.median(outside) * 1e6
