"""Median time rank 0 spends inside the program's own ``dev_<coll>``
span (coll/device.py:_run): two barrier waits, staging, dispatch, and on
the slot channel the leader's wait for the device. Read from the
program's recorder (MV2T_TRACE=1), as far as its ring holds the window."""

import statistics

from ..context import paired_spans

NAME = "rendezvous_span_us"


def compute(ctx):
    lo, hi = ctx.window_mono
    spans = paired_spans(ctx.spans.get(0, []), "device",
                         f"dev_{ctx.collective.NAME}")
    took = [e - b for b, e in spans if b >= lo and e <= hi]
    if not took:
        return None
    return statistics.median(took) * 1e6
