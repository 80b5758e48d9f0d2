"""What the phase-span readers share: the program's phase spans inside
``dev_<coll>`` (coll/device.py: ``dev_arrive``, ``dev_stage``,
``dev_dispatch``, ``dev_device_wait``, ``dev_collect``, ``dev_release``,
then ``dev_deliver``), each a B/E pair of the ``device`` lane whose args
carry the collective's ``seq``. Not a metric: no entry of BENCHMARK.json
names it. A program that records no such span (the parent of the PR that
added them) gives every reader here nothing to read, and it returns
``None``. So does a run that traced no device (``chip_traced``): the
phases are read beside the device's timeline, whose idle time they
divide, and a CPU rehearsal's host times are not filed under a metric's
name (tests/test_rehearsal.py holds the rehearsal's line to that)."""

import statistics

LANE = "device"
KERNEL_TOKEN = "mv2t_"      # ops/_compat.kernel_name: every pallas_call


def chip_traced(ctx) -> bool:
    """The run traced rank 0's device."""
    return ctx.rank0_device() is not None


def closed(events, name):
    """``(begin, end, args of the E)`` of every closed B/E pair of the
    device lane's ``name`` in one rank's recorder events; a pair whose
    other half fell off the ring is dropped."""
    out, open_at = [], None
    for t, lay, nam, ph, args in events:
        if lay != LANE or nam != name:
            continue
        if ph == "B":
            open_at = t
        elif ph == "E" and open_at is not None:
            out.append((open_at, t, args or {}))
            open_at = None
    return out


def median_us(ctx, values):
    """Median of seconds, in microseconds; ``None`` of nothing, and of
    a run that traced no device."""
    if not values or not chip_traced(ctx):
        return None
    return statistics.median(values) * 1e6


def span_us(ctx, name, keep=lambda args: True):
    """Median length of rank 0's ``name`` spans that lie inside the
    measured window, as far as the recorder's ring holds it."""
    lo, hi = ctx.window_mono
    return median_us(ctx, [e - b for b, e, args in
                           closed(ctx.spans.get(0, []), name)
                           if b >= lo and e <= hi and keep(args)])


def kernel_ops(ctx):
    """``(start, end)`` on the trace's axis, clipped to the traced
    sub-window, of rank 0's device's ops whose name carries the token
    every kernel of ops/ is named with."""
    dev = ctx.rank0_device()
    if dev is None:
        return []
    return [(max(s, dev.lo), min(e, dev.hi)) for name, s, e in dev.ops
            if KERNEL_TOKEN in name and min(e, dev.hi) > max(s, dev.lo)]
