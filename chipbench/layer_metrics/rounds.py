"""What the round readers share: one rank's recorder events cut into its
``comm.<coll>`` calls, so that a reader can join a call's ``mpi`` lane
stamps (which carry no ``seq``) with its ``device`` lane stamps (which
do), a call with the next one of the same rank, and one ``seq`` across
ranks. Not a metric: no entry of BENCHMARK.json names it.

A rank records, per blocking device collective (coll/device.py, trace/):
``mpi:<coll>`` B, ``dev_<coll>`` B (``seq``, ``as_is``), ``dev_arrive``
B/E, on rank 0 the leader's ``dev_stage``, ``dev_dispatch``,
``dev_device_wait`` (slot channel), ``dev_collect``, then ``dev_release``
B/E, ``dev_<coll>`` E, ``dev_deliver`` B/E, ``mpi:<coll>`` E. A *slice*
is what a rank runs between being let go at the gate (``dev_release`` E)
and being counted in again (the next call's ``dev_arrive`` E).

Every reader here returns ``None`` on a run that traced no device
(``phase.median_us``), and leaves out a call any stamp of which fell off
the ring or lies outside the measured window.
"""

import bisect

from .. import xplane
from . import phase

SCAN_S = 0.005          # shift_bounds looks this far either way


def calls(events, coll):
    """One dict per ``comm.<coll>`` call of one rank, in ring order:
    the stamp of each of its events under ``(name, ph)`` (the ``mpi``
    lane's two under ``("mpi", "B")`` and ``("mpi", "E")``), its ``seq``
    and the ``as_is`` of its ``dev_<coll>`` B. A call cut by the ring's
    start lacks what fell off."""
    out, cur = [], None
    dev_coll = f"dev_{coll}"
    for t, lane, name, ph, args in events:
        if lane == "mpi":
            if name != coll:
                continue
            if ph == "B":
                cur = {("mpi", "B"): t}
                out.append(cur)
            elif ph == "E" and cur is not None:
                cur[("mpi", "E")] = t
                cur = None
        elif lane == phase.LANE and ph in ("B", "E") and args \
                and args.get("coll") == coll and "seq" in args:
            if cur is None or cur.get("seq", args["seq"]) != args["seq"]:
                cur = {}                # its mpi B fell off the ring
                out.append(cur)
            cur["seq"] = args["seq"]
            cur[(name, ph)] = t
            if name == dev_coll and ph == "B":
                cur["as_is"] = args.get("as_is")
    return out


def rank_calls(ctx):
    """``{rank: calls}`` of the cell's collective."""
    return {rank: calls(events, ctx.collective.NAME)
            for rank, events in ctx.spans.items()}


def took(a, begin, b, end, window):
    """Seconds from stamp ``begin`` of call ``a`` to stamp ``end`` of
    call ``b`` (the same or the next), or ``None`` unless both are held
    and lie inside the measured window."""
    t0, t1 = a.get(begin), b.get(end)
    if t0 is None or t1 is None or t0 < window[0] or t1 > window[1]:
        return None
    return t1 - t0


def successive(ctx, ranks):
    """``(call k, call k + 1)`` of every rank in ``ranks``: neighbours in
    the ring whose ``seq`` differ by one."""
    for rank, got in rank_calls(ctx).items():
        if rank not in ranks:
            continue
        for a, b in zip(got, got[1:]):
            if "seq" in a and b.get("seq") == a["seq"] + 1:
                yield a, b


def others(ctx):
    """Every rank but the leader."""
    return {r for r in ctx.spans if r != 0}


def known_ready(ctx):
    """``(dev_dispatch B, dev_dispatch E, result known ready)`` of rank
    0's calls in the traced sub-window, on ``time.monotonic``: one per
    entry of ``ctx.caller_waits`` (rank 0's traced iterations, from
    ``comm.<coll>`` returning to ``block_until_ready`` returning), joined
    to the last call dispatched before the wait began and after the
    previous wait ended. Known ready is the call's ``dev_device_wait`` E
    where it has one (the slot leader waits itself), else the end of the
    caller's wait."""
    mine = [c for c in calls(ctx.spans.get(0, []), ctx.collective.NAME)
            if ("dev_dispatch", "B") in c and ("dev_dispatch", "E") in c]
    ends = [c[("dev_dispatch", "E")] for c in mine]
    out, before = [], float("-inf")
    for t1, t2 in ctx.caller_waits:
        i = bisect.bisect_right(ends, t1) - 1
        if i >= 0 and ends[i] > before:
            c = mine[i]
            out.append((c[("dev_dispatch", "B")], ends[i],
                        c.get(("dev_device_wait", "E"), t2)))
        before = t2
    return out


def shift_bounds(ctx):
    """The two planes: the shifts of rank 0's device plane, in seconds
    and within ``+-SCAN_S``, under which every busy interval of the
    traced sub-window lies inside some host interval [``dev_dispatch``
    B, result known ready] put on the trace's axis by the harness's
    clock offset. A sorted list of ``(low, high)``: one interval that
    holds 0 says the planes agree to within its width; one that does
    not says how far the device plane sits off; several say the fit is
    ambiguous; none says no shift fits. No call is paired with an op (a
    shift by a whole call leaves the first or the last op outside every
    traced call, so it does not fit). A busy interval the sub-window's
    edge cut is left out. ``None`` where there is nothing to compare."""
    dev = ctx.rank0_device()
    if dev is None or ctx.clock_offset_s is None:
        return None
    host = sorted((b + ctx.clock_offset_s, r + ctx.clock_offset_s)
                  for b, _e, r in known_ready(ctx))
    busy = [(s, e) for s, e in dev.busy if s > dev.lo and e < dev.hi]
    if not host or not busy:
        return None
    readies = [r for _b, r in host]     # rank 0's calls follow one another
    allowed = [(-SCAN_S, SCAN_S)]
    for s, e in busy:
        fits = []       # the shifts that put (s, e) inside a host interval
        for hb, hr in host[bisect.bisect_left(readies, e - SCAN_S):]:
            if hb > s + SCAN_S:
                break
            low, high = max(hb - s, -SCAN_S), min(hr - e, SCAN_S)
            if low <= high:
                fits.append((low, high))
        allowed = [part for low, high in xplane.union(fits)
                   for part in xplane.clip(allowed, low, high)]
        if not allowed:
            break
    return allowed
