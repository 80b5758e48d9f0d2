"""Share of calls that ran on a derived communicator's channel: of rank
0's ``dev_<coll>`` B events inside the measured window, the per cent
whose ``derived`` is true (``coll/device.py`` ``_run``: the channel the
call ran on was made by ``derive`` for a communicator split, duplicated
or created from a device-bound one). A cell that calls its collective on
such a communicator reads 100; anything less is a call that went to the
world's channel, and a call that took the host arm leaves no B at all
(the level pvars and ``dev_coll_fallback_host_comm`` then fail
``correct``). ``None`` where no call ran on a derived channel (a cell
on the world; a program whose B does not say ``derived``) and of a run
that traced no device."""

from . import phase

NAME = "derived_calls_pct"


def compute(ctx):
    if not phase.chip_traced(ctx):
        return None
    lo, hi = ctx.window_mono
    name = f"dev_{ctx.collective.NAME}"
    said = [bool(args["derived"])
            for t, lane, nam, ph, args in ctx.spans.get(0, [])
            if (lane, nam, ph) == (phase.LANE, name, "B") and args
            and "derived" in args and lo <= t <= hi]
    return 100.0 * sum(said) / len(said) if any(said) else None
