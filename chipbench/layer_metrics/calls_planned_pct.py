"""Share of calls that ran on a filed call plan: of all ranks'
``dev_<coll>`` B events inside the measured window, the per cent whose
``planned`` is true (the rank had decided a call of this signature
before, under the cvars as they stand, and ran on what it filed then:
``coll/device.py`` ``plan_of``). Every cell is a closed loop of one
signature, filed by the warm-up's first call, so it reads 100 in every
cell; under 100 says that calls are deciding their transport and tier
again. ``None`` where no B says ``planned`` (a program without call
plans) and of a run that traced no device."""

from . import phase

NAME = "calls_planned_pct"


def compute(ctx):
    if not phase.chip_traced(ctx):
        return None
    lo, hi = ctx.window_mono
    name = f"dev_{ctx.collective.NAME}"
    said = [bool(args["planned"]) for events in ctx.spans.values()
            for t, lane, nam, ph, args in events
            if (lane, nam, ph) == (phase.LANE, name, "B") and args
            and "planned" in args and lo <= t <= hi]
    return 100.0 * sum(said) / len(said) if said else None
