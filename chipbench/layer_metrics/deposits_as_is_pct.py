"""Share of deposits that are the caller's own array: of all ranks'
``dev_<coll>`` B events inside the measured window, the per cent whose
``as_is`` is true (``_as_local`` handed back the flat, whole device
array it was given, by identity). 100 in every cell: each caller hands
over such an array; under 100 says the deposit is being made by jax's
``reshape`` and indexing again. ``None`` where no B says ``as_is`` (a
program that does not record it) and of a run that traced no device."""

from . import phase

NAME = "deposits_as_is_pct"


def compute(ctx):
    if not phase.chip_traced(ctx):
        return None
    lo, hi = ctx.window_mono
    name = f"dev_{ctx.collective.NAME}"
    said = [bool(args["as_is"]) for events in ctx.spans.values()
            for t, lane, nam, ph, args in events
            if (lane, nam, ph) == (phase.LANE, name, "B") and args
            and "as_is" in args and lo <= t <= hi]
    return 100.0 * sum(said) / len(said) if said else None
