"""From the leader being done to every rank being out: rank 0's
``dev_release`` B to the latest ``dev_<coll>`` E of any rank, joined by
the collective's ``seq`` (the second barrier and the wake-up of the rank
threads). A ``seq`` is dropped unless the ring still holds rank 0's
begin and every rank's end."""

from . import phase

NAME = "release_us"


def compute(ctx):
    lo, hi = ctx.window_mono
    coll = f"dev_{ctx.collective.NAME}"
    begun = {args["seq"]: t for t, lay, nam, ph, args in ctx.spans.get(0, [])
             if (lay, nam, ph) == (phase.LANE, "dev_release", "B")
             and args and "seq" in args}
    ended = {}           # seq -> the E of each rank that has one
    for events in ctx.spans.values():
        for t, lay, nam, ph, args in events:
            if (lay, nam, ph) == (phase.LANE, coll, "E") and args \
                    and args.get("seq") in begun:
                ended.setdefault(args["seq"], []).append(t)
    return phase.median_us(
        ctx, [max(ends) - begun[seq] for seq, ends in ended.items()
              if len(ends) == len(ctx.spans) and begun[seq] >= lo
              and max(ends) <= hi])
