"""From the MPI entry to the rendezvous: rank 0's ``mpi:<coll>`` B to the
``dev_<coll>`` B that follows it (transport choice, the rank's shard as a
flat array, the tier count). Both events predate the phase spans."""

from . import phase

NAME = "entry_us"


def compute(ctx):
    lo, hi = ctx.window_mono
    coll = ctx.collective.NAME
    took, entered = [], None
    for t, layer, name, ph, _args in ctx.spans.get(0, []):
        if ph != "B":
            continue
        if (layer, name) == ("mpi", coll):
            entered = t
        elif (layer, name) == (phase.LANE, f"dev_{coll}") \
                and entered is not None:
            if entered >= lo and t <= hi:
                took.append(t - entered)
            entered = None
    return phase.median_us(ctx, took)
