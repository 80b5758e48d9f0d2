"""Each rank's own round, the loop's period under the recorder: on every
rank, from one ``mpi:<coll>`` B to the next of the same rank (successive
calls by ``seq``), both inside the measured window; the median of all
ranks' rounds pooled. ``busbw_GBps`` goes by this, not by ``lat_us_p50``
(the slowest rank of an iteration); untraced the ``window:`` line's
window over iterations says the same round without the recorder."""

from . import phase, rounds

NAME = "rank_round_us"


def compute(ctx):
    return phase.median_us(ctx, [
        t for a, b in rounds.successive(ctx, set(ctx.spans))
        if (t := rounds.took(a, ("mpi", "B"), b, ("mpi", "B"),
                             ctx.window_mono)) is not None])
