"""The library's lines in a rank's slice: for every rank but the leader
and every ``seq`` k, ``dev_release`` E of k to ``mpi:<coll>`` E of k (the
span's E, ``_hand_back`` and ``_deliver``, the way out through
``comm.<coll>``) plus ``mpi:<coll>`` B of k + 1 to ``dev_arrive`` E of
k + 1 (``comm.<coll>``'s own lines, ``_select_transport``, ``_as_local``,
``_run``'s preamble, the deposit and the count-in). Median over all such
ranks' slices pooled. With ``slice_caller_us`` it is the slice, of which
``ranks - 1`` in series and ``leader_wake_us`` are rank 0's
``arrive_wait_us`` on one chip."""

from . import phase, rounds

NAME = "slice_library_us"


def compute(ctx):
    w = ctx.window_mono
    got = []
    for a, b in rounds.successive(ctx, rounds.others(ctx)):
        out = rounds.took(a, ("dev_release", "E"), a, ("mpi", "E"), w)
        back = rounds.took(b, ("mpi", "B"), b, ("dev_arrive", "E"), w)
        if out is not None and back is not None:
            got.append(out + back)
    return phase.median_us(ctx, got)
