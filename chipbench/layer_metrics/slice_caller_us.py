"""The caller's own lines in a rank's slice: for every rank but the
leader, ``mpi:<coll>`` E of ``seq`` k to ``mpi:<coll>`` B of k + 1: what
the code that called the library runs between two calls (in these cells
the harness's loop and its ``block_until_ready``: on one chip a few tens
of microseconds of Python, on four chips the caller's wait for the
device). Median over all such ranks pooled. Nothing of the library's
lies in it; ``slice_library_us`` is the rest of the slice."""

from . import phase, rounds

NAME = "slice_caller_us"


def compute(ctx):
    return phase.median_us(ctx, [
        t for a, b in rounds.successive(ctx, rounds.others(ctx))
        if (t := rounds.took(a, ("mpi", "E"), b, ("mpi", "B"),
                             ctx.window_mono)) is not None])
