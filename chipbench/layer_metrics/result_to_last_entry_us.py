"""The slices behind every result: per ``seq`` k, from call k's result
first seen (``hostplane``: on a TPU the start of the runtime's
completion event, from which it wakes the waiting threads; else the
earliest wait end over every rank's line) to the latest ``mpi:<coll>``
B of call k + 1 over all ranks (the last rank is back in the library),
the recorder's stamp put on the trace's axis by the tie. In a closed loop no rank
starts call k + 1 before it has call k's result, so this is the ranks
coming back through the caller one at a time under the interpreter
lock: ROADMAP A1(c)(v)'s "eight times about 60 us". A ``seq`` counts
only where every rank's next call is held."""

from . import hostplane, phase

NAME = "result_to_last_entry_us"


def compute(ctx):
    tb = hostplane.tables(ctx)
    if tb is None:
        return None
    took = []
    for seq in tb.calls.get(0, {}):
        seen = tb.seen(seq)
        entries = [c[("mpi", "B")] for mine in tb.spans.values()
                   if (c := mine.get(seq + 1)) is not None
                   and ("mpi", "B") in c]
        if seen is None or len(entries) != len(ctx.spans) \
                or not tb.inside(*entries):
            continue
        took.append(tb.at(max(entries)) - seen)
    return phase.median_us(ctx, took)
