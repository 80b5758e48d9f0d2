"""Share of rank 0's device receives in the window whose message had
come before the receive was posted (the ``unexpected`` arg on the E of
``dev_recv``): such a message waits in the matcher's unexpected queue
and is delivered by the posting call itself."""

from . import phase

NAME = "p2p_unexpected_pct"


def compute(ctx):
    if not phase.chip_traced(ctx):
        return None
    lo, hi = ctx.window_mono
    flags = [bool(args.get("unexpected")) for b, e, args in
             phase.closed(ctx.spans.get(0, []), "dev_recv")
             if b >= lo and e <= hi]
    if not flags:
        return None
    return 100.0 * sum(flags) / len(flags)
