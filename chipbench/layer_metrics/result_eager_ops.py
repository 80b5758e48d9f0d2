"""Eager device operations one collective issues after its program to
hand every rank its result: rank 0's ``parts`` (the ``dev_collect`` E:
arrays the leader cut out of the program's result, one eager op each)
plus the ``relaid`` of every rank's ``dev_deliver`` E of the same
``seq`` (1 where ``_deliver`` issued a reshape), joined as
``release_us`` joins. A count the program records, not a time: 0 where
the program's own outputs are handed out flat (every accepted cell),
2 x ranks where a slice and a reshape stand behind every rank's result.
Median over the collectives inside the measured window, as far as the
recorder's ring holds it; a ``seq`` is dropped unless the ring still
holds rank 0's ``dev_collect`` and every rank's ``dev_deliver``.
``None`` where no ``dev_collect`` E carries ``parts`` (a program that
does not record it), and of a run that traced no device, as the phase
times: the ops are read beside the device's timeline, where
``device_ops_per_call`` sees them run."""

import statistics

from . import phase

NAME = "result_eager_ops"


def compute(ctx):
    if not phase.chip_traced(ctx):
        return None
    lo, hi = ctx.window_mono
    parts = {args["seq"]: args["parts"] for b, e, args in
             phase.closed(ctx.spans.get(0, []), "dev_collect")
             if "parts" in args and "seq" in args and b >= lo and e <= hi}
    relaid = {}             # seq -> the relaid of each rank that has one
    for events in ctx.spans.values():
        for _b, e, args in phase.closed(events, "dev_deliver"):
            if args.get("seq") in parts and "relaid" in args and e <= hi:
                relaid.setdefault(args["seq"], []).append(args["relaid"])
    counts = [parts[seq] + sum(got) for seq, got in relaid.items()
              if len(got) == len(ctx.spans)]
    return statistics.median(counts) if counts else None
