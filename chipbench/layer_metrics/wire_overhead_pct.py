"""What the kernel puts on the wire beyond what the exchange needs:
``100 x (wire bytes per call / least bytes - 1)``. The wire bytes are
the program's own reckoning of what its alltoall kernel sends over ICI
per rank per call, tile padding included and the local block excluded:
the ``wire_bytes`` arg of a ``device``-lane event that carries the
call's ``seq`` (coll/device.py ``_note_tier``; the pvar
``dev_a2a_wire_bytes`` is the same count summed). The least bytes are
the collective's own rule (collectives/<name>.py:least_bytes). 0 when
every block is a whole number of tiles; above 0 when blocks are padded
(and, one day, when a variable-count exchange pads to the step's
maximum). Median over rank 0's calls inside the measured window, as far
as the recorder's ring holds it; ``None`` where no event carries the
count (a program that does not record it, or a call that took another
lowering)."""

import statistics

NAME = "wire_overhead_pct"
LANE = "device"


def compute(ctx):
    lo, hi = ctx.window_mono
    wire = [args["wire_bytes"] for t, lane, _name, _ph, args
            in ctx.spans.get(0, [])
            if lane == LANE and args and "wire_bytes" in args
            and "seq" in args and lo <= t <= hi]
    if not wire:
        return None
    least, _peak = ctx.collective.least_bytes(
        ctx.config["expect"]["least_bytes"], ctx.ranks, ctx.bytes_per_rank)
    return 100.0 * (statistics.median(wire) / least - 1.0)
