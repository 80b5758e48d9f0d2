"""The hand-over between rank threads at the gate: per ``seq``, between
the ``dev_release`` E of the rank whose ``turn`` is t and of the one
whose ``turn`` is t + 1 (the place in the line the leader let go, which
``_Gate.leave`` returns and the E carries; each rank lets the next go
before it does anything else). Median, every turn of every call inside
the measured window pooled. A program whose E says no ``turn`` (the
parent of the PR that added it) gives nothing to read. Read beside the
device's timeline only, as the phase readers (``phase.median_us``)."""

from . import phase

NAME = "gate_handover_us"


def compute(ctx):
    lo, hi = ctx.window_mono
    by_seq = {}             # seq -> {turn: its dev_release E}
    for rank, events in ctx.spans.items():
        for t, lane, name, ph, args in events:
            if lane == phase.LANE and name == "dev_release" and ph == "E" \
                    and args and "turn" in args and lo <= t <= hi:
                by_seq.setdefault(args["seq"], {})[args["turn"]] = t
    return phase.median_us(ctx, [t - turns[turn - 1]
                                 for turns in by_seq.values()
                                 for turn, t in turns.items()
                                 if turn - 1 in turns])
