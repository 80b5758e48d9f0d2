"""How long the leader takes to wake once the last rank is in: per
``seq``, rank 0's ``dev_arrive`` E less the latest ``dev_arrive`` E of
any other rank; 0 where rank 0 came last itself (its ``dev_arrive`` B
lies after every other rank's E: it waited for nobody) and where its
stamp beat the last rank's. A ``seq`` is dropped unless the rings hold
every rank's. What a gate that needs no wake-up (the last rank to
arrive dispatching, ROADMAP A1(c)(iii) / C4) can win at most."""

from . import phase, rounds

NAME = "leader_wake_us"


def compute(ctx):
    lo, hi = ctx.window_mono
    by_seq = {}             # seq -> {rank: its call}
    for rank, got in rounds.rank_calls(ctx).items():
        for call in got:
            if ("dev_arrive", "E") in call:
                by_seq.setdefault(call["seq"], {})[rank] = call
    woke = []
    for ranks in by_seq.values():
        mine = ranks.get(0)
        if len(ranks) != len(ctx.spans) or len(ranks) < 2 \
                or ("dev_arrive", "B") not in mine:
            continue
        came, up = mine[("dev_arrive", "B")], mine[("dev_arrive", "E")]
        last = max(c[("dev_arrive", "E")] for r, c in ranks.items() if r)
        if came >= lo and max(up, last) <= hi:
            woke.append(0.0 if came > last else max(0.0, up - last))
    return phase.median_us(ctx, woke)
