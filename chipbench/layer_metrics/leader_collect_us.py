"""The leader turning the program's output into one result per rank:
rank 0's ``dev_collect`` span (the walk over ``addressable_shards``,
slicing)."""

from . import phase

NAME = "leader_collect_us"


def compute(ctx):
    return phase.span_us(ctx, "dev_collect")
