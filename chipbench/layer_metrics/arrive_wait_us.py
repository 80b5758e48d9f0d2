"""Rank 0's wait for the last rank to arrive: its ``dev_arrive`` span
(slot deposit to the rendezvous' first barrier returning)."""

from . import phase

NAME = "arrive_wait_us"


def compute(ctx):
    return phase.span_us(ctx, "dev_arrive")
