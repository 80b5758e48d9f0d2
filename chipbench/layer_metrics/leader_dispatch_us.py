"""The leader's program call up to its return: rank 0's ``dev_dispatch``
span (program-cache lookup and enqueue). A call whose E says ``built``
made or loaded its program and is left out: that is set-up, not the
steady dispatch."""

from . import phase

NAME = "leader_dispatch_us"


def compute(ctx):
    return phase.span_us(ctx, "dev_dispatch",
                         keep=lambda args: not args.get("built"))
