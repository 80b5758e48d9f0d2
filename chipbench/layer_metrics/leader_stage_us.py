"""The leader assembling the program's input: rank 0's ``dev_stage``
span (per-slot reshape or device_put, the stack, the global array)."""

from . import phase

NAME = "leader_stage_us"


def compute(ctx):
    return phase.span_us(ctx, "dev_stage")
