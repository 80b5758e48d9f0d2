"""Level 1 of the two-level collective as the leader sees it: rank 0's
``dev_chip_fold`` span (coll/device.py ``DeviceFoldChannel._leader``),
inside ``dev_stage``: every chip's staging and fold dispatches, issued
from the one leader thread. Where the device work is eager the thread
is held for about the device's own time, so this says whether the chips
fold side by side or one after the other. ``None`` on a channel, or a
program, that records no such span."""

from . import phase

NAME = "chip_fold_us"


def compute(ctx):
    return phase.span_us(ctx, "dev_chip_fold")
