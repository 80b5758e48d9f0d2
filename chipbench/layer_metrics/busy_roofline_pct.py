"""The least time the chip could take for one call (fewest bytes over
the peak they move over; collectives/<name>.py:least_bytes, peaks.json)
as a share of the device time the call did take (device_busy_us). All
the device's busy time is in the denominator, staging copies included,
so nothing the call made the device do is left out of the time."""

from . import device_busy_us

NAME = "busy_roofline_pct"


def compute(ctx):
    busy_us = device_busy_us.compute(ctx)
    if busy_us is None:
        return None
    nbytes, peak_key = ctx.collective.least_bytes(
        ctx.config["expect"]["least_bytes"], ctx.ranks, ctx.bytes_per_rank)
    least_us = nbytes / (ctx.peaks[peak_key] * 1e9) * 1e6
    return 100.0 * least_us / busy_us
