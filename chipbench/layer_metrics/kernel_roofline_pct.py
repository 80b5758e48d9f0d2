"""The least time the chip could take for one call (the same least time
``busy_roofline_pct`` uses: fewest bytes over the peak they move over) as
a share of the time the library's kernel took (``kernel_us``). The least
bytes are what the kernel cannot avoid moving, so the share cannot pass
100 %."""

from . import kernel_us

NAME = "kernel_roofline_pct"


def compute(ctx):
    took_us = kernel_us.compute(ctx)
    if took_us is None:
        return None
    nbytes, peak_key = ctx.collective.least_bytes(
        ctx.config["expect"]["least_bytes"], ctx.ranks, ctx.bytes_per_rank)
    return 100.0 * (nbytes / (ctx.peaks[peak_key] * 1e9) * 1e6) / took_us
