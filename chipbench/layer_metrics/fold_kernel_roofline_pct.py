"""The least time a chip's HBM could take for level 1 of one call (the
collective module's ``fold_bytes``: the chip's ``k = ranks / chips``
deposits read once and their sum written once, ``(k + 1) x m``, over the
HBM peak) as a share of the time the fold's kernel took
(``fold_kernel_us``). The kernel cannot move fewer bytes, so the share
cannot pass 100 %. ``None`` where the kernel did not run, or the
collective counts no fold."""

from . import fold_kernel_us

NAME = "fold_kernel_roofline_pct"


def compute(ctx):
    took_us = fold_kernel_us.compute(ctx)
    fold_bytes = getattr(ctx.collective, "fold_bytes", None)
    if took_us is None or fold_bytes is None:
        return None
    k = int(ctx.config["ranks"]) // int(ctx.config["chips"])
    least_us = fold_bytes(k, ctx.bytes_per_rank) / (
        ctx.peaks["hbm_GBps"] * 1e9) * 1e6
    return 100.0 * least_us / took_us
