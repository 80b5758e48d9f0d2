"""Device time of the chip fold's kernel per collective: seconds of the
ops on rank 0's device named ``mv2t_slot_reduce`` (ops/pallas_hbm.py:
level 1 of the two-level allreduce, the slot channel's one kernel),
clipped to the traced sub-window, over the collectives completed in it.
``kernel_us`` less this is the ring (``mv2t_hbm_all_reduce``). ``None``
where no such op ran."""

NAME = "fold_kernel_us"
FOLD_KERNEL = "mv2t_slot_reduce"


def compute(ctx):
    dev = ctx.rank0_device()
    if dev is None or ctx.traced_calls <= 0:
        return None
    took = sum(max(0.0, min(e, dev.hi) - max(s, dev.lo))
               for name, s, e in dev.ops if FOLD_KERNEL in name)
    if took <= 0:
        return None
    return took / ctx.traced_calls * 1e6
