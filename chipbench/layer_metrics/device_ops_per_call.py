"""Operations the device ran for one collective: the events of rank
0's device's ``XLA Ops`` line that lie inside the traced sub-window,
over the collectives completed in it. Name-free, as ``device_busy_us``:
the kernel, the XLA ops the program compiled to around it, and whatever
the library issued eagerly for the call (slices, reshapes) all count
one each. An operation cut by the sub-window's edge counts where it
ends. ``None`` of a run that traced no device."""

NAME = "device_ops_per_call"


def compute(ctx):
    dev = ctx.rank0_device()
    if dev is None or ctx.traced_calls <= 0:
        return None
    ran = sum(1 for _name, _s, e in dev.ops if dev.lo < e <= dev.hi)
    return ran / ctx.traced_calls if ran else None
