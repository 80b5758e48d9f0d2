"""From the enqueue to the result, over the device's own time: over rank
0's calls in the traced sub-window, the median of (result known ready
less rank 0's ``dev_dispatch`` E), less ``device_busy_us``. Known ready
is the call's ``dev_device_wait`` E where it has one (the slot leader
blocks itself), else the end of that iteration's wait in the caller's
``block_until_ready`` (``ctx.caller_waits``; ``rounds.known_ready``).
Launch and completion latency, the runtime's and the interpreter
lock's, with no line of the library's in it (ROADMAP A7). Durations
only: the two planes are not aligned."""

import statistics

from . import rounds

NAME = "enqueue_to_result_over_us"


def compute(ctx):
    dev = ctx.rank0_device()
    if dev is None or ctx.traced_calls <= 0 or dev.busy_s <= 0:
        return None
    lag = [ready - enq for _b, enq, ready in rounds.known_ready(ctx)]
    if not lag:
        return None
    return (statistics.median(lag) - dev.busy_s / ctx.traced_calls) * 1e6
