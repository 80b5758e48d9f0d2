"""From the launch's return to the result first seen, over the device's
own time: per ``seq``, when the result was first seen (``hostplane``:
on a TPU, whose client writes nothing on a waiting thread's line, the
start of the runtime's completion event for the program the launch
enqueued, where its own thread has read the chip's sync flag and begins
to wake whoever waits; on a client that writes the waits, the earliest
wait end over every rank's line), less the end of rank 0's launch event,
less ``device_busy_us`` a call (as ``enqueue_to_result_over_us``
subtracts it); the median. Both ends are the runtime's stamps on one
plane, written outside the interpreter lock: the runtime's launch and
completion latency with no thread's turn at the lock in it, the floor
ROADMAP A7 asks for. It reads below 0 where the device starts on a call
before the launch event has ended. ``None`` where fewer than half the
calls are seen done."""

from . import hostplane, phase

NAME = "result_seen_over_us"


def compute(ctx):
    tb = hostplane.tables(ctx)
    dev = ctx.rank0_device()
    if tb is None or ctx.traced_calls <= 0 or dev.busy_s <= 0:
        return None
    launched = tb.launches()
    lag = [seen - events[-1][1] for seq, _span, events in launched
           if (seen := tb.seen(seq)) is not None]
    if not lag or 2 * len(lag) < len(launched):
        return None
    return phase.median_us(ctx, lag) - dev.busy_s / ctx.traced_calls * 1e6
