#!/usr/bin/env python
"""trace_cost — what the recorder costs when it is on, piece by piece.

A lone thread, no chip, no ranks: the time of one ``Recorder.record``, of
one phase span of coll/device.py (``_phase`` entered and left), of one
traced call through profile.py's wrapper with the ``mpi`` lane's tool
installed, and of ``_run``'s ``TraceAnnotation`` with no profiler
session; beside them the floor, a bare ``deque.append`` of the ring's
tuple. Best of ``--rounds`` rounds of ``--repeats`` repeats, the loop's
own cost taken out. It also times ``_run``'s always-on measurement lines
as they stand there (ISSUE 36). Host times of whatever machine runs it:
not a device metric, and no test asserts them.

    python benchmarks/trace_cost.py [--repeats 200000] [--rounds 5]
"""

import argparse
import collections
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from mvapich2_tpu import profile, trace  # noqa: E402
from mvapich2_tpu.coll import device as devmod  # noqa: E402
from mvapich2_tpu.trace.recorder import Recorder  # noqa: E402


def best_us(fn, repeats, rounds):
    """Best round's microseconds a repeat of ``fn(repeats)``, less the
    same loop around nothing."""
    def empty(n):
        for _ in range(n):
            pass

    def one(f):
        took = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            f(repeats)
            took.append(time.perf_counter() - t0)
        return min(took)
    return (one(fn) - one(empty)) / repeats * 1e6


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--repeats", type=int, default=200000)
    ap.add_argument("--rounds", type=int, default=5)
    a = ap.parse_args()
    rows = []

    ring = collections.deque(maxlen=65536)
    mono = time.monotonic
    args = {"seq": 1, "coll": "allreduce"}

    def floor(n):
        for _ in range(n):
            ring.append((mono(), "device", "dev_arrive", "B", args))
    rows.append(("floor: deque.append of the tuple", floor))

    rec = Recorder(0, 65536)

    def record_kwargs(n):
        for _ in range(n):
            rec.record("device", "dev_arrive", "B", seq=1, coll="allreduce")
    rows.append(("Recorder.record, two kwargs", record_kwargs))

    def record_bare(n):
        for _ in range(n):
            rec.record("mpi", "allreduce", "B")
    rows.append(("Recorder.record, no args", record_bare))

    # one phase span as a leader or a rank opens it inside _run
    ch = object.__new__(devmod.DeviceCollChannel)
    ch._tr, ch._seq = rec, 1
    ch._args = args         # what _run builds once a call

    def phase_span(n):
        for _ in range(n):
            with ch._phase("dev_arrive"):
                pass
    rows.append(("one _phase span (B and E)", phase_span))

    def phase_span_adds(n):
        for _ in range(n):
            with ch._phase("dev_dispatch") as ph:
                if ph is not None:
                    ph.args["built"] = False
    rows.append(("one _phase span whose site adds an arg", phase_span_adds))

    # the mpi lane: profile.py's wrapper around a method that does
    # nothing, the recorder's tool the one interceptor installed
    class _Engine:
        tracer = rec

    class _Universe:
        engine = _Engine()

    class _Comm:
        u = _Universe()

    def real(self, x, op=None):
        return x
    profile.install(trace._mpi_tracer)
    try:
        wrapped = profile._make_wrapper("allreduce", real)
        comm = _Comm()

        def mpi_call(n):
            for _ in range(n):
                wrapped(comm, 1)
        rows.append(("profile wrapper, traced call (two records)", mpi_call))
        results = [(label, best_us(fn, a.repeats, a.rounds))
                   for label, fn in rows]
    finally:
        profile.uninstall(trace._mpi_tracer)

    import jax

    def annotation(n):
        for _ in range(n):
            with jax.profiler.TraceAnnotation("dev_allreduce", seq=1):
                pass
    results.append(("TraceAnnotation, no profiler session",
                    best_us(annotation, a.repeats, a.rounds)))

    # _run's always-on measurement lines, as they stand in the function
    def import_time(n):
        for _ in range(n):
            import time as _time  # noqa: F401
    results.append(("_run: import time", best_us(import_time, a.repeats,
                                                 a.rounds)))

    def profile_check(n):
        for _ in range(n):
            devmod._maybe_start_jax_profile()
    results.append(("_run: _maybe_start_jax_profile()",
                    best_us(profile_check, a.repeats, a.rounds)))

    # compiled in coll/device.py's own namespace: the relative form
    # resolves its package on every pass, as the line in _run does
    scope = dict(vars(devmod))
    exec("def import_metrics(n):\n"
         "    for _ in range(n):\n"
         "        from .. import metrics as _metrics\n"
         "        _metrics.LIVE\n", scope)
    import_metrics = scope["import_metrics"]
    results.append(("_run: import metrics, read LIVE",
                    best_us(import_metrics, a.repeats, a.rounds)))

    def two_clocks(n):
        for _ in range(n):
            time.perf_counter()
            time.perf_counter()
    results.append(("_run: two perf_counter reads",
                    best_us(two_clocks, a.repeats, a.rounds)))

    print(f"# trace_cost: lone thread, best of {a.rounds} x {a.repeats}, "
          f"us each")
    for label, us in results:
        print(f"{label:<48} {us:8.3f}")


if __name__ == "__main__":
    main()
