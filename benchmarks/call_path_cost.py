#!/usr/bin/env python
"""call_path_cost — the library's path of one blocking device collective.

A lone thread: rank 0 of a ``run_ranks`` job whose peers have returned,
its channel's ``_execute`` stubbed to hand the deposit back, so no
rendezvous, no leader and no device: what is timed is ``comm.<coll>``
down to ``_execute`` and back up, the lines every rank walks in its
slice of the interpreter lock (PERF.md, "A rank's slice"). Each row is
one collective on one channel, untraced or traced, timed twice: on a
miss every time (the rank's filed plans dropped before each call, so it
decides transport, deposit, op and tier again and files: the path of a
signature's first call) and on a hit (the call plan of coll/device.py
``plan_of`` / ``run_plan``). Best of ``--rounds`` rounds of ``--repeats``
repeats, the loop's own cost taken out. Host times of whatever machine
runs it: not a device metric, and no test asserts them.

    python benchmarks/call_path_cost.py [--repeats 200000] [--rounds 5]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# the host's path alone: the CPU backend on a machine with a chip too
# (nothing runs on a device), four virtual devices for the mesh channel,
# and the tier decision a TPU backend makes (the kernels runnable)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
os.environ.setdefault("MV2T_ICI_INTERPRET", "1")
os.environ.setdefault("MV2T_DEVICE_COLL_MIN_BYTES", "1")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from mvapich2_tpu.parallel.mesh import make_mesh  # noqa: E402
from mvapich2_tpu.runtime.universe import run_ranks  # noqa: E402
from mvapich2_tpu.utils.config import get_config  # noqa: E402

# channel -> (ranks, devices of the mesh it binds to)
CHANNELS = {"slot": (8, 1), "mesh": (4, 4)}
N = 1024    # float32 elements a rank: 4 KiB, the small cells' message


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


def measure(channel, traced, repeats, rounds):
    """[(collective, miss us, hit us)] of rank 0's lone thread."""
    ranks, ndev = CHANNELS[channel]
    os.environ["MV2T_TRACE"] = "1" if traced else "0"
    get_config().reload()
    rows = []

    def app(comm):
        if comm.rank:
            return
        ch = comm.device_channel
        ch._execute = lambda name, local, op="sum", root=0: local
        x = jax.device_put(np.zeros(N, np.float32), ch.device)
        plans = getattr(ch, "_plans", {})   # none: a tree without plans
        for name in ("allreduce", "alltoall"):
            call = getattr(comm, name)

            def miss(n):
                for _ in range(n):
                    plans.clear()
                    call(x)

            def hit(n):
                for _ in range(n):
                    call(x)
            call(x)
            rows.append((name, best_us(miss, repeats, rounds),
                         best_us(hit, repeats, rounds)))

    run_ranks(ranks, app, device_mesh=make_mesh(
        (ndev,), ("x",), jax.devices()[:ndev]), timeout=3600)
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--repeats", type=int, default=200000)
    ap.add_argument("--rounds", type=int, default=5)
    a = ap.parse_args()
    print(f"# call_path_cost: lone thread, _execute stubbed, best of "
          f"{a.rounds} x {a.repeats}, us a call")
    print(f"{'channel':<8}{'collective':<12}{'traced':<8}"
          f"{'miss':>9}{'hit':>9}")
    for channel in CHANNELS:
        for traced in (False, True):
            for name, miss, hit in measure(channel, traced, a.repeats,
                                           a.rounds):
                print(f"{channel:<8}{name:<12}{str(traced):<8}"
                      f"{miss:9.3f}{hit:9.3f}")


if __name__ == "__main__":
    main()
