"""Shared OSU micro-benchmark machinery.

Python port of the OSU harness contract (BASELINE.md / SURVEY §6:
osu_benchmarks/util/osu_util_mpi.c): power-of-two message sweep, warm-up
``skip`` iterations outside the timed window, MPI_Wtime bracketing,
min/max/avg reduction across ranks, and the exact output format — so
results are comparable line-for-line with the reference suite.
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Callable, Iterable, List

import numpy as np

from .. import mpi
from ..utils.config import cvar

cvar("BENCH_INIT_BUDGET_MS", 2000, int, "bench",
     "bin/bench_osu startup gate: fail the bench run when MPI_Init's "
     "p50 over the trials exceeds this many milliseconds (0 disables; "
     "--init-budget-ms overrides per run).")


def options(desc: str, default_max: int = 1 << 22, collective: bool = False):
    ap = argparse.ArgumentParser(description=desc)
    ap.add_argument("-m", "--max-size", type=int, default=default_max)
    ap.add_argument("--min-size", type=int, default=4)
    ap.add_argument("-i", "--iterations", type=int,
                    default=100 if collective else 1000)
    ap.add_argument("-x", "--skip", type=int, default=10)
    ap.add_argument("-f", "--full", action="store_true",
                    help="print min/max/iterations columns")
    return ap.parse_args()


def sizes(opts) -> Iterable[int]:
    s = max(opts.min_size, 1)
    while s <= opts.max_size:
        yield s
        s *= 2


def scale_iters(opts, size: int) -> int:
    """OSU halves the iteration count for large messages."""
    if size > (1 << 20):
        return max(10, opts.iterations // 10)
    if size > (1 << 16):
        return max(20, opts.iterations // 2)
    return opts.iterations


def header(comm, title: str, cols: str = "Latency (us)") -> None:
    if comm.rank == 0:
        print(f"# OSU MPI {title}")
        print(f"# {'Size':<10} {cols}")
        sys.stdout.flush()


def collective_latency(comm, title: str, run_one: Callable[[int], None],
                       opts) -> None:
    """Time a collective per message size: every rank times its call,
    results reduced min/max/avg over ranks (osu_allreduce.c:110-142)."""
    header(comm, title, "Avg Latency(us)" +
           ("    Min Latency(us)    Max Latency(us)  Iterations"
            if opts.full else ""))
    for size in sizes(opts):
        iters = scale_iters(opts, size)
        for _ in range(opts.skip):
            run_one(size)
        comm.barrier()
        t0 = mpi.Wtime()
        for _ in range(iters):
            run_one(size)
        elapsed = (mpi.Wtime() - t0) / iters * 1e6
        stats = np.array([elapsed, -elapsed, elapsed], np.float64)
        # avg over ranks; min = -max(-t); max
        from ..core import op as opmod
        red = comm.allreduce(np.array([elapsed], np.float64))
        avg = float(red[0]) / comm.size
        mn = float(comm.allreduce(np.array([elapsed]), op=opmod.MIN)[0])
        mx = float(comm.allreduce(np.array([elapsed]), op=opmod.MAX)[0])
        if comm.rank == 0:
            if opts.full:
                print(f"{size:<12} {avg:>14.2f} {mn:>18.2f} {mx:>18.2f} "
                      f"{iters:>10}")
            else:
                print(f"{size:<12} {avg:>14.2f}")
            sys.stdout.flush()
        comm.barrier()


def finalize_ok(comm, errs: int = 0) -> None:
    """Close a benchmark the way the conformance corpus closes a test:
    sum every rank's validation-error count and have rank 0 print
    ``No Errors`` (or the count) as the last line; a job with errors
    exits non-zero."""
    total = int(comm.allreduce(np.array([errs], np.int32))[0])
    if comm.rank == 0:
        print("No Errors" if total == 0 else f"Found {total} errors")
        sys.stdout.flush()
    comm.barrier()
    mpi.Finalize()
    if total:
        sys.exit(1)
