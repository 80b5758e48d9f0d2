#!/usr/bin/env python
"""osu_allreduce — float32 allreduce latency (port of osu_allreduce.c,
the north-star benchmark: BASELINE.md row 1)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from mvapich2_tpu import mpi
from mvapich2_tpu.bench import osu_util as u

mpi.Init()
comm = mpi.COMM_WORLD
opts = u.options("allreduce", default_max=1 << 20, collective=True)

_bufs = {}
errs = 0


def run_one(size: int) -> None:
    """One allreduce of ``size`` bytes, validated the way osu's ``-c``
    does: every rank contributes ones, so every element of the result
    is the communicator size (checked at both ends and the middle —
    outside the timed region's cost model, three scalar reads)."""
    global errs
    n = max(size // 4, 1)
    if n not in _bufs:
        _bufs[n] = (np.ones(n, np.float32), np.empty(n, np.float32))
    sb, rb = _bufs[n]
    comm.allreduce(sb, rb)
    if not (rb[0] == rb[n // 2] == rb[-1] == comm.size):
        errs += 1


u.collective_latency(comm, "Allreduce Latency Test", run_one, opts)
u.finalize_ok(comm, errs)
