"""Rank program: large-message allreduce perf smoke.

Times a handful of 1 MiB allreduces at np=4 and prints the per-call
average. The harness (tests/test_perf_smoke.py) asserts the average
stays under a generous wall-clock budget — the scratch-file cliff this
guards against was ~33 ms/call on the host that showed it, an order
of magnitude over the budget, so the check is variance-proof while still catching
any silent return of per-send staging files.

Launched via: python -m mvapich2_tpu.run -np 4 tests/progs/allreduce_smoke_prog.py
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")
from mvapich2_tpu import mpi                        # noqa: E402

mpi.Init()
comm = mpi.COMM_WORLD
rank, size = comm.rank, comm.size

n = (1 << 20) // 4              # 1 MiB of float32
sbuf = np.full(n, float(rank + 1), dtype=np.float32)
rbuf = np.zeros(n, dtype=np.float32)
expect = float(sum(range(1, size + 1)))

# warmup (segment/arena construction, tuning-table touch)
for _ in range(3):
    comm.allreduce(sbuf, rbuf, mpi.SUM)

iters = 10
comm.barrier()
t0 = time.perf_counter()
for _ in range(iters):
    comm.allreduce(sbuf, rbuf, mpi.SUM)
comm.barrier()
dt = time.perf_counter() - t0

errs = 0
if not np.all(rbuf == expect):
    errs += 1
    print(f"rank {rank}: allreduce result wrong "
          f"(got {rbuf[0]}, want {expect})")

if rank == 0:
    print(f"allreduce_1MiB_avg_us={dt / iters * 1e6:.1f}")
    if errs == 0:
        print("No Errors")
mpi.Finalize()
sys.exit(1 if errs else 0)
