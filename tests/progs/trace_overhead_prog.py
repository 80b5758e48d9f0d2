"""Trace-overhead guard: tracing-off must stay in the noise on the
osu_latency-shaped ping-pong path, so the recorder can stay compiled-in.

The trace-off cost at every instrumented site is ONE attribute check
(``engine.tracer is None``) plus, on the channel layer, the per-packet
pvar increments. There is no un-instrumented build to A/B against, so
the guard measures those exact unit costs on this host, scales them by a
deliberately generous per-message site count, and asserts the total is
under 5% of the measured per-message latency. If someone fattens the
gate (a config lookup, a dict build) or slows PVar.inc, this trips.

Each unit cost is the least of BATCHES batches of BATCH_N: the test
runs beside five other xdist workers, and contention only ever adds to
a unit cost, so the least batch is the nearest to the cost itself; the
bare ``for`` of the batch, measured the same way, is taken off, because
no site pays it. The latency is taken as measured.

Launched via: python -m mvapich2_tpu.run -np 2 tests/progs/trace_overhead_prog.py
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")
from mvapich2_tpu import mpi, mpit  # noqa: E402

ITERS = 300
SKIP = 50
BATCHES = 5
BATCH_N = 40000


def least_batch(batch) -> float:
    """Seconds per iteration of ``batch(n)``: the least of BATCHES."""
    best = float("inf")
    for _ in range(BATCHES):
        t0 = time.perf_counter()
        batch(BATCH_N)
        best = min(best, (time.perf_counter() - t0) / BATCH_N)
    return best


def bare_loop(n):
    for _ in range(n):
        pass


def unit_cost(batch) -> float:
    """What one iteration of ``batch`` costs beyond the loop itself."""
    return max(0.0, least_batch(batch) - least_batch(bare_loop))

# per ping-pong message, generous upper bounds for trace-off work:
GATE_SITES = 16     # tracer-is-None checks (mpi/protocol/progress/nbc/chan)
PVINC_SITES = 8     # channel + protocol counter increments
# native ring off (ISSUE 10): every MV2T_NTRACE site in cplane.cpp is
# ONE pointer-NULL branch (p->nt_mine) — strictly cheaper than the
# python attribute check measured below, so modeling the C sites with
# the python gate's unit cost OVERSTATES them. Generous per-message
# count: eager tx+rx, bell ring, spin->bell, wake, flat fan-in/fold/
# fan-out, dispatch, plus slack.
NTRACE_SITES = 12
# metrics-off (ISSUE 17): every histogram site is ONE module-attribute
# check (``metrics.LIVE is None``) — same discipline, measured with
# its own unit cost below. Generous per-message count: collective
# flat/sched gates, rendezvous drain/publish, RMA, plus slack.
METRICS_SITES = 8

mpi.Init()
comm = mpi.COMM_WORLD
rank, size = comm.rank, comm.size
assert size == 2, "trace_overhead_prog requires exactly 2 ranks"

sbuf = np.zeros(8, np.uint8)
rbuf = np.zeros(8, np.uint8)
comm.barrier()
if rank == 0:
    for i in range(ITERS + SKIP):
        if i == SKIP:
            t0 = time.perf_counter()
        comm.send(sbuf, dest=1, tag=1)
        comm.recv(rbuf, source=1, tag=1)
    lat = (time.perf_counter() - t0) / ITERS / 2    # one-way seconds
else:
    for i in range(ITERS + SKIP):
        comm.recv(rbuf, source=0, tag=1)
        comm.send(sbuf, dest=0, tag=1)

errs = 0
if rank == 0 and comm.u.engine.tracer is not None:
    # run under bin/mpitrace: the off-cost guard is meaningless with the
    # recorder attached — report and pass (the tier-1 test runs untraced)
    print("tracing is ON; skipping the trace-off overhead guard")
elif rank == 0:
    # the native ring must actually be OFF for this budget to be the
    # trace-off cost (MV2T_NTRACE unset follows MV2T_TRACE, also off)
    sch = comm.u.shm_channel
    if sch is not None and getattr(sch, "ntrace_active", lambda: False)():
        print("native trace ring is ON; overhead guard expects it off")
        errs += 1
    eng = comm.u.engine

    def gate_batch(n):
        hits = 0
        for _ in range(n):
            if eng.tracer is not None:      # the exact trace-off gate
                hits += 1
        assert hits == 0
    t_gate = unit_cost(gate_batch)

    pv = mpit.pvar("trace_overhead_probe", mpit.PVAR_CLASS_COUNTER,
                   "test", "overhead-guard probe counter")

    def inc_batch(n):
        for _ in range(n):
            pv.inc()
    t_inc = unit_cost(inc_batch)

    # the metrics-off branch: the exact gate the histogram sites pay
    # when MV2T_METRICS=0 (module attribute read + None check). The
    # job here runs with metrics ON (the default), so LIVE is not None
    # and the measured cost is the on-path check — an upper bound on
    # the off-path one (same lookup, same branch shape).
    from mvapich2_tpu import metrics as _metrics

    def metrics_batch(n):
        seen = 0
        for _ in range(n):
            if _metrics.LIVE is not None:   # the exact metrics gate
                seen += 1
    t_met = unit_cost(metrics_batch)

    overhead = (GATE_SITES + NTRACE_SITES) * t_gate \
        + PVINC_SITES * t_inc + METRICS_SITES * t_met
    frac = overhead / lat
    print(f"latency {lat * 1e6:.2f} us/msg; gate {t_gate * 1e9:.1f} ns; "
          f"pvar.inc {t_inc * 1e9:.1f} ns; metrics gate "
          f"{t_met * 1e9:.1f} ns; trace-off overhead "
          f"(incl. {NTRACE_SITES} native ring-off branches and "
          f"{METRICS_SITES} metrics gates) "
          f"{overhead * 1e6:.3f} us/msg = {frac * 100:.2f}% of latency")
    if frac >= 0.05:
        errs += 1
        print(f"trace-off overhead {frac * 100:.2f}% >= 5% budget")

    # sampler-on smoke budget: one tick (fp-mirror slice + a dozen
    # pvar reads + ~600 B of struct packing) must cost well under one
    # sampling interval — the heartbeat thread absorbs it without ever
    # falling behind the lease cadence. Budget: 1% of the 250 ms
    # default interval (2.5 ms/tick) — generous by ~3 orders on any
    # plausible host, but catches an accidental O(ring) or O(n_local)
    # regression in the tick path.
    smp = getattr(sch, "_sampler", None) if sch is not None else None
    if smp is not None and not smp.dead:
        reps = 200
        t0 = time.perf_counter()
        for _ in range(reps):
            smp.tick()
        t_tick = (time.perf_counter() - t0) / reps
        print(f"sampler tick {t_tick * 1e6:.2f} us "
              f"(budget {0.01 * smp.interval * 1e6:.0f} us)")
        if t_tick >= 0.01 * smp.interval:
            errs += 1
            print(f"sampler tick {t_tick * 1e6:.1f} us exceeds 1% of "
                  f"the {smp.interval * 1e3:.0f} ms interval")

comm.barrier()
if rank == 0 and errs == 0:
    print("No Errors")
mpi.Finalize()
sys.exit(1 if errs else 0)
