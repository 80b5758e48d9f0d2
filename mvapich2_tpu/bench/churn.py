"""Sustained rank-churn benchmark: connect/disconnect cycles per second.

The serving-scale startup scenario (ROADMAP item 4): jobs and sessions
churn constantly, so the metric that matters is not one cold MPI_Init
but how many full job lifecycles — launch, Init, (optional traffic),
Finalize, reap — a node sustains per second. Two scenarios:

  * **serial** (``churn_rate``): one launcher process runs N sequential
    jobs, so the measured cycle is exactly the per-job cost: rank
    process spawn + light boot (+ world build when the program
    communicates) + teardown. Measured with MV2T_DAEMON=0 and 1, the
    delta is the warm-attach daemon's contribution.
  * **concurrent** (``churn_concurrent``): the multi-tenant shape —
    N jobs of >= 2 geometries launched with up to ``inflight`` jobs
    overlapping against ONE daemon dir, exercising the per-geometry
    set instances, the admission quota and the claim queue. Reports
    sustained cycles/s plus p50/p99 per-job attach latency (the full
    job lifecycle, the serving-traffic tail metric).

``exec_cache_bench`` measures the device-executable cache's
contribution on this host (interpreter/CPU mode): cold trace+compile
vs warm deserialize of the same device-collective program build
(coll/device.py ``_build`` through the ops/_compat.py export seam).

``python -m mvapich2_tpu.bench.churn --artifact <path>`` writes the
serial band, the concurrent band and the exec-cache probe as one JSON
file; ``bin/bench_osu`` embeds the serial band in its own output;
tests/test_daemon.py keeps a tier-1 smoke on both scenarios.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import List, Optional, Sequence


def churn_rate(argv: List[str], np_: int = 2, cycles: int = 8,
               daemon: int = 0, env_extra: Optional[dict] = None,
               timeout: float = 120.0) -> dict:
    """Run ``argv`` as ``cycles`` sequential ``np_``-rank jobs; return
    {"cps", "s_per_cycle", "per_cycle_s", ...}. Raises on any nonzero
    job exit — a churn bench that drops cycles is not a benchmark."""
    from ..runtime.launcher import launch
    env = dict(env_extra or {})
    env["MV2T_DAEMON"] = str(daemon)
    per_cycle = []
    for i in range(cycles):
        t0 = time.perf_counter()
        rc = launch(np_, list(argv), env_extra=env, timeout=timeout)
        if rc != 0:
            raise RuntimeError(
                f"churn cycle {i} (daemon={daemon}) exited rc={rc}")
        per_cycle.append(time.perf_counter() - t0)
    total = sum(per_cycle)
    return {"np": np_, "cycles": cycles, "daemon": daemon,
            "cps": cycles / total if total else 0.0,
            "s_per_cycle": total / cycles,
            "min_s": min(per_cycle), "max_s": max(per_cycle),
            "per_cycle_s": [round(s, 4) for s in per_cycle]}


def _pct(sorted_vals: List[float], p: float) -> float:
    """Nearest-rank percentile of an ascending list."""
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1,
            max(0, int(round(p / 100.0 * len(sorted_vals) + 0.5)) - 1))
    return sorted_vals[i]


def churn_concurrent(argv: List[str], geometries: Sequence[int] = (2, 3),
                     jobs: int = 8, inflight: int = 4, daemon: int = 1,
                     env_extra: Optional[dict] = None,
                     timeout: float = 240.0) -> dict:
    """Run ``jobs`` jobs round-robin over ``geometries`` (rank counts)
    with up to ``inflight`` overlapping, all against one daemon dir —
    the multi-tenant serving shape. Returns {"cps", "p50_s", "p99_s",
    ...}; raises on any nonzero job exit. ``inflight=1`` is the serial
    equal-load baseline the concurrent band is gated against."""
    from ..runtime.launcher import launch
    env = dict(env_extra or {})
    env["MV2T_DAEMON"] = str(daemon)
    sem = threading.Semaphore(max(1, inflight))
    per_job: List[Optional[float]] = [None] * jobs
    errs: List[str] = []
    lock = threading.Lock()

    def one(i: int) -> None:
        np_ = geometries[i % len(geometries)]
        t0 = time.perf_counter()
        try:
            rc = launch(np_, list(argv), env_extra=env, timeout=timeout)
        except Exception as e:   # noqa: BLE001 — collected, re-raised
            rc, msg = -1, repr(e)
        else:
            msg = f"rc={rc}"
        dt = time.perf_counter() - t0
        with lock:
            if rc != 0:
                errs.append(f"job {i} (np={np_}, daemon={daemon}): {msg}")
            per_job[i] = dt
        sem.release()

    t_start = time.perf_counter()
    threads = []
    for i in range(jobs):
        sem.acquire()
        th = threading.Thread(target=one, args=(i,),
                              name=f"churn-job-{i}")
        th.start()
        threads.append(th)
    for th in threads:
        th.join()
    total = time.perf_counter() - t_start
    if errs:
        raise RuntimeError("concurrent churn dropped cycles — not a "
                           "benchmark: " + "; ".join(errs))
    lat = sorted(float(s) for s in per_job)
    return {"geometries": list(geometries), "jobs": jobs,
            "inflight": inflight, "daemon": daemon,
            "cps": jobs / total if total else 0.0,
            "total_s": round(total, 4),
            "p50_s": round(_pct(lat, 50), 4),
            "p99_s": round(_pct(lat, 99), 4),
            "max_s": round(lat[-1], 4),
            "per_job_s": [round(s, 4) for s in lat]}


def exec_cache_bench(dir_: Optional[str] = None, n: int = 65536,
                     ranks: int = 4) -> dict:
    """Cold trace+compile vs warm cache-deserialize of one device-
    collective program build (the HBM slot-channel allreduce at ``n``
    f32 elements — what a first device collective pays on this host;
    interpreter/CPU mode off-TPU). Returns {"cold_ms", "warm_ms",
    "hit": bool}; hit=False means this jax has no export API and the
    cache no-ops (still a valid artifact — the gate only compares
    when hit is True)."""
    import numpy as np   # noqa: F401 — jax path below needs the stack

    from ..coll.device import HBMSlotChannel, _Rendezvous
    from ..ops import _compat
    import jax
    dev = jax.devices()[0]
    ch = HBMSlotChannel(dev, _Rendezvous(ranks), 0, ranks)
    x = jax.device_put(
        np.ones((ranks, n), np.float32), dev)

    t0 = time.perf_counter()
    prog = ch._build("allreduce", n, "sum", 0)
    jax.block_until_ready(prog(x))
    cold = time.perf_counter() - t0

    blob = _compat.serialize_executable(prog, x)
    if blob is None:
        return {"n": n, "ranks": ranks, "cold_ms": round(cold * 1e3, 2),
                "warm_ms": None, "hit": False}
    t0 = time.perf_counter()
    fn = _compat.deserialize_executable(blob)
    jax.block_until_ready(fn(x))
    warm = time.perf_counter() - t0
    return {"n": n, "ranks": ranks, "cold_ms": round(cold * 1e3, 2),
            "warm_ms": round(warm * 1e3, 2), "hit": True,
            "blob_bytes": len(blob)}


def _default_prog() -> List[str]:
    """A python Init/Finalize cycle program (used when no compiled C
    program is supplied — python ranks build the world at Init, so
    this exercises the full attach-not-construct path)."""
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return [sys.executable,
            os.path.join(repo, "tests", "progs", "churn_cycle_prog.py")]


def run_artifact(prog: List[str], jobs: int = 8,
                 inflight: int = 4,
                 geometries: Sequence[int] = (2, 3),
                 env_extra: Optional[dict] = None) -> dict:
    """The body of the ``--artifact`` file:

      * ``churn_np2`` — the serial per-geometry band (daemon 0 vs 1),
        osu_compare's existing churn comparison shape;
      * ``churn_concurrent`` — serial equal-load baseline (inflight=1)
        vs the overlapping run (inflight=N), BOTH with the daemon on
        and the same total jobs (overlap that loses to the serial
        run is the defect to look for);
      * ``exec_cache`` — the warm-hit probe (cold trace+compile vs
        cache deserialize, interpreter/CPU mode off-TPU).
    """
    env = dict(env_extra or {})
    results: dict = {}
    results["churn_np2"] = {
        f"daemon{dm}": churn_rate(prog, 2, jobs, dm, env_extra=env)
        for dm in (0, 1)}
    results["churn_concurrent"] = {
        "serial1": churn_concurrent(prog, geometries, jobs, 1,
                                    env_extra=env),
        f"conc{inflight}": churn_concurrent(prog, geometries, jobs,
                                            inflight, env_extra=env),
    }
    return results


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="connect/disconnect churn: serial daemon off/on, "
                    "many-jobs-in-flight concurrent, exec-cache probe")
    ap.add_argument("--np", type=int, default=2)
    ap.add_argument("--cycles", type=int, default=8)
    ap.add_argument("--prog", nargs="+", default=None,
                    help="rank program argv (default: python "
                         "Init/Finalize cycle prog)")
    ap.add_argument("--daemon", choices=("0", "1", "both"),
                    default="both")
    ap.add_argument("--concurrent", action="store_true",
                    help="many-jobs-in-flight scenario instead of "
                         "serial cycles")
    ap.add_argument("--jobs", type=int, default=8)
    ap.add_argument("--inflight", type=int, default=4)
    ap.add_argument("--geometries", type=int, nargs="+",
                    default=[2, 3])
    ap.add_argument("--artifact", default=None,
                    help="write the full churn artifact (serial "
                         "+ concurrent bands + exec-cache probe) to "
                         "this path")
    a = ap.parse_args(argv)
    prog = a.prog or _default_prog()
    if a.artifact:
        # exec_cache sits BESIDE results: osu_compare treats every
        # results key as a band map, and the probe is ms-shaped
        out = {"host": os.uname().nodename,
               "convention": "churn bands: cycles/s (higher better) + "
                             "p99 attach latency s; exec_cache: ms",
               "results": run_artifact(prog, a.jobs, a.inflight,
                                       a.geometries),
               "exec_cache": exec_cache_bench()}
        with open(a.artifact, "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps({"results": out["results"],
                          "exec_cache": out["exec_cache"]}, indent=1))
        return 0
    out = {}
    if a.concurrent:
        for dm in ((0, 1) if a.daemon == "both" else (int(a.daemon),)):
            out[f"conc-daemon{dm}"] = churn_concurrent(
                prog, a.geometries, a.jobs, a.inflight, dm)
    else:
        for dm in ((0, 1) if a.daemon == "both" else (int(a.daemon),)):
            out[f"daemon{dm}"] = churn_rate(prog, a.np, a.cycles, dm)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
