"""Nonblocking + persistent device collectives on the NBC DAG (ISSUE 18).

The VERDICT-driving contract: i-collectives on a mesh-bound comm route
to the device tier as NBC-DAG schedules (deposit CALL -> per-segment
POLL vertices -> completion CALL) whose results are bit-identical to
the blocking device path; calls the channel cannot route count
dev_coll_fallback_nbc and take the host schedule unchanged; the
MPI_*_init persistent surface pre-warms the program build through the
daemon exec-cache seam so warm starts skip the compile; a rank dying
mid-flight unwinds survivor DAGs with MPIX_ERR_PROC_FAILED and leaks
no schedule state.
"""

import os
import shutil
import tempfile
import time

import numpy as np
import pytest

from mvapich2_tpu import mpit
from mvapich2_tpu.core.errors import MPIException, MPIX_ERR_PROC_FAILED
from mvapich2_tpu.runtime.universe import run_ranks
from mvapich2_tpu.utils.config import get_config

N_RANKS = 8


def _reload(**env):
    for k, v in env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    get_config().reload()


@pytest.fixture(autouse=True)
def _clean_env():
    yield
    _reload(MV2T_DEVICE_COLL_MIN_BYTES=None,
            MV2T_DEVICE_NBC_SEG_BYTES=None,
            MV2T_DEVICE_NBC_MAX_SEGS=None,
            MV2T_ALLREDUCE_ALGO=None, MV2T_METRICS=None)


@pytest.fixture()
def ddir():
    d = tempfile.mkdtemp(prefix="mv2t-devnbc-test-")
    _reload(MV2T_DAEMON_SPAWN="0")
    yield d
    _reload(MV2T_DAEMON_SPAWN=None, MV2T_DAEMON=None,
            MV2T_DAEMON_DIR=None, MV2T_DAEMON_EXEC_CACHE=None)
    shutil.rmtree(d, ignore_errors=True)


def _count_matrix(p, shape):
    """Deterministic skewed count matrices every rank can rebuild."""
    if shape == "uniform":
        return [[3] * p for _ in range(p)]
    if shape == "zero":                 # rank 0 sends nothing at all
        return [[0] * p if i == 0 else [(i + j) % 4 for j in range(p)]
                for i in range(p)]
    return [[(i + 2 * j) % 3 for j in range(p)] for i in range(p)]


def _v_bufs(p, r, counts, dtype):
    """(sendbuf, scounts, rcounts, expect) for rank r: peer j's payload
    is arange(sender*1000 + receiver*100, ...) — position-exact."""
    scounts = list(counts[r])
    rcounts = [counts[j][r] for j in range(p)]
    send = np.concatenate(
        [np.arange(r * 1000 + j * 100, r * 1000 + j * 100 + c)
         for j, c in enumerate(scounts)] or [np.zeros(0)]).astype(dtype)
    expect = np.concatenate(
        [np.arange(j * 1000 + r * 100, j * 1000 + r * 100 + c)
         for j, c in enumerate(rcounts)] or [np.zeros(0)]).astype(dtype)
    return send, scounts, rcounts, expect


# ---------------------------------------------------------------------------
# tentpole: i-collectives ride the device NBC DAG, results bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nr", [2, 4, 8])
def test_inbc_device_route_bit_identical(nr):
    """iallreduce/ialltoall/ialltoallv on int data route device
    (req.device_nbc), overlap a local compute phase between issue and
    wait, and land bit-identical results; the DAG engine issues their
    vertices (nbc_vertices_issued) and the segmented allreduce launches
    multiple device segments (dev_nbc_segments)."""
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1",
            MV2T_DEVICE_NBC_SEG_BYTES="256")
    v0 = mpit.pvar("nbc_vertices_issued").read()
    s0 = mpit.pvar("dev_nbc_segments").read()
    routed = {"ar": [], "a2a": [], "a2av": []}

    def app(comm):
        p, r = comm.size, comm.rank
        # iallreduce: 2048B int32 -> 8 segments at 256B
        x = np.arange(512, dtype=np.int32) + r
        out = np.zeros_like(x)
        req = comm.iallreduce(x, out)
        routed["ar"].append(getattr(req, "device_nbc", False))
        local = x * 2                    # overlapped compute
        req.wait()
        blocking = comm.allreduce(x)     # the blocking device path
        np.testing.assert_array_equal(out, blocking)
        np.testing.assert_array_equal(
            out, np.arange(512, dtype=np.int32) * p + sum(range(p)))
        assert local[1] == x[1] * 2
        # ialltoall
        send = np.array([r * p + j for j in range(p)],
                        np.int32).repeat(8)
        recv = np.zeros_like(send)
        req = comm.ialltoall(send, recv)
        routed["a2a"].append(getattr(req, "device_nbc", False))
        req.wait()
        np.testing.assert_array_equal(
            recv, np.array([s * p + r for s in range(p)],
                           np.int32).repeat(8))
        # ialltoallv: skewed counts, dense displs
        counts = _count_matrix(p, "skew")
        send, scounts, rcounts, expect = _v_bufs(p, r, counts, np.int32)
        recv = np.zeros(sum(rcounts), np.int32)
        req = comm.ialltoallv(send, scounts, None, recv, rcounts, None)
        routed["a2av"].append(getattr(req, "device_nbc", False))
        req.wait()
        np.testing.assert_array_equal(recv, expect)

    run_ranks(nr, app, device_mesh=True)
    for k, v in routed.items():
        assert v and all(v), f"{k} did not route device: {v}"
    assert mpit.pvar("nbc_vertices_issued").read() > v0
    assert mpit.pvar("dev_nbc_segments").read() >= s0 + 8 + 1 + 1


def test_nonroutable_icoll_counts_fallback():
    """float64 does not lower (x64 off): the i-collective counts
    dev_coll_fallback_nbc, takes the host schedule, and is still
    correct."""
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")
    f0 = mpit.pvar("dev_coll_fallback_nbc").read()
    routed = []

    def app(comm):
        x = np.arange(64, dtype=np.float64) + comm.rank
        out = np.zeros_like(x)
        req = comm.iallreduce(x, out)
        routed.append(getattr(req, "device_nbc", False))
        req.wait()
        np.testing.assert_array_equal(
            out, np.arange(64, dtype=np.float64) * comm.size
            + sum(range(comm.size)))

    run_ranks(4, app, device_mesh=True)
    assert not any(routed)
    assert mpit.pvar("dev_coll_fallback_nbc").read() >= f0 + 4


# ---------------------------------------------------------------------------
# blocking alltoall(v) correctness sweep through the coll API
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.int32, np.float32, np.uint16])
@pytest.mark.parametrize("shape", ["uniform", "skew", "zero"])
def test_blocking_alltoallv_sweep(dtype, shape):
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")

    def app(comm):
        p, r = comm.size, comm.rank
        counts = _count_matrix(p, shape)
        send, scounts, rcounts, expect = _v_bufs(p, r, counts, dtype)
        sd = np.concatenate(([0], np.cumsum(scounts)[:-1])).tolist()
        rd = np.concatenate(([0], np.cumsum(rcounts)[:-1])).tolist()
        recv = np.zeros(max(1, sum(rcounts)), dtype)
        comm.alltoallv(send, scounts, sd, recv, rcounts, rd)
        np.testing.assert_array_equal(recv[:sum(rcounts)], expect)

    run_ranks(4, app, device_mesh=True)


@pytest.mark.parametrize("c", [1, 16, 33])   # straddles chunk edges
def test_blocking_alltoall_shapes(c):
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")

    def app(comm):
        p, r = comm.size, comm.rank
        send = np.array([r * p + j for j in range(p)],
                        np.int32).repeat(c)
        got = comm.alltoall(send)
        np.testing.assert_array_equal(
            got, np.array([s * p + r for s in range(p)],
                          np.int32).repeat(c))

    run_ranks(N_RANKS, app, device_mesh=True)


# ---------------------------------------------------------------------------
# persistent collectives: exec-cache pre-warm + cheap starts
# ---------------------------------------------------------------------------

def test_persistent_allreduce_warm_start_exec_cache(ddir):
    """MPI_Allreduce_init pre-warms the device program through the
    daemon exec-cache seam: the cold job's init BUILDS and caches
    (exec_cache_misses moves), the second job's init fetches the
    serialized executable instead of compiling (exec_cache_hits moves
    — the measurably-cheaper path by construction) and every start()
    rides the device NBC tier (dev_persistent_starts)."""
    _reload(MV2T_DAEMON="1", MV2T_DAEMON_DIR=ddir,
            MV2T_DAEMON_EXEC_CACHE="1", MV2T_DEVICE_COLL_MIN_BYTES="1")
    p0 = mpit.pvar("dev_persistent_starts").read()

    def app(comm):
        x = np.arange(256, dtype=np.float32) + comm.rank
        out = np.zeros_like(x)
        req = comm.allreduce_init(x, out)
        for _ in range(3):
            req.start()
            req.wait()
            np.testing.assert_array_equal(
                out, (np.arange(256, dtype=np.float32) * comm.size
                      + sum(range(comm.size))))
        req.free()

    m0 = mpit.pvar("exec_cache_misses").read()
    run_ranks(2, app, device_mesh=True)          # cold: builds + caches
    starts_cold = mpit.pvar("dev_persistent_starts").read()
    assert starts_cold >= p0 + 2 * 3, "starts did not ride device NBC"
    assert mpit.pvar("exec_cache_misses").read() > m0
    h0 = mpit.pvar("exec_cache_hits").read()
    run_ranks(2, app, device_mesh=True)          # warm: deserialize
    assert mpit.pvar("exec_cache_hits").read() > h0
    assert mpit.pvar("dev_persistent_starts").read() >= starts_cold + 6


def test_persistent_alltoallv_starts():
    """alltoallv_init: the counts matrix is cross-rank state so init
    cannot pre-build, but every start() still routes device NBC."""
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")
    p0 = mpit.pvar("dev_persistent_starts").read()

    def app(comm):
        p, r = comm.size, comm.rank
        counts = _count_matrix(p, "skew")
        send, scounts, rcounts, expect = _v_bufs(p, r, counts, np.int32)
        recv = np.zeros(max(1, sum(rcounts)), np.int32)
        req = comm.alltoallv_init(send, scounts, None, recv, rcounts,
                                  None)
        for _ in range(2):
            recv[:] = 0
            req.start()
            req.wait()
            np.testing.assert_array_equal(recv[:sum(rcounts)], expect)
        req.free()

    run_ranks(4, app, device_mesh=True)
    assert mpit.pvar("dev_persistent_starts").read() >= p0 + 4 * 2


# ---------------------------------------------------------------------------
# chaos: rank death mid-flight unwinds survivor DAGs, no leaked state
# ---------------------------------------------------------------------------

def _chaos_mid_icoll(nr, victim, coll):
    outcome = {}

    def app(comm):
        p, r = comm.size, comm.rank
        if r == victim:
            time.sleep(0.5)     # survivors deposit + park in wait first
            raise RuntimeError("chaos: victim dies mid i-collective")
        if coll == "iallreduce":
            x = np.ones(64, np.int32)
            req = comm.iallreduce(x, np.zeros_like(x))
        elif coll == "ialltoallv":
            counts = _count_matrix(p, "skew")
            send, sc, rc, _ = _v_bufs(p, r, counts, np.int32)
            req = comm.ialltoallv(send, sc, None,
                                  np.zeros(max(1, sum(rc)), np.int32),
                                  rc, None)
        else:
            send = np.zeros(p * 4, np.int32)
            req = comm.ialltoall(send, np.zeros_like(send))
        assert getattr(req, "device_nbc", False)
        try:
            req.wait()
            outcome[r] = "completed"
        except MPIException as e:
            outcome[r] = e.error_class

    with pytest.raises(RuntimeError):
        run_ranks(nr, app, device_mesh=True, timeout=60)
    assert outcome and all(v == MPIX_ERR_PROC_FAILED
                           for v in outcome.values()), outcome
    assert mpit.pvar("nbc_scheds_active").read() == 0, \
        "leaked parked NBC schedule after unwind"


def test_rank_death_mid_ialltoall_unwinds():
    """Tier-1 seeded chaos case: victim dies while survivors are parked
    in wait() on a device ialltoall — every survivor unwinds with
    MPIX_ERR_PROC_FAILED and no schedule leaks."""
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")
    _chaos_mid_icoll(4, 1, "ialltoall")


def test_rank_death_mid_persistent_start_unwinds():
    """Tier-1 seeded: a completed persistent round, then the victim
    dies before the next start — survivors' start()+wait() unwinds with
    MPIX_ERR_PROC_FAILED; no leaked schedules."""
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")
    outcome = {}

    def app(comm):
        p, r = comm.size, comm.rank
        x = np.arange(32, dtype=np.float32) + r
        out = np.zeros_like(x)
        req = comm.allreduce_init(x, out)
        req.start()
        req.wait()                      # round 1: everyone alive
        np.testing.assert_array_equal(
            out, np.arange(32, dtype=np.float32) * p + sum(range(p)))
        if r == 2:
            time.sleep(0.5)
            raise RuntimeError("chaos: victim dies before restart")
        try:
            req.start()
            req.wait()
            outcome[r] = "completed"
        except MPIException as e:
            outcome[r] = e.error_class

    with pytest.raises(RuntimeError):
        run_ranks(4, app, device_mesh=True, timeout=60)
    assert outcome and all(v == MPIX_ERR_PROC_FAILED
                           for v in outcome.values()), outcome
    assert mpit.pvar("nbc_scheds_active").read() == 0


@pytest.mark.chaos
@pytest.mark.parametrize("coll", ["iallreduce", "ialltoall",
                                  "ialltoallv"])
@pytest.mark.parametrize("victim", [0, 1, 3])
def test_chaos_matrix_mid_icoll(coll, victim):
    """Full victim x op matrix (runtests --chaos lane)."""
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")
    _chaos_mid_icoll(4, victim, coll)


# ---------------------------------------------------------------------------
# observability: gated lat_dev_nbc histogram + trace instants
# ---------------------------------------------------------------------------

def _nbc_app_with_tracecap(seen):
    def app(comm):
        x = np.arange(256, dtype=np.int32) + comm.rank
        out = np.zeros_like(x)
        req = comm.iallreduce(x, out)
        assert getattr(req, "device_nbc", False)
        req.wait()
        comm.barrier()      # every rank's polls have landed their segments
        # a segment's issue/complete instants go to the tracer of the
        # rank whose poll launched / landed it — whichever got there
        # first — so the lane is the union over ranks
        tr = comm.u.engine.tracer
        if tr is not None:
            seen[comm.rank] = {e[2] for e in tr.tail(100000)
                               if e[1] == "device"}
    return app


def test_nbc_device_observability(monkeypatch):
    """MV2T_METRICS=1 records the lat_dev_nbc histogram per completed
    segment; the device trace lane carries nbc_dev_issue/complete
    instants."""
    monkeypatch.setenv("MV2T_TRACE", "1")
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1",
            MV2T_DEVICE_NBC_SEG_BYTES="256", MV2T_METRICS="1")
    h = mpit.pvar("lat_dev_nbc")
    c0 = h.count
    seen = {}
    run_ranks(2, _nbc_app_with_tracecap(seen), device_mesh=True)
    assert h.count > c0, "lat_dev_nbc histogram did not record"
    assert {"nbc_dev_issue", "nbc_dev_complete"} <= set().union(
        *seen.values()), seen


def test_nbc_histogram_gated_off():
    """MV2T_METRICS=0: the telemetry gate stays disarmed and the
    lat_dev_nbc histogram records nothing."""
    from mvapich2_tpu import metrics as metrics_mod
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1", MV2T_METRICS="0")
    live_prev, metrics_mod.LIVE = metrics_mod.LIVE, None
    h = mpit.pvar("lat_dev_nbc")
    c0 = h.count
    try:
        def app(comm):
            x = np.ones(256, np.int32)
            out = np.zeros_like(x)
            req = comm.iallreduce(x, out)
            req.wait()

        run_ranks(2, app, device_mesh=True)
        assert h.count == c0, "histogram recorded under MV2T_METRICS=0"
    finally:
        metrics_mod.LIVE = live_prev
