"""Multi-tenant warm-attach node daemon (runtime/daemon.py) + churn
bench smoke.

Unit level: claim/release/epoch protocol, per-geometry set instances
under the admission quota, bounded FIFO claim queue, versioned
handshake (v2 upgrade-in-place, future refusal), reset zeroing,
stale-epoch sweep, crash-mid-claim recovery (MV2T_FAULTS=claim:crash),
exec-cache hit/miss/invalidation, SCM_RIGHTS listener handoff.

End to end: two OVERLAPPING jobs of different geometries warm-attach
concurrently from one daemon; the serve loop idle-expires without ever
reaping a held set (the no-reap-under-concurrency regression); the
churn bench (serial + concurrent) stays wired. The full overlap matrix
at higher job counts rides the ``chaos`` marker.
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from mvapich2_tpu.runtime import daemon  # noqa: E402


def _reload(**env):
    for k, v in env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    from mvapich2_tpu.utils.config import get_config
    get_config().reload()


@pytest.fixture()
def ddir():
    d = tempfile.mkdtemp(prefix="mv2t-daemon-test-")
    # unit tests drive the manifest protocol directly — no serve loop
    _reload(MV2T_DAEMON_SPAWN="0")
    yield d
    _reload(MV2T_DAEMON_SPAWN=None, MV2T_DAEMON_NSETS=None,
            MV2T_DAEMON_QUOTA=None, MV2T_DAEMON=None,
            MV2T_DAEMON_EXEC_CACHE=None, MV2T_DAEMON_DIR=None)
    shutil.rmtree(d, ignore_errors=True)


def test_claim_creates_and_epochs(ddir):
    c = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    assert c is not None and c.epoch == 1
    assert c.setkey == f"{c.geokey}-i0"
    # flags = pad8(2) + 2 lease stamps + 2 x 16 fpc-mirror slots
    # (runtime/boot.py flags_len — the ISSUE 10 counter tail)
    for p, want in ((c.ring, 4 << 20), (c.flags, 8 + 16 + 256),
                    (c.flat, 0), (c.arena, 4096 + 2 * (1 << 20))):
        assert os.path.getsize(p) == want, p
    daemon.release(c)
    c2 = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    assert c2 is not None and c2.epoch == 2
    assert c2.setkey == c.setkey, "released instance is reused"
    daemon.release(c2)


def test_concurrent_claims_same_geometry(ddir):
    """The multi-tenant core: a second overlapping job of the SAME
    geometry claims a second set instance instead of serializing."""
    a = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    b = daemon.claim(2, 1 << 20, 1 << 20, ddir, wait_s=0.2)
    assert a is not None and b is not None
    assert a.geokey == b.geokey and a.setkey != b.setkey
    assert a.ring != b.ring, "instances must map disjoint files"
    daemon.release(a)
    daemon.release(b)


def test_nsets_bound_queues_then_times_out(ddir):
    """Instances are bounded by MV2T_DAEMON_NSETS: past the bound a
    claim queues (daemon_queue_waits pvar) and times out to None —
    private segments, never an error."""
    from mvapich2_tpu import mpit
    _reload(MV2T_DAEMON_NSETS="1")
    waits0 = mpit.pvar("daemon_queue_waits").read()
    a = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    assert a is not None
    b = daemon.claim(2, 1 << 20, 1 << 20, ddir, wait_s=0.2)
    assert b is None
    assert mpit.pvar("daemon_queue_waits").read() == waits0 + 1
    with open(os.path.join(ddir, "manifest.json")) as f:
        assert json.load(f)["queue"] == [], "timed-out waiter dequeued"
    daemon.release(a)


def test_quota_queues_and_grants_on_release(ddir):
    """A claim past MV2T_DAEMON_QUOTA parks in the FIFO queue and is
    granted when capacity frees (the no-hang shape)."""
    _reload(MV2T_DAEMON_QUOTA="1")
    a = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    assert a is not None
    got = {}

    def waiter():
        got["cl"] = daemon.claim(3, 1 << 20, 1 << 20, ddir, wait_s=10)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.15)          # the waiter is parked in the queue
    daemon.release(a)
    t.join(timeout=15)
    assert got["cl"] is not None, "queued waiter was never granted"
    daemon.release(got["cl"])


def test_claim_resets_previous_epoch(ddir):
    c = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    with open(c.ring, "r+b") as f:
        f.write(b"\xab" * 4096)   # stale protocol words from this epoch
    daemon.release(c)
    c2 = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    with open(c2.ring, "rb") as f:
        assert f.read(4096) == b"\x00" * 4096, \
            "claim must never expose the previous epoch's words"
    daemon.release(c2)


def test_stale_epoch_sweep(ddir):
    c = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    # simulate a SIGKILLed owner: mark the set busy under a dead pid
    with daemon._manifest_txn(ddir) as m:
        m["sets"][c.setkey]["owner_pid"] = 2 ** 22 + 12345
    assert daemon.sweep(ddir) == 1
    c2 = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    assert c2 is not None and c2.epoch == c.epoch + 1
    daemon.release(c2)


def test_dead_owner_reclaimed_at_claim(ddir):
    """No sweep in between: with every instance held by dead owners
    (NSETS=1 pins one instance), the claim itself reclaims the stale
    epoch."""
    _reload(MV2T_DAEMON_NSETS="1")
    c = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    with daemon._manifest_txn(ddir) as m:
        m["sets"][c.setkey]["owner_pid"] = 2 ** 22 + 54321
    c2 = daemon.claim(2, 1 << 20, 1 << 20, ddir, wait_s=2)
    assert c2 is not None and c2.epoch == c.epoch + 1
    assert c2.setkey == c.setkey
    daemon.release(c2)


def test_crash_mid_claim_recovery(ddir):
    """MV2T_FAULTS=claim:crash kills the claimer between the grant
    transaction and its attach — the exact window the stale-epoch
    sweep must recover. The next claim reclaims the set."""
    code = (
        "from mvapich2_tpu.utils.config import get_config\n"
        "get_config().reload()\n"
        "from mvapich2_tpu import faults\n"
        "faults.configure(0)\n"
        "from mvapich2_tpu.runtime import daemon\n"
        f"daemon.claim(2, 1 << 20, 1 << 20, {ddir!r})\n"
        "raise SystemExit('fault did not fire')\n")
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 MV2T_FAULTS="claim:crash", MV2T_DAEMON_SPAWN="0"),
        capture_output=True, text=True)
    assert r.returncode == 17, f"crash kind exits 17: {r.stderr}"
    with open(os.path.join(ddir, "manifest.json")) as f:
        s = list(json.load(f)["sets"].values())[0]
    assert s["state"] == "busy" and not daemon._alive(s["owner_pid"])
    c = daemon.claim(2, 1 << 20, 1 << 20, ddir, wait_s=2)
    assert c is not None and c.epoch == 2, \
        "stale epoch of the crashed claimer must be reclaimed"
    daemon.release(c)


def test_version_handshake_refuses_future(ddir):
    c = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    daemon.release(c)
    with daemon._manifest_txn(ddir) as m:
        m["version"] = daemon.MANIFEST_VERSION + 1
    assert daemon.claim(2, 1 << 20, 1 << 20, ddir, wait_s=0.2) is None


def test_v2_manifest_upgraded_in_place(ddir):
    """A pre-multi-tenant (v2) manifest is adopted under the flock:
    sets re-key to instance 0, epochs survive, v3 fields appear."""
    geo = "n2-r1048576-p1048576"
    files = {k: os.path.join(ddir, f"{geo}.{k}")
             for k in ("ring", "flags", "flat", "flat2", "arena")}
    for p in files.values():
        open(p, "wb").close()
    with open(os.path.join(ddir, "manifest.json"), "w") as f:
        json.dump({"version": 2, "daemon_pid": 0, "sets": {
            geo: {"state": "free", "epoch": 7, "owner_pid": 0,
                  "files": files,
                  "sizes": {k: 0 for k in files}}}}, f)
    c = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    assert c is not None and c.setkey == f"{geo}-i0" and c.epoch == 8
    with open(os.path.join(ddir, "manifest.json")) as f:
        m = json.load(f)
    assert m["version"] == daemon.MANIFEST_VERSION
    assert "exec_epoch" in m and "queue" in m
    daemon.release(c)


def test_geometry_keys_are_disjoint(ddir):
    a = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    b = daemon.claim(4, 1 << 20, 1 << 20, ddir)
    assert a is not None and b is not None
    assert a.geokey != b.geokey and a.ring != b.ring
    daemon.release(a)
    daemon.release(b)


def test_status_cli(ddir):
    c = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    st = daemon.status(ddir)
    assert st["sets"][c.setkey]["state"] == "busy"
    assert st["daemon_alive"] is False
    assert "exec_cache" in st
    daemon.release(c)


# -- executable cache ----------------------------------------------------

def test_exec_cache_hit_miss_invalidation(ddir):
    """The epoch discipline applied to executables: get/put roundtrip,
    key separation, and a reset (epoch bump) that makes every old
    entry a miss — with the hits/misses/bytes pvars counting."""
    from mvapich2_tpu import mpit
    h0 = mpit.pvar("exec_cache_hits").read()
    m0 = mpit.pvar("exec_cache_misses").read()
    assert daemon.exec_cache_get("k1", ddir) is None          # miss
    assert daemon.exec_cache_put("k1", b"artifact-1", ddir)
    assert daemon.exec_cache_get("k1", ddir) == b"artifact-1"  # hit
    assert daemon.exec_cache_get("k2", ddir) is None           # miss
    assert mpit.pvar("exec_cache_hits").read() == h0 + 1
    assert mpit.pvar("exec_cache_misses").read() == m0 + 2
    assert mpit.pvar("exec_cache_bytes").read() >= 10
    old_epoch = daemon.exec_cache_epoch(ddir)
    assert daemon.exec_cache_reset(ddir) == old_epoch + 1
    assert daemon.exec_cache_get("k1", ddir) is None, \
        "a stale-epoch artifact must never be served"
    st = daemon.exec_cache_stats(ddir)
    assert st["entries"] == 0, "reset sweeps the stale files"


def test_exec_cache_gating(ddir):
    """exec_cache_enabled follows MV2T_DAEMON + MV2T_DAEMON_EXEC_CACHE
    (the coll/device.py builds consult it before touching the dir)."""
    _reload(MV2T_DAEMON=None, MV2T_DAEMON_EXEC_CACHE=None)
    assert not daemon.exec_cache_enabled()
    _reload(MV2T_DAEMON="1")
    assert daemon.exec_cache_enabled()
    _reload(MV2T_DAEMON_EXEC_CACHE="0")
    assert not daemon.exec_cache_enabled()


def test_exec_cache_device_build_roundtrip(ddir):
    """End to end through coll/device.py: the first device-collective
    program build of a 'process' populates the cache, a fresh channel
    (the next process) hits it, and an epoch reset invalidates — on
    the CPU/interpreter path of this host."""
    import numpy as np

    from mvapich2_tpu import mpit
    from mvapich2_tpu.runtime.universe import run_ranks
    # force the device transport: the committed CPU tuning profile
    # routes host-staged buffers to the host path at these sizes, and
    # this test is about the BUILD cost, not the crossover
    _reload(MV2T_DAEMON="1", MV2T_DAEMON_DIR=ddir,
            MV2T_DAEMON_EXEC_CACHE="1", MV2T_ALLREDUCE_ALGO="device")

    def app(comm):
        x = np.full(16384, float(comm.rank + 1), np.float32)
        out = comm.allreduce(x)
        assert out[0] == sum(range(1, comm.size + 1))

    h0 = mpit.pvar("exec_cache_hits").read()
    run_ranks(4, app, device_mesh=True)
    assert daemon.exec_cache_stats(ddir)["entries"] >= 1, \
        "first build must populate the cache"
    run_ranks(4, app, device_mesh=True)   # fresh channels: cache hit
    assert mpit.pvar("exec_cache_hits").read() > h0
    daemon.exec_cache_reset(ddir)
    m0 = mpit.pvar("exec_cache_misses").read()
    run_ranks(4, app, device_mesh=True)
    assert mpit.pvar("exec_cache_misses").read() > m0, \
        "epoch reset must invalidate (miss + repopulate)"
    _reload(MV2T_DAEMON_DIR=None, MV2T_ALLREDUCE_ALGO=None)


def _mesh_allreduce(comm):
    import numpy as np
    out = comm.allreduce(np.full(16384, float(comm.rank + 1), np.float32))
    assert out[0] == sum(range(1, comm.size + 1))


def _slot_alltoall(comm):
    import numpy as np
    n = comm.size * 256
    x = np.arange(n, dtype=np.float32) + 10000.0 * comm.rank
    out = np.asarray(comm.alltoall(x))
    want = np.concatenate([
        np.arange(comm.rank * 256, (comm.rank + 1) * 256,
                  dtype=np.float32) + 10000.0 * s
        for s in range(comm.size)])
    assert out.shape == (n,) and np.array_equal(out, want)


def _fold_allreduce(comm):
    import jax
    import numpy as np
    x = jax.device_put(np.full(16384, float(comm.rank + 1), np.float32),
                       comm.device_channel.device)
    out = np.asarray(comm.allreduce(x))
    assert out[0] == sum(range(1, comm.size + 1))


@pytest.mark.parametrize("ranks,ndev,app,exports", [
    (4, 4, _mesh_allreduce, True), (8, 1, _slot_alltoall, True),
    # the fused program's interpreted slot reduction resists export on
    # the CPU: its key is asked for and nothing is there to load
    (8, 4, _fold_allreduce, False)],
    ids=["mesh-allreduce", "slot-alltoall", "fold-allreduce"])
def test_exec_cache_parent_artifact_is_never_offered(ddir, monkeypatch,
                                                     ranks, ndev, app,
                                                     exports):
    """A program's signature can change under an unchanged (name, n,
    dtype, op, root, extra): the mesh programs' operand became flat
    (ISSUE 29, key ``mv2t-exec-v1`` -> ``v2``), the slot channel's
    alltoall and reduce_scatter_block return one output per rank where
    they returned one array (ISSUE 33, ``v2`` -> ``v3``; the parent's
    artifact runs on the same operands without an error and its one
    array would be shared out as every rank's result), the mesh
    channel's reduce_scatter_block became the ring kernel where it was
    XLA's psum_scatter (ISSUE 42, ``v3`` -> ``v4``; the parent's
    artifact is right and is not the program the call counts itself
    as), the fold channel's fused program hands its ``k`` operands to
    the ring, whose rounds fold them, where it held the slot-reduce
    kernel and then the ring (ISSUE 49, ``v4`` -> ``v5``; the parent's
    two-kernel artifact is right too, and is not what
    ``dev_fold_in_ring`` says ran), the mesh channel's bcast became the
    streaming chain where it was a one-hot psum (ISSUE 51, ``v5`` ->
    ``v6``; the parent's artifact gives the same bits from an
    all-reduce, and is not what ``dev_coll_tier_hbm`` says ran). An
    artifact a parent of any of these
    changes exported on this
    machine is never asked for
    and never deserialized, whatever else of its key matches; the
    second job, which does load what the first exported, is still
    right."""
    import jax

    from mvapich2_tpu.ops import _compat
    from mvapich2_tpu.parallel.mesh import make_mesh
    from mvapich2_tpu.runtime.universe import run_ranks
    _reload(MV2T_DAEMON="1", MV2T_DAEMON_DIR=ddir,
            MV2T_DAEMON_EXEC_CACHE="1", MV2T_ALLREDUCE_ALGO="device",
            MV2T_DEVICE_COLL_MIN_BYTES="1")
    mesh = make_mesh((ndev,), ("x",), jax.devices()[:ndev])
    asked, offered = [], []
    get, load = daemon.exec_cache_get, _compat.deserialize_executable
    monkeypatch.setattr(daemon, "exec_cache_get",
                        lambda k, *a: asked.append(k) or get(k, *a))
    monkeypatch.setattr(_compat, "deserialize_executable",
                        lambda b: offered.append(b) or load(b))

    run_ranks(ranks, app, device_mesh=mesh)
    assert asked and all(k.startswith("mv2t-exec-v6|") for k in asked)
    poison = b"artifact of a parent's program"
    for k in set(asked):    # the parents' keys for the same signature
        for old in ("mv2t-exec-v1|", "mv2t-exec-v2|", "mv2t-exec-v3|",
                    "mv2t-exec-v4|", "mv2t-exec-v5|"):
            assert daemon.exec_cache_put(
                k.replace("mv2t-exec-v6|", old, 1), poison, ddir)
    del asked[:]
    run_ranks(ranks, app, device_mesh=mesh)     # fresh channels ask again
    assert asked and all(k.startswith("mv2t-exec-v6|") for k in asked)
    assert bool(offered) == exports and poison not in offered
    _reload(MV2T_DAEMON_DIR=None, MV2T_ALLREDUCE_ALGO=None,
            MV2T_DEVICE_COLL_MIN_BYTES=None)


# -- listener handoff ----------------------------------------------------

def test_take_listener_scm_rights(ddir):
    """The serve loop hands a pre-bound listening TCP socket over
    SCM_RIGHTS; without a daemon the call returns None (private bind,
    bit-identical to MV2T_DAEMON=0)."""
    import socket as socketlib
    assert daemon.take_listener(ddir) is None    # nobody serving
    p = subprocess.Popen(
        [sys.executable, "-m", "mvapich2_tpu.runtime.daemon",
         "--serve", "--dir", ddir, "--idle", "60"],
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 MV2T_DAEMON_SPAWN="0"))
    try:
        sock_path = os.path.join(ddir, "daemon.sock")
        for _ in range(200):
            if os.path.exists(sock_path):
                break
            time.sleep(0.05)
        lst = daemon.take_listener(ddir, geokey="n2-test")
        assert lst is not None, "daemon must serve a listener"
        host, port = lst.getsockname()[:2]
        assert port > 0
        c = socketlib.create_connection((host, port), timeout=5)
        conn, _ = lst.accept()
        conn.sendall(b"ok")
        assert c.recv(2) == b"ok"
        c.close()
        conn.close()
        lst.close()
    finally:
        subprocess.run(
            [sys.executable, "-m", "mvapich2_tpu.runtime.daemon",
             "--stop", "--dir", ddir],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=60)
        p.wait(timeout=30)


# -- serve loop: expiry is concurrency-safe ------------------------------

def test_serve_loop_idle_expiry(ddir):
    """The serve loop exits after the idle timeout and unlinks free
    sets (run with a subsecond budget; no background daemon left)."""
    c = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    daemon.release(c)
    rc = subprocess.run(
        [sys.executable, "-m", "mvapich2_tpu.runtime.daemon", "--serve",
         "--dir", ddir, "--idle", "0.1"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert rc.returncode == 0, rc.stderr
    assert not os.path.exists(c.ring)
    with open(os.path.join(ddir, "manifest.json")) as f:
        m = json.load(f)
    assert m["daemon_pid"] == 0 and m["sets"] == {}


def test_serve_never_reaps_held_set(ddir):
    """The no-reap-under-concurrency regression (model mutation
    expiry_checks_set0): the serve loop's idle-exit teardown
    (daemon._expire_idle — the exact code serve() runs) must leave a
    held set intact even when free sibling sets in the same manifest
    made the daemon decide to expire; only the free siblings go."""
    held = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    free = daemon.claim(3, 1 << 20, 1 << 20, ddir)
    daemon.release(free)
    with daemon._manifest_txn(ddir) as m:
        m["daemon_pid"] = os.getpid()    # adopt as the serving daemon
    assert daemon._expire_idle(ddir, os.getpid())
    assert os.path.exists(held.ring), \
        "expiry reaped a live job's segment files"
    with open(os.path.join(ddir, "manifest.json")) as f:
        m = json.load(f)
    assert held.setkey in m["sets"], "held set must survive expiry"
    assert free.setkey not in m["sets"], "free sibling is expired"
    assert not os.path.exists(free.ring)
    daemon.release(held)


def test_serve_stays_up_while_held_or_queued(ddir):
    """Idle expiry must not fire while a set is held: a serve with a
    tiny idle budget keeps running until the claim is released."""
    c = daemon.claim(2, 1 << 20, 1 << 20, ddir)
    p = subprocess.Popen(
        [sys.executable, "-m", "mvapich2_tpu.runtime.daemon", "--serve",
         "--dir", ddir, "--idle", "0.6"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    try:
        time.sleep(2.5)
        assert p.poll() is None, \
            "serve idle-expired while a claim was held"
        assert os.path.exists(c.ring)
        daemon.release(c)
        p.wait(timeout=60)
        assert p.returncode == 0
        assert not os.path.exists(c.ring), "released set expired"
    finally:
        if p.poll() is None:
            p.kill()


# -- end to end ----------------------------------------------------------

def _run_job(env_extra, argv, np_=2, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-m", "mvapich2_tpu.run", "-np", str(np_),
         *argv],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=timeout)


def test_warm_attach_two_jobs_reuse_segments(tmp_path):
    """End to end: two sequential np2 jobs with MV2T_DAEMON=1 share one
    segment set (epoch 1 then 2), and the second job's collectives are
    correct on the reused (reset) segments."""
    d = str(tmp_path / "dd")
    prog = os.path.join(REPO, "tests", "progs", "lazywire_prog.py")
    env = {"MV2T_DAEMON": "1", "MV2T_DAEMON_DIR": d,
           "MV2T_DAEMON_SPAWN": "0"}
    for i in (1, 2):
        r = _run_job(env, [sys.executable, prog, "flat"])
        assert r.returncode == 0, \
            f"job {i}: stdout={r.stdout}\nstderr={r.stderr}"
        assert "No Errors" in r.stdout
    with open(os.path.join(d, "manifest.json")) as f:
        m = json.load(f)
    sets = list(m["sets"].values())
    assert len(sets) == 1, "both jobs must reuse ONE geometry instance"
    assert sets[0]["epoch"] == 2
    assert sets[0]["state"] == "free"


def test_overlapping_jobs_two_geometries_e2e(tmp_path):
    """ISSUE 14 acceptance: two OVERLAPPING jobs of different
    geometries (np2 + np3) warm-attach concurrently from one daemon
    manifest — both run collectives to completion, each on its own
    set instance."""
    d = str(tmp_path / "dd")
    prog = os.path.join(REPO, "tests", "progs", "lazywire_prog.py")
    env = {"MV2T_DAEMON": "1", "MV2T_DAEMON_DIR": d,
           "MV2T_DAEMON_SPAWN": "0"}
    results = {}

    def job(np_):
        results[np_] = _run_job(env, [sys.executable, prog, "flat"],
                                np_=np_)

    ts = [threading.Thread(target=job, args=(n,)) for n in (2, 3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for np_, r in results.items():
        assert r.returncode == 0, \
            f"np{np_}: stdout={r.stdout}\nstderr={r.stderr}"
        assert "No Errors" in r.stdout
    with open(os.path.join(d, "manifest.json")) as f:
        m = json.load(f)
    assert m["version"] == daemon.MANIFEST_VERSION
    geos = {s["geokey"] for s in m["sets"].values()}
    assert len(geos) == 2, \
        f"expected two geometry sets in one manifest: {m['sets']}"
    assert all(s["state"] == "free" and s["epoch"] >= 1
               for s in m["sets"].values())


def test_daemon_off_is_default_path(tmp_path):
    """MV2T_DAEMON unset: no daemon dir is created or touched."""
    d = str(tmp_path / "dd")
    prog = os.path.join(REPO, "tests", "progs", "lazywire_prog.py")
    r = _run_job({"MV2T_DAEMON_DIR": d}, [sys.executable, prog, "flat"])
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert not os.path.exists(d)


def test_churn_smoke(tmp_path):
    """Tier-1 churn-bench smoke: a few Init/Finalize cycles complete
    through the launcher with the daemon on and off, and report a
    positive cycles/s (the full measurement lives in the BENCH_CHURN
    artifact)."""
    from mvapich2_tpu.bench.churn import churn_rate
    prog = os.path.join(REPO, "tests", "progs", "churn_cycle_prog.py")
    env = {"MV2T_DAEMON_DIR": str(tmp_path / "dd"),
           "MV2T_DAEMON_SPAWN": "0", "JAX_PLATFORMS": "cpu"}
    for dm in (0, 1):
        r = churn_rate([sys.executable, prog], np_=2, cycles=2,
                       daemon=dm, env_extra=env, timeout=240)
        assert r["cps"] > 0 and r["cycles"] == 2, r


def test_churn_concurrent_smoke(tmp_path):
    """The many-jobs-in-flight scenario stays wired: 2 jobs of 2
    geometries overlapping against one daemon dir, cps and the p99
    attach latency reported."""
    from mvapich2_tpu.bench.churn import churn_concurrent
    prog = os.path.join(REPO, "tests", "progs", "churn_cycle_prog.py")
    env = {"MV2T_DAEMON_DIR": str(tmp_path / "dd"),
           "MV2T_DAEMON_SPAWN": "0", "JAX_PLATFORMS": "cpu"}
    r = churn_concurrent([sys.executable, prog], geometries=(2, 3),
                         jobs=2, inflight=2, env_extra=env,
                         timeout=240)
    assert r["cps"] > 0 and r["p99_s"] >= r["p50_s"] > 0, r


@pytest.mark.chaos
def test_overlapping_jobs_full_matrix(tmp_path):
    """Chaos lane: 6 overlapping jobs over np{2,3} against one daemon
    under a tight quota — admission queues, nobody fails, every set
    ends free."""
    from mvapich2_tpu.bench.churn import churn_concurrent
    prog = os.path.join(REPO, "tests", "progs", "churn_cycle_prog.py")
    d = str(tmp_path / "dd")
    env = {"MV2T_DAEMON_DIR": d, "MV2T_DAEMON_SPAWN": "0",
           "MV2T_DAEMON_QUOTA": "2", "JAX_PLATFORMS": "cpu"}
    r = churn_concurrent([sys.executable, prog], geometries=(2, 3),
                         jobs=6, inflight=3, env_extra=env,
                         timeout=600)
    assert r["cps"] > 0, r
    with open(os.path.join(d, "manifest.json")) as f:
        m = json.load(f)
    assert all(s["state"] == "free" for s in m["sets"].values())
    assert m["queue"] == []
