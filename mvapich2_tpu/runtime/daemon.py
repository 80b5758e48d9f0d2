"""Multi-tenant warm-attach node service: segment sets, executables
and bootstrap sockets that outlive jobs.

The attach-not-construct startup model (the process-in-process
multi-object blueprint, PAPERS.md) applied three times over: serving-
scale traffic churns MPI worlds constantly, so the per-node state every
job rebuilds is instead kept alive by a persistent per-node daemon and
*claimed* by arriving jobs:

  * **segment sets** — the shm ring/flags/flat/flat2/arena files of one
    geometry. The manifest holds up to ``MV2T_DAEMON_NSETS`` independent
    *instances* per geometry key under a node-wide admission quota
    (``MV2T_DAEMON_QUOTA``), so overlapping jobs — same geometry or
    different — claim concurrently instead of serializing on one
    flock'd cycle. Claims past the quota enter a bounded FIFO queue
    rather than being refused; a timed-out waiter falls back to private
    per-job segments. The invariant set (per-set exclusivity, per-set
    epoch freshness, admission <= quota, no-reap, no-hang) is
    exhaustively model-checked in ``analysis/model/daemon.py`` — the
    model is extended in lockstep with every protocol change here.
  * **device executables** — a cache of serialized traced+compiled
    programs (``jax.export``) keyed on (kernel, shape, mesh, jax/profile
    fingerprint), populated by ``coll/device.py``'s program builds
    through the ``ops/_compat.py`` export seam, so the first device
    collective of a new process deserializes instead of paying jax
    tracing + Mosaic compile. Invalidation rides the same epoch
    discipline as the segment reset: entries are named under the
    manifest's ``exec_epoch``; a reset bumps the epoch so stale
    artifacts can never load, and the serve loop sweeps them.
  * **bootstrap listen sockets** — the serve loop pre-binds listening
    TCP sockets and hands them to claiming jobs over a unix socket with
    SCM_RIGHTS (``take_listener``), so multi-node bootstrap wiring also
    attaches instead of constructing (transport/tcp.py adopts one when
    the daemon is on).

Protocol (filesystem for claims — a claim must survive a dead daemon
and a dead claimer; the socket handoff is serve-loop-only and
best-effort):

  <dir>/manifest.json     {"version", "daemon_pid", "exec_epoch",
                           "qseq", "queue": [{"pid","geokey","seq"}],
                           "sets": {setkey: {"geokey", "state":
                            free|busy, "epoch", "owner_pid",
                            "files": {...}, "sizes": {...}}}}
  <dir>/manifest.lock     flock serializing every manifest transaction
  <dir>/<geokey>-i<k>.{ring,flags,flat,flat2,arena}
  <dir>/exec-cache/<sha>-e<exec_epoch>.exe
  <dir>/daemon.sock       listener handoff (serve loop only)

* **versioned handshake**: manifest version + the geometry key
  (``n<local>-r<ring_bytes>-p<part_bytes>``) must match exactly or the
  claim fails and the job constructs private segments (bit-identical
  to MV2T_DAEMON=0). Older manifests this daemon understands are
  upgraded in place under the flock.
* **admission**: a claim is granted only while busy sets stay within
  the quota AND no earlier waiter is queued (FIFO); otherwise the
  claimer parks in the bounded queue and retries until its deadline.
* **epoch**: bumped on every claim; travels in the leader's boot card
  so every attacher of a set agrees on which incarnation it maps.
* **stale-epoch sweep**: a busy set whose owner pid is dead is
  reclaimed — at the next claim, and by the daemon's sweep loop, which
  also prunes dead queue entries and rides the existing arena sweep
  (``ShmArena.sweep_stale``) for legacy per-job segments.
* **reset**: a claim truncates every file to zero and back to size —
  O(resident pages) on tmpfs — so stale ring heads / flat seq stamps /
  spill counters from the previous epoch can never be read as live
  protocol state. ``exec_cache_reset`` is the same discipline for the
  executable cache: bump ``exec_epoch``, never serve the old words.
* **no-reap**: neither idle expiry nor the serve teardown ever unlinks
  a set a live job holds, regardless of how many sibling sets are in
  flight (the concurrency case is in the model's mutation matrix).

Module import stays stdlib-only: ``claim``/``release``/``take_listener``
run inside MPI_Init's light boot (tests/test_cabi.py guards the import
graph). The serve loop may import heavier modules lazily — it runs in
its own process, never on a rank's init path.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import socket
import sys
import threading
import time
from typing import Dict, List, Optional

from .. import mpit
from ..utils.config import cvar, get_config
from ..utils.mlog import get_logger

log = get_logger("daemon")

# Serving-fabric observability (predeclared in mpit.py — the early-
# declaration contract; fetched here by full signature, the faults/
# lockorder idiom, so the module also lints standalone). mpit sits on
# the stdlib-only light-boot path already (faults -> mpit).
pv_claims_active = mpit.pvar(
    "daemon_claims_active", mpit.PVAR_CLASS_LEVEL, "runtime",
    "warm-attach segment-set claims this process currently holds")
pv_queue_waits = mpit.pvar(
    "daemon_queue_waits", mpit.PVAR_CLASS_COUNTER, "runtime",
    "claims that entered the daemon's bounded admission queue")
pv_cache_hits = mpit.pvar(
    "exec_cache_hits", mpit.PVAR_CLASS_COUNTER, "runtime",
    "device-executable cache hits (deserialize instead of "
    "trace+compile)")
pv_cache_misses = mpit.pvar(
    "exec_cache_misses", mpit.PVAR_CLASS_COUNTER, "runtime",
    "device-executable cache misses (absent or stale-epoch entry)")
pv_cache_bytes = mpit.pvar(
    "exec_cache_bytes", mpit.PVAR_CLASS_COUNTER, "runtime",
    "serialized executable bytes written into the exec-cache")

cvar("DAEMON_DIR", "", str, "runtime",
     "Directory holding the warm-attach daemon's manifest and segment "
     "sets. Empty = /dev/shm/mv2t-daemon-<uid> (tmpdir fallback).")
cvar("DAEMON_IDLE_S", 600.0, float, "runtime",
     "Serve loop: exit after this many seconds with no busy set and no "
     "queued waiter, unlinking free sets. 0 = never exit.")
cvar("DAEMON_SPAWN", 1, int, "runtime",
     "Auto-spawn the serve loop from the first claim when none is "
     "running. 0 = claims still work against the manifest, but nothing "
     "sweeps or expires the directory and no listener handoff runs.")
# The admission/cache knobs are owned by mpit.py (the early-
# declaration contract: MPI_T enumerates the serving-fabric knobs
# before any heavy import); declared here as well — idempotent, the
# boot.py pattern — because claim()/exec_cache_enabled() are reached
# from paths that may import neither mpit's surface nor boot.
cvar("DAEMON", 0, int, "runtime",
     "Warm-attach startup: node leaders claim pre-provisioned shm "
     "segment sets from the per-node daemon instead of constructing "
     "them (see runtime/boot.py, the owning declaration).")
cvar("DAEMON_NSETS", 4, int, "runtime",
     "Maximum segment-set instances per geometry key (see mpit.py, "
     "the owning declaration).")
cvar("DAEMON_QUOTA", 8, int, "runtime",
     "Node-wide admission quota on busy segment sets (see mpit.py, "
     "the owning declaration).")
cvar("DAEMON_EXEC_CACHE", 1, int, "runtime",
     "Device-executable cache in the daemon dir (see mpit.py, the "
     "owning declaration).")

MANIFEST_VERSION = 3     # v3: per-geometry set instances + admission
                         # queue + exec_epoch (the multi-tenant layout)

# Claim admission bounds. The queue wait is a deadline, not a retry
# count: a waiter that cannot be admitted within _CLAIM_WAIT_S falls
# back to private segments (bit-identical to MV2T_DAEMON=0), so a
# wedged daemon dir can never park MPI_Init.
_CLAIM_WAIT_S = 5.0
_CLAIM_POLL_S = 0.02
_QUEUE_SLACK = 4         # queue bound = quota + slack (see claim())

_SEG_KINDS = ("ring", "flags", "flat", "flat2", "arena")


def default_dir() -> str:
    d = str(get_config().get("DAEMON_DIR", "") or "")
    if d:
        return d
    base = "/dev/shm" if os.path.isdir("/dev/shm") else None
    if base is None:
        import tempfile
        base = tempfile.gettempdir()
    return os.path.join(base, f"mv2t-daemon-{os.getuid()}")


def _geokey(n_local: int, ring_bytes: int, part_bytes: int) -> str:
    return f"n{n_local}-r{ring_bytes}-p{part_bytes}"


def _alive(pid: int) -> bool:
    if not pid:
        return False
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except OSError:
        return True     # alive but not ours


@contextlib.contextmanager
def _manifest_txn(dir_: str):
    """flock'd read-modify-write window over the manifest. Yields the
    manifest dict; mutations are persisted on clean exit."""
    import fcntl
    os.makedirs(dir_, exist_ok=True)
    with open(os.path.join(dir_, "manifest.lock"), "w") as lockf:
        fcntl.flock(lockf, fcntl.LOCK_EX)
        try:
            path = os.path.join(dir_, "manifest.json")
            try:
                with open(path) as f:
                    m = json.load(f)
            except (OSError, ValueError):
                m = _fresh_manifest()
            yield m
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(m, f)
            os.replace(tmp, path)   # readers never see a torn manifest
        finally:
            import fcntl as _f
            _f.flock(lockf, _f.LOCK_UN)


def _fresh_manifest() -> dict:
    return {"version": MANIFEST_VERSION, "daemon_pid": 0,
            "exec_epoch": 1, "qseq": 0, "queue": [], "sets": {}}


def _upgrade_manifest(m: dict, dir_: str) -> bool:
    """In-place upgrade of an older manifest this daemon understands
    (returns False when the version is unknown/newer — the claim
    refuses and the job constructs private segments). Runs under the
    manifest flock, so mixed-version claimers serialize: once upgraded,
    an old claimer sees version 3 and degrades cleanly."""
    v = m.get("version")
    if v == MANIFEST_VERSION:
        return True
    if v not in (1, 2):
        return False
    # proto: manifest-v2
    # (the single-instance layout: sets keyed by bare geokey, no
    # admission queue, no exec cache. Re-key every set to instance 0
    # of its geometry and provision the v3 fields.)
    sets = {}
    for key, s in m.get("sets", {}).items():
        s.setdefault("geokey", key)
        if "flat2" not in s.get("files", {}):  # proto: manifest-v1
            # pre-v2 set surviving a daemon version adoption: provision
            # the flat2 segment in place (the claim's reset zeroes it
            # like every other file)
            p = os.path.join(dir_, f"{key}.flat2")
            fd = os.open(p, os.O_CREAT | os.O_RDWR, 0o600)
            os.close(fd)
            s["files"]["flat2"] = p
        sets[f"{key}-i0"] = s
    m["sets"] = sets
    m.setdefault("exec_epoch", 1)
    m.setdefault("qseq", 0)
    m.setdefault("queue", [])
    m["version"] = MANIFEST_VERSION
    return True


class Claim:
    """One claimed segment-set instance (held by a job's node leader)."""

    __slots__ = ("dir", "geokey", "setkey", "epoch", "ring", "flags",
                 "flat", "flat2", "arena", "part_bytes")

    def __init__(self, dir_: str, geokey: str, setkey: str, epoch: int,
                 files: Dict[str, str], part_bytes: int):
        self.dir = dir_
        self.geokey = geokey
        self.setkey = setkey
        self.epoch = epoch
        self.ring = files["ring"]
        self.flags = files["flags"]
        self.flat = files["flat"]
        self.flat2 = files["flat2"]
        self.arena = files["arena"]
        self.part_bytes = part_bytes


def _reset_file(path: str, size: int, prefault: bool = False) -> None:
    """Zero a segment file: drop every page, then restore the size.
    ``prefault`` (the ring) zero-WRITES instead of ftruncate-sparse —
    the datapath's hot loops would otherwise pay a page fault per
    4 KiB until the ring first wraps (see runtime/boot.py
    write_zeros); everything else re-zero-fills lazily."""
    os.truncate(path, 0)
    if not size:
        return
    if prefault:
        from .boot import write_zeros
        fd = os.open(path, os.O_WRONLY)
        try:
            write_zeros(fd, size)
        finally:
            os.close(fd)
    else:
        os.truncate(path, size)


def _set_sizes(n_local: int, ring_bytes: int, part_bytes: int) -> dict:
    from .boot import flags_len
    hdr = (n_local * n_local * 8 + 4095) & ~4095   # arena spill grid
    return {"ring": n_local * n_local * ring_bytes,
            "flags": flags_len(n_local),
            "flat": 0,       # cp_flat_attach(create=1) sizes it
            "flat2": 0,      # cp_flat2_attach(create=1) sizes it
            "arena": hdr + n_local * part_bytes}


def _busy_count(m: dict) -> int:
    return sum(1 for s in m.get("sets", {}).values()
               if s.get("state") == "busy")


def _prune_queue(m: dict) -> None:
    m["queue"] = [q for q in m.get("queue", []) if _alive(q.get("pid"))]


def _provision_set(m: dict, dir_: str, geokey: str, sizes: dict,
                   nsets: int) -> Optional[str]:
    """Create the next free instance slot of ``geokey`` (files + manifest
    entry); returns its setkey, or None when all ``nsets`` instances
    exist."""
    for i in range(nsets):
        setkey = f"{geokey}-i{i}"
        if setkey in m["sets"]:
            continue
        files = {k: os.path.join(dir_, f"{setkey}.{k}")
                 for k in _SEG_KINDS}
        for k, p in files.items():
            fd = os.open(p, os.O_CREAT | os.O_RDWR, 0o600)
            os.ftruncate(fd, sizes[k])
            os.close(fd)
        m["sets"][setkey] = {"geokey": geokey, "state": "free",
                             "epoch": 0, "owner_pid": 0,
                             "files": files, "sizes": sizes}
        return setkey
    return None


def _grantable(m: dict, geokey: str, quota: int) -> Optional[str]:
    """The setkey this claimer may take right now: a free instance of
    its geometry, or a busy one whose owner died (the at-claim stale
    sweep), admission quota permitting. None = must wait/provision."""
    stale = None
    for setkey, s in m["sets"].items():
        if s.get("geokey") != geokey:
            continue
        if s["state"] == "free":
            if _busy_count(m) < quota:
                return setkey
            return None      # instance free but node at quota
        if not _alive(s["owner_pid"]) and stale is None:
            stale = setkey   # reclaim frees capacity, always admissible
    return stale


def claim(n_local: int, ring_bytes: int, part_bytes: int,
          dir_: Optional[str] = None,
          wait_s: Optional[float] = None) -> Optional[Claim]:
    """Claim (creating on first use) a segment-set instance for this
    geometry. Busy instances under the admission quota are queued for
    up to ``wait_s`` (default 5 s) in FIFO order; None means the wait
    timed out, the queue is full, or the manifest speaks an unknown
    version — callers fall back to private per-job segments."""
    dir_ = dir_ or default_dir()
    t_enter = time.monotonic()
    t_queued = None           # set when this claimer joins the queue
    deadline = t_enter + (_CLAIM_WAIT_S if wait_s is None
                          else float(wait_s))
    cfg = get_config()
    nsets = max(1, int(cfg.get("DAEMON_NSETS", 4) or 1))
    quota = max(1, int(cfg.get("DAEMON_QUOTA", 8) or 1))
    key = _geokey(n_local, ring_bytes, part_bytes)
    sizes = _set_sizes(n_local, ring_bytes, part_bytes)
    me = os.getpid()
    queued = False
    out: Optional[Claim] = None
    try:
        # bounded: every lap re-checks the deadline; a waiter that
        # cannot be admitted in time degrades to private segments
        while True:   # proto: bounded-by(claim-wait-deadline)
            with _manifest_txn(dir_) as m:
                if not _upgrade_manifest(m, dir_):
                    log.warn("daemon manifest version %s unknown "
                             "(mine: %s); not claiming",
                             m.get("version"), MANIFEST_VERSION)
                    return None
                _prune_queue(m)
                qpids = [q["pid"] for q in m["queue"]]
                head = (not qpids) or qpids[0] == me
                setkey = _grantable(m, key, quota) if head else None
                if setkey is None and head \
                        and _busy_count(m) < quota:
                    setkey = _provision_set(m, dir_, key, sizes, nsets)
                if setkey is not None:
                    s = m["sets"][setkey]
                    if s["state"] == "busy":
                        # stale epoch: the owner died without releasing
                        log.info("sweeping stale epoch %d of %s (dead "
                                 "owner %d)", s["epoch"], setkey,
                                 s["owner_pid"])
                    # reset BEFORE publishing the claim: no attacher may
                    # ever read the previous epoch's protocol words
                    for k, p in s["files"].items():
                        _reset_file(p, sizes[k], prefault=(k == "ring"))
                    s["sizes"] = sizes
                    s["state"] = "busy"
                    s["owner_pid"] = me
                    s["epoch"] = int(s["epoch"]) + 1
                    if queued:
                        m["queue"] = [q for q in m["queue"]
                                      if q["pid"] != me]
                    out = Claim(dir_, key, setkey, s["epoch"],
                                s["files"], part_bytes)
                elif not queued:
                    if len(m["queue"]) >= quota + _QUEUE_SLACK:
                        log.warn("daemon admission queue full (%d); "
                                 "private segments", len(m["queue"]))
                        return None
                    m["qseq"] = int(m.get("qseq", 0)) + 1
                    m["queue"].append({"pid": me, "geokey": key,
                                       "seq": m["qseq"]})
                    queued = True
                    t_queued = time.monotonic()
                    pv_queue_waits.inc()
            if out is not None:
                break
            if time.monotonic() >= deadline:
                with _manifest_txn(dir_) as m:
                    m["queue"] = [q for q in m.get("queue", [])
                                  if q.get("pid") != me]
                log.info("daemon claim wait for %s timed out; private "
                         "segments", key)
                return None
            time.sleep(_CLAIM_POLL_S)
    except OSError as e:
        log.warn("daemon claim failed (%s); private segments", e)
        return None
    pv_claims_active.inc()
    # attach/queue latency distributions for the node exporter: entry->
    # grant, and (only when this claimer actually queued) queue->grant.
    # ensure_live here — claim runs inside MPI_Init's light boot, ahead
    # of the universe's trace-attach phase
    from .. import metrics as _metrics
    mx = _metrics.ensure_live()
    if mx is not None:
        t_grant = time.monotonic()
        mx.rec_us("lat_daemon_attach", (t_grant - t_enter) * 1e6)
        if t_queued is not None:
            mx.rec_us("lat_daemon_queue", (t_grant - t_queued) * 1e6)
    if os.environ.get("MV2T_" + "FAULTS"):
        # crash-mid-claim site: the grant is published, the claimer has
        # not yet attached — exactly the window the stale-epoch sweep
        # must recover (import-gated like the boot-path sites)
        from .. import faults
        faults.fire("claim")
    if int(get_config().get("DAEMON_SPAWN", 1) or 0):
        ensure_daemon(dir_)
    return out


def release(cl: Claim) -> None:
    """Return a claimed set (job Finalize). Safe to call once per
    claim; a crashed owner is handled by the stale-epoch sweep."""
    try:
        with _manifest_txn(cl.dir) as m:
            s = m.get("sets", {}).get(cl.setkey)
            if s is not None and s.get("epoch") == cl.epoch:
                s["state"] = "free"
                s["owner_pid"] = 0
                pv_claims_active.inc(-1)
    except OSError as e:
        log.warn("daemon release failed (%s)", e)


def sweep(dir_: Optional[str] = None) -> int:
    """Free busy sets whose owner died (the stale-epoch sweep) and
    prune dead queue entries. Returns how many sets were reclaimed."""
    dir_ = dir_ or default_dir()
    n = 0
    try:
        with _manifest_txn(dir_) as m:
            for key, s in m.get("sets", {}).items():
                if s["state"] == "busy" and not _alive(s["owner_pid"]):
                    s["state"] = "free"
                    s["owner_pid"] = 0
                    n += 1
            _prune_queue(m)
    except OSError:
        pass
    return n


# ---------------------------------------------------------------------------
# device-executable cache (the PiP attach-not-construct model applied
# to compiled programs; populated by coll/device.py via the
# ops/_compat.py export seam)
# ---------------------------------------------------------------------------

def exec_cache_enabled() -> bool:
    cfg = get_config()
    return bool(int(cfg.get("DAEMON", 0) or 0)
                and int(cfg.get("DAEMON_EXEC_CACHE", 1) or 0))


def exec_cache_dir(dir_: Optional[str] = None) -> str:
    d = os.path.join(dir_ or default_dir(), "exec-cache")
    os.makedirs(d, exist_ok=True)
    return d


def exec_cache_epoch(dir_: Optional[str] = None) -> int:
    """Current cache epoch — one manifest.json read, no lock (the
    epoch only ever grows; a racing reset makes a get a miss, never a
    stale hit, because the epoch is part of the entry filename)."""
    try:
        with open(os.path.join(dir_ or default_dir(),
                               "manifest.json")) as f:
            return int(json.load(f).get("exec_epoch", 1))
    except (OSError, ValueError):
        return 1


def _exec_entry_path(key: str, epoch: int,
                     dir_: Optional[str] = None) -> str:
    h = hashlib.sha256(key.encode()).hexdigest()[:24]
    return os.path.join(exec_cache_dir(dir_), f"{h}-e{epoch}.exe")


def exec_cache_get(key: str,
                   dir_: Optional[str] = None) -> Optional[bytes]:
    """Serialized executable for ``key`` at the current cache epoch, or
    None (counted as a miss). Stale-epoch entries can never match: the
    epoch is baked into the entry name — the truncate-reset discipline
    applied to executables."""
    try:
        path = _exec_entry_path(key, exec_cache_epoch(dir_), dir_)
        with open(path, "rb") as f:
            blob = f.read()
        pv_cache_hits.inc()
        return blob
    except OSError:
        pv_cache_misses.inc()
        return None


def exec_cache_put(key: str, blob: bytes,
                   dir_: Optional[str] = None) -> bool:
    """Store a serialized executable under the current epoch
    (atomic tmp+rename; concurrent writers of one key converge on
    identical content)."""
    try:
        path = _exec_entry_path(key, exec_cache_epoch(dir_), dir_)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
        pv_cache_bytes.inc(len(blob))
        return True
    except OSError as e:
        log.dbg(1, "exec-cache put failed (%s)", e)
        return False


def exec_cache_reset(dir_: Optional[str] = None) -> int:
    """Invalidate the whole cache: bump the manifest epoch (old entries
    can never load again) and unlink the stale files. Returns the new
    epoch."""
    dir_ = dir_ or default_dir()
    with _manifest_txn(dir_) as m:
        _upgrade_manifest(m, dir_)
        m["exec_epoch"] = int(m.get("exec_epoch", 1)) + 1
        epoch = m["exec_epoch"]
    _exec_cache_sweep(dir_, epoch)
    return epoch


def _exec_cache_sweep(dir_: str, epoch: int) -> int:
    """Unlink cache entries not of ``epoch`` (serve loop + reset)."""
    n = 0
    try:
        d = exec_cache_dir(dir_)
        for name in os.listdir(d):
            if name.endswith(f"-e{epoch}.exe") or name.endswith(".tmp"):
                continue
            try:
                os.unlink(os.path.join(d, name))
                n += 1
            except OSError:
                pass
    except OSError:
        pass
    return n


def exec_cache_stats(dir_: Optional[str] = None) -> dict:
    """{entries, bytes, epoch} from one directory scan (mpistat /
    watchdog rows; nothing here touches the job)."""
    dir_ = dir_ or default_dir()
    entries = nbytes = 0
    try:
        d = os.path.join(dir_, "exec-cache")
        for name in os.listdir(d):
            if not name.endswith(".exe"):
                continue
            entries += 1
            try:
                nbytes += os.path.getsize(os.path.join(d, name))
            except OSError:
                pass
    except OSError:
        pass
    return {"entries": entries, "bytes": nbytes,
            "epoch": exec_cache_epoch(dir_)}


# ---------------------------------------------------------------------------
# bootstrap listener handoff (SCM_RIGHTS over <dir>/daemon.sock)
# ---------------------------------------------------------------------------

_SOCK_NAME = "daemon.sock"
_LISTEN_POOL = 4


def _sock_path(dir_: str) -> str:
    return os.path.join(dir_, _SOCK_NAME)


def take_listener(dir_: Optional[str] = None,
                  geokey: str = "",
                  timeout: float = 0.25) -> Optional[socket.socket]:
    """A pre-bound, listening TCP socket from the serve loop's pool
    (SCM_RIGHTS), or None when no daemon serves here — callers bind
    their own, bit-identical to MV2T_DAEMON=0. ``geokey`` tags the
    request for the daemon's per-geometry accounting only; the sockets
    are interchangeable (bound to 127.0.0.1, ephemeral port)."""
    dir_ = dir_ or default_dir()
    try:
        with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as c:
            c.settimeout(timeout)
            c.connect(_sock_path(dir_))
            c.sendall(json.dumps({"op": "listener",
                                  "geokey": geokey}).encode() + b"\n")
            msg, fds, _flags, _addr = socket.recv_fds(c, 16, 1)
            if not fds:
                return None
            lst = socket.socket(fileno=fds[0])
            for extra in fds[1:]:
                os.close(extra)
            if msg.strip() != b"OK":
                lst.close()
                return None
            return lst
    except (OSError, ValueError):
        return None


class _ListenerServer:
    """Serve-loop half of the handoff: a pool of pre-bound listening
    TCP sockets behind the unix socket, replenished as they are handed
    out. All state is private to the daemon process."""

    def __init__(self, dir_: str):
        self.dir = dir_
        self.path = _sock_path(dir_)
        self.handed = 0
        self.by_geo: Dict[str, int] = {}
        self._pool: List[socket.socket] = []
        self._stop = threading.Event()
        try:
            os.unlink(self.path)
        except OSError:
            pass
        self._srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._srv.bind(self.path)
        self._srv.listen(16)
        self._srv.settimeout(0.5)
        self._fill()
        self._thread = threading.Thread(target=self._serve, daemon=True,
                                        name="daemon-listener-handoff")
        self._thread.start()

    def _fill(self) -> None:
        while len(self._pool) < _LISTEN_POOL:
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", 0))
            s.listen(128)
            self._pool.append(s)

    def _serve(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            with conn:
                try:
                    conn.settimeout(0.5)
                    req = json.loads(conn.makefile().readline() or "{}")
                    if req.get("op") == "metrics":
                        # node metrics exporter verb: the whole node
                        # aggregate (manifest occupancy/queue, exec
                        # cache, merged per-job rank histograms) as one
                        # JSON blob or Prometheus text exposition —
                        # read-only, nothing the jobs can observe
                        conn.settimeout(5.0)
                        try:
                            from ..metrics import export as _export
                            snap = _export.node_snapshot(
                                daemon_dir=self.dir)
                            if str(req.get("fmt", "json")) in (
                                    "prom", "prometheus"):
                                payload = _export.to_prometheus(snap)
                            else:
                                payload = json.dumps(snap) + "\n"
                        except Exception as e:
                            payload = json.dumps(
                                {"error": str(e)}) + "\n"
                        conn.sendall(payload.encode())
                        continue
                    if req.get("op") != "listener":
                        continue
                    if not self._pool:
                        self._fill()
                    lst = self._pool.pop(0)
                    socket.send_fds(conn, [b"OK"], [lst.fileno()])
                    lst.close()          # the job owns the fd now
                    self.handed += 1
                    geo = str(req.get("geokey", "") or "?")
                    self.by_geo[geo] = self.by_geo.get(geo, 0) + 1
                    self._fill()
                except (OSError, ValueError):
                    continue

    def close(self) -> None:
        self._stop.set()
        try:
            self._srv.close()
        except OSError:
            pass
        for s in self._pool:
            try:
                s.close()
            except OSError:
                pass
        self._pool.clear()
        try:
            os.unlink(self.path)
        except OSError:
            pass


# ---------------------------------------------------------------------------
# daemon lifecycle
# ---------------------------------------------------------------------------

def ensure_daemon(dir_: Optional[str] = None) -> bool:
    """Spawn the serve loop when none is running. Returns True when a
    daemon is (now) alive. The spawn is detached and best-effort — a
    claim never depends on it."""
    dir_ = dir_ or default_dir()
    try:
        with _manifest_txn(dir_) as m:
            if _alive(m.get("daemon_pid", 0)):
                return True
    except OSError:
        return False
    try:
        import subprocess
        # the daemon is host-only: never let it reach for a chip
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        # ranks export MV2T_RANK etc.; the daemon is node-scoped, not a
        # rank — scrub job identity so nothing in it boots as one
        for k in ("MV2T_RANK", "MV2T_SIZE", "MV2T_KVS", "MV2T_FT",
                  "MV2T_WORLD_BASE"):
            env.pop(k, None)
        with open(os.devnull, "rb") as nullin, \
                open(os.devnull, "ab") as nullout:
            subprocess.Popen(
                [sys.executable, "-m", "mvapich2_tpu.runtime.daemon",
                 "--serve", "--dir", dir_],
                stdin=nullin, stdout=nullout, stderr=nullout,
                start_new_session=True, env=env)
        return True
    except OSError as e:
        log.warn("could not spawn warm-attach daemon (%s)", e)
        return False


def serve(dir_: Optional[str] = None,
          idle_s: Optional[float] = None) -> int:
    """The daemon body: adopt (and upgrade) the manifest, serve the
    listener-handoff socket, then loop — stale-epoch sweep, queue
    prune, exec-cache epoch sweep, legacy segment sweep — until idle
    (no busy set AND no live waiter) for DAEMON_IDLE_S."""
    dir_ = dir_ or default_dir()
    idle_s = float(get_config().get("DAEMON_IDLE_S", 600.0)
                   if idle_s is None else idle_s)
    with _manifest_txn(dir_) as m:
        if _alive(m.get("daemon_pid", 0)) \
                and m["daemon_pid"] != os.getpid():
            log.info("daemon already serving (pid %d)", m["daemon_pid"])
            return 0
        _upgrade_manifest(m, dir_)
        m["version"] = MANIFEST_VERSION
        m["daemon_pid"] = os.getpid()
        exec_epoch = int(m.get("exec_epoch", 1))
    try:
        handoff: Optional[_ListenerServer] = _ListenerServer(dir_)
    except OSError as e:
        log.warn("listener handoff unavailable (%s); claims still "
                 "served", e)
        handoff = None
    log.info("multi-tenant node daemon serving %s (pid %d)", dir_,
             os.getpid())
    last_busy = time.monotonic()
    last_legacy = 0.0
    try:
        while True:
            time.sleep(0.5)
            busy = False
            try:
                with _manifest_txn(dir_) as m:
                    if m.get("daemon_pid") != os.getpid():
                        return 0    # replaced (e.g. --stop + respawn)
                    for s in m.get("sets", {}).values():
                        if s["state"] == "busy":
                            if _alive(s["owner_pid"]):
                                busy = True
                            else:
                                s["state"] = "free"
                                s["owner_pid"] = 0
                    _prune_queue(m)
                    if m["queue"]:
                        busy = True   # live waiters hold the daemon up
                    exec_epoch = int(m.get("exec_epoch", 1))
            except OSError:
                pass
            now = time.monotonic()
            if busy:
                last_busy = now
            if now - last_legacy > 30.0:
                last_legacy = now
                _exec_cache_sweep(dir_, exec_epoch)
                try:
                    # ride the existing arena sweep for crashed per-job
                    # segments outside the daemon dir (lazy import:
                    # numpy lives in the daemon process only, never on
                    # a rank's light-boot path)
                    from ..transport.arena import ShmArena
                    from .boot import shm_base_dir
                    ShmArena.sweep_stale(shm_base_dir())
                except Exception:
                    pass
            if idle_s > 0 and now - last_busy > idle_s:
                break
    finally:
        if handoff is not None:
            handoff.close()
    if not _expire_idle(dir_, os.getpid()):
        return 0
    log.info("multi-tenant node daemon idle-expired; freed %s", dir_)
    return 0


def _expire_idle(dir_: str, daemon_pid: int) -> bool:
    """The idle-exit teardown, factored out so the no-reap guard is
    directly regression-testable: drop and unlink every set NOT held
    by a live owner; a busy set with a live claimer survives — even
    when sibling sets/claims made the daemon think itself idle (the
    expiry_checks_set0 model mutation). False = this daemon was
    replaced; nothing touched."""
    with _manifest_txn(dir_) as m:
        if m.get("daemon_pid") != daemon_pid:
            return False
        m["daemon_pid"] = 0
        for key, s in list(m.get("sets", {}).items()):
            if s["state"] == "busy" and _alive(s["owner_pid"]):
                continue     # never pull a live job's mapping (no-reap)
            for p in s["files"].values():
                try:
                    os.unlink(p)
                except OSError:
                    pass
            # the metrics time-series segment rides beside the claimed
            # ring (created lazily by the job, not in the manifest)
            ring = s["files"].get("ring")
            if ring:
                try:
                    os.unlink(ring + ".metrics")
                except OSError:
                    pass
            del m["sets"][key]
    return True


def status(dir_: Optional[str] = None) -> dict:
    dir_ = dir_ or default_dir()
    try:
        with open(os.path.join(dir_, "manifest.json")) as f:
            m = json.load(f)
    except (OSError, ValueError):
        return {"dir": dir_, "manifest": None}
    m["daemon_alive"] = _alive(m.get("daemon_pid", 0))
    m["dir"] = dir_
    m["exec_cache"] = exec_cache_stats(dir_)
    return m


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="mvapich2-tpu multi-tenant warm-attach node daemon")
    ap.add_argument("--dir", default=None)
    ap.add_argument("--serve", action="store_true")
    ap.add_argument("--idle", type=float, default=None,
                    help="override MV2T_DAEMON_IDLE_S")
    ap.add_argument("--status", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--reset-exec-cache", action="store_true",
                    help="bump the exec-cache epoch (invalidate all "
                         "cached executables; the re-measure workflow "
                         "after a jax/profile change)")
    ap.add_argument("--stop", action="store_true")
    a = ap.parse_args(argv)
    if a.status:
        print(json.dumps(status(a.dir), indent=1))
        return 0
    if a.sweep:
        print(f"swept {sweep(a.dir)} stale set(s)")
        return 0
    if a.reset_exec_cache:
        print(f"exec-cache epoch now {exec_cache_reset(a.dir)}")
        return 0
    if a.stop:
        d = a.dir or default_dir()
        with _manifest_txn(d) as m:
            pid = m.get("daemon_pid", 0)
            m["daemon_pid"] = 0
        if _alive(pid):
            import signal
            os.kill(pid, signal.SIGTERM)
            print(f"stopped daemon pid {pid}")
        return 0
    if a.serve:
        return serve(a.dir, a.idle)
    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
