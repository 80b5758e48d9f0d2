"""Shared-memory channel for co-located rank processes.

The SMP channel (SURVEY §2.2 ch3_smp_progress.c analog): a per-node mmap'd
segment of SPSC rings for every (src, dst) pair, written by the native C++
fast path (native/shmring.cpp, loaded via ctypes). A pure-Python
implementation of the identical layout serves as fallback when the .so
can't be built. Bootstrap (who creates the segment, name exchange) rides
the KVS like everything else.

Zero-copy rendezvous: large messages use the RGET protocol with a
size-ordered handle ladder — CMA (the receiver reads the sender's user
buffer via process_vm_readv when the unanimous bootstrap probe passed),
the persistent per-node scratch arena (transport/arena.py — one block
allocation per send, reused across sends), and only as the last resort
the legacy per-send scratch file. Oversize python packets (spills) stage
through the arena too, reclaimed via its spill-consumed counters.
"""

from __future__ import annotations

import collections
import ctypes
import mmap
import os
import select
import socket
import struct
import subprocess
import tempfile
import threading
import time
import uuid
from typing import Dict, List, Optional

import numpy as np

from ..utils.config import cvar, get_config
from ..utils.mlog import get_logger
from .arena import ShmArena, cma_read
from .base import Channel, Packet, decode_packet, encode_packet

log = get_logger("shm")

cvar("SHM_RING_BYTES", 0, int, "shm",
     "Per-(src,dst)-pair ring size in bytes (analog of "
     "MV2_SMP_QUEUE_LENGTH). 0 = auto: sized by co-located rank count "
     "(4 MiB for <=2, 2 MiB for <=4, 1 MiB beyond) so a 64-deep window "
     "of eager-size payloads stays in flight without backpressure.")
cvar("USE_CPLANE", 1, int, "shm",
     "Use the native C data plane (envelope matching in C) when the native "
     "ring is available. 0 falls back to python-side matching.")
cvar("CPLANE_DEBUG", 0, int, "shm",
     "Native C-plane debug tracing to stderr (read by cplane.cpp's "
     "cp_debug() straight from the env at attach, so it must be set at "
     "launch; any non-empty value enables).")
cvar("USE_CMA", 1, int, "shm",
     "Use cross-memory-attach (process_vm_readv) for large intra-node "
     "messages when the bootstrap probe succeeds (the CMA/LiMIC2 path of "
     "ch3_smp_progress.c:525). 0 forces the staged rendezvous.")
cvar("WIRE_TIMEOUT", 120.0, float, "shm",
     "Deadline in seconds for the blocking per-node wire gate "
     "(ensure_wired): how long a collective/rendezvous entry waits for "
     "every co-located rank to publish its wiring cards before failing "
     "with MPI_ERR_INTERN. Lazy wiring only blocks where all "
     "participants are known to arrive (collectives, rendezvous).")
cvar("PEER_TIMEOUT", 10.0, float, "ft",
     "Liveness-lease timeout in seconds: a co-located peer whose "
     "heartbeat stamp (refreshed by a dedicated thread, so compute-"
     "silent ranks stay alive) goes stale past this is declared dead — "
     "blocking waits in the datapath unwind with MPIX_ERR_PROC_FAILED "
     "instead of hanging. 0 disables lease detection. Containment "
     "latency for a SIGKILLed peer is <= 2x this value.")

from .. import mpit as _mpit  # noqa: E402  (after cvar decls, same registry)

# Plane counters (the mv2_mpit.c:17-39 channel-counter analog). Declared
# at import so tools can enumerate them; finish_wiring() rebinds the
# sources to the live plane.
_PV_PLANE_DECLS = [
    ("cplane_eager_tx", "eager sends injected by the C plane"),
    ("cplane_eager_rx", "eager receives matched in the C plane"),
    ("cplane_fwd_py",
     "packets forwarded to the python protocol layer (fast-path misses)"),
    ("cplane_rndv_tx", "CMA rendezvous sends exposed by the C plane"),
    ("cplane_rndv_rx", "CMA rendezvous pulls completed by the C plane"),
]
for _n, _d in _PV_PLANE_DECLS:
    _mpit.pvar(_n, _mpit.PVAR_CLASS_COUNTER, "shm", _d)

# startup-path observability: every node wire is counted as eager
# (bootstrap/spawn forced it) or lazy (deferred to the first operation
# that needed the agreement — the on-demand CM model)
pv_wiring_eager = _mpit.pvar(
    "wiring_eager", _mpit.PVAR_CLASS_COUNTER, "shm",
    "shm channels wired eagerly at bootstrap "
    "(MV2T_LAZY_WIRING=0 or the spawn path)")
pv_wiring_lazy = _mpit.pvar(
    "wiring_lazy", _mpit.PVAR_CLASS_COUNTER, "shm",
    "shm channels wired on demand, at the first rendezvous/collective "
    "that needed the per-node agreement")

# Fast-path observability (native/mpi/fastpath.c + the flat collective
# tier in cplane.cpp). Index order mirrors cplane.cpp's FPC_* enum; the
# counters live in the plane (cp_fp_counters) so both the C ABI's
# fastpath and python-rank flat collectives feed the same slots.
_FP_COUNTERS = [
    ("fp_hits", "pt2pt operations completed on the C fast path"),
    ("fp_gil_takes",
     "python progress passes taken from the C fast path's hot loop"),
    ("fp_fallback_dtype", "fast-path fallbacks: datatype not carryable"),
    ("fp_fallback_comm", "fast-path fallbacks: comm not plane-owned"),
    ("fp_fallback_size", "fast-path fallbacks: payload above fp_threshold"),
    ("fp_fallback_plane", "fast-path fallbacks: plane missing or failed"),
    ("fp_coll_flat", "collectives completed on the flat-slot shm tier"),
    ("fp_coll_sched", "collectives completed on the C pt2pt schedules"),
    ("fp_wait_spin", "fast-path blocking waits satisfied during the spin"),
    ("fp_wait_bell",
     "fast-path blocking waits satisfied after the doorbell sleep"),
    ("fp_flat_progress",
     "python progress callbacks fired from flat-collective waits"),
    ("fp_dead_peer",
     "peers declared dead by the C-plane lease scan (flat waits and "
     "wait quanta)"),
    ("fp_coll_flat2",
     "collectives completed on the hierarchical flat tier / multicast "
     "bcast (cp_flat2_*)"),
]
for _n, _d in _FP_COUNTERS:
    _mpit.pvar(_n, _mpit.PVAR_CLASS_COUNTER, "fastpath", _d)

# ring framing + flags-segment layout constants. The C side's numbers
# live in native/shm_layout.h; the mv2tlint `native` pass checks the two
# sets byte-for-byte (MV2T_RING_HDR_BYTES <-> _HEADER, ...), so a drift
# is a lint failure instead of a silent protocol break.
_HEADER = 128            # per-ring control block (MV2T_RING_HDR_BYTES)
_WRAP = 0xFFFFFFFF       # wrap marker (MV2T_RING_WRAP)
_ALIGN = 8               # ring message alignment (MV2T_RING_ALIGN)
_LEASE_ALIGN = 8         # flags segment: pad sleep bytes to this
_LEASE_STAMP = 8         # bytes per liveness-lease stamp (u64)
_FPC_SLOTS = 16          # fast-path counter mirror slots per rank
                         # (MV2T_FPC_SLOTS — the flags-segment tail that
                         # makes fp_* counters attachable by bin/mpistat)

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_lib = None
_lib_tried = False


def _load_native():
    """Load (building if needed) the C++ ring library."""
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    # sanitizer lane (bin/runtests --tsan): every consumer in the job —
    # this ctypes loader AND fastpath.c's dlopen — must map the SAME
    # instrumented ring, so the override is one env var for both
    so = os.environ.get("MV2T_SHMRING_SO") or os.path.join(
        _REPO, "native", "libshmring.so")
    # always run make (no-op when fresh): an existence check would keep
    # loading a stale .so after shmring.cpp edits. fcntl.flock serializes
    # co-launched ranks racing on the shared build target. An override
    # points at a prebuilt variant (the sanitizer lane owns its build).
    try:
        if os.environ.get("MV2T_SHMRING_SO"):
            if not os.path.exists(so):
                raise OSError(f"MV2T_SHMRING_SO does not exist: {so}")
        else:
            import fcntl
            native_dir = os.path.join(_REPO, "native")
            with open(os.path.join(native_dir, ".build.lock"), "w") as lockf:
                fcntl.flock(lockf, fcntl.LOCK_EX)
                try:
                    subprocess.run(["make", "-C", native_dir,
                                    "libshmring.so"],
                                   capture_output=True, timeout=120,
                                   check=True)
                finally:
                    fcntl.flock(lockf, fcntl.LOCK_UN)
    except Exception as e:
        if not os.path.exists(so):
            log.warn("native shmring build failed (%s); python fallback", e)
            return None
        log.warn("shmring rebuild failed (%s); using existing .so", e)
    try:
        lib = ctypes.CDLL(so)
        lib.sr_attach.restype = ctypes.c_void_p
        lib.sr_attach.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                  ctypes.c_long, ctypes.c_int]
        lib.sr_send.restype = ctypes.c_int
        lib.sr_send.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
                                ctypes.c_char_p, ctypes.c_long]
        lib.sr_peek.restype = ctypes.c_long
        lib.sr_peek.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_int]
        lib.sr_recv.restype = ctypes.c_long
        lib.sr_recv.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
                                ctypes.c_void_p, ctypes.c_long]
        lib.sr_detach.argtypes = [ctypes.c_void_p]
        lib.sr_capacity.restype = ctypes.c_long
        lib.sr_capacity.argtypes = [ctypes.c_void_p]
        _bind_cplane(lib)
        _lib = lib
    except OSError as e:  # pragma: no cover
        log.warn("cannot load libshmring.so (%s); python fallback", e)
        _lib = None
    return _lib


def _bind_cplane(lib) -> None:
    """ctypes signatures for the native data plane (native/cplane.cpp)."""
    L = ctypes
    lib.cp_create.restype = L.c_void_p
    lib.cp_create.argtypes = [L.c_void_p, L.c_int, L.c_int, L.c_char_p]
    lib.cp_destroy.argtypes = [L.c_void_p]
    lib.cp_register_global.argtypes = [L.c_void_p]
    lib.cp_set_bell.argtypes = [L.c_void_p, L.c_int, L.c_char_p]
    lib.cp_set_world.argtypes = [L.c_void_p, L.c_int, L.c_int]
    lib.cp_set_wait_fd.argtypes = [L.c_void_p, L.c_int]
    lib.cp_ctx_enable.argtypes = [L.c_void_p, L.c_int]
    lib.cp_ctx_disable.argtypes = [L.c_void_p, L.c_int]
    lib.cp_inject.argtypes = [L.c_void_p, L.c_int, L.c_char_p, L.c_long]
    lib.cp_send_eager.restype = L.c_longlong
    lib.cp_send_eager.argtypes = [L.c_void_p, L.c_int, L.c_int, L.c_int,
                                  L.c_int, L.c_void_p, L.c_long, L.c_longlong]
    lib.cp_irecv.restype = L.c_longlong
    lib.cp_irecv.argtypes = [L.c_void_p, L.c_void_p, L.c_long, L.c_int,
                             L.c_int, L.c_int]
    lib.cp_req_state.argtypes = [L.c_void_p, L.c_longlong]
    lib.cp_req_status.argtypes = [L.c_void_p, L.c_longlong,
                                  L.POINTER(L.c_int), L.POINTER(L.c_int),
                                  L.POINTER(L.c_longlong), L.POINTER(L.c_int),
                                  L.POINTER(L.c_int)]
    lib.cp_req_buf.argtypes = [L.c_void_p, L.c_longlong,
                               L.POINTER(L.c_void_p), L.POINTER(L.c_longlong)]
    lib.cp_req_free.argtypes = [L.c_void_p, L.c_longlong]
    lib.cp_req_orphan.argtypes = [L.c_void_p, L.c_longlong]
    lib.cp_cancel_recv.argtypes = [L.c_void_p, L.c_longlong]
    lib.cp_complete_assist.argtypes = [L.c_void_p, L.c_longlong, L.c_longlong,
                                       L.c_int, L.c_int, L.c_int]
    lib.cp_error_req.argtypes = [L.c_void_p, L.c_longlong, L.c_int]
    lib.cp_advance.argtypes = [L.c_void_p]
    lib.cp_coll_gather.restype = L.c_int
    lib.cp_coll_gather.argtypes = [L.c_void_p, L.c_int, L.c_int, L.c_int,
                                   L.c_void_p, L.c_void_p, L.c_long,
                                   L.c_void_p]
    lib.cp_py_pending.argtypes = [L.c_void_p]
    lib.cp_py_peek.restype = L.c_long
    lib.cp_py_peek.argtypes = [L.c_void_p]
    lib.cp_py_pop.restype = L.c_long
    lib.cp_py_pop.argtypes = [L.c_void_p, L.c_char_p, L.c_long]
    lib.cp_assist_pending.argtypes = [L.c_void_p]
    lib.cp_assist_peek.restype = L.c_long
    lib.cp_assist_peek.argtypes = [L.c_void_p]
    lib.cp_assist_pop.restype = L.c_long
    lib.cp_assist_pop.argtypes = [L.c_void_p, L.POINTER(L.c_longlong),
                                  L.c_char_p, L.c_long]
    lib.cp_probe.argtypes = [L.c_void_p, L.c_int, L.c_int, L.c_int, L.c_int,
                             L.POINTER(L.c_int), L.POINTER(L.c_int),
                             L.POINTER(L.c_longlong), L.POINTER(L.c_longlong)]
    lib.cp_mrecv_start.restype = L.c_longlong
    lib.cp_mrecv_start.argtypes = [L.c_void_p, L.c_longlong, L.c_void_p,
                                   L.c_long]
    lib.cp_cancel_send.argtypes = [L.c_void_p, L.c_longlong, L.c_int]
    lib.cp_cancel_result.argtypes = [L.c_void_p, L.c_longlong]
    lib.cp_cancel_forget.argtypes = [L.c_void_p, L.c_longlong]
    lib.cp_mark_failed.argtypes = [L.c_void_p, L.c_int]
    lib.cp_any_failed.argtypes = [L.c_void_p]
    lib.cp_rank_failed.argtypes = [L.c_void_p, L.c_int]
    # liveness leases + flat-region forensics (failure containment)
    lib.cp_set_peer_timeout.argtypes = [L.c_void_p, L.c_longlong]
    lib.cp_lease_age_us.restype = L.c_longlong
    lib.cp_lease_age_us.argtypes = [L.c_void_p, L.c_int]
    lib.cp_lease_scan.argtypes = [L.c_void_p]
    lib.cp_flat_poisoned.argtypes = [L.c_void_p, L.c_int, L.c_int]
    lib.cp_flat_poison_region.argtypes = [L.c_void_p, L.c_int, L.c_int]
    lib.cp_flat_slot_state.argtypes = [L.c_void_p, L.c_int, L.c_int,
                                       L.c_int, L.POINTER(L.c_longlong),
                                       L.POINTER(L.c_longlong)]
    lib.cp_posted_count.argtypes = [L.c_void_p]
    lib.cp_posted_get.argtypes = [L.c_void_p, L.c_int,
                                  L.POINTER(L.c_longlong), L.POINTER(L.c_int),
                                  L.POINTER(L.c_int), L.POINTER(L.c_int)]
    lib.cp_unexpected_count.argtypes = [L.c_void_p]
    lib.cp_stats.argtypes = [L.c_void_p, L.POINTER(L.c_ulonglong),
                             L.POINTER(L.c_ulonglong),
                             L.POINTER(L.c_ulonglong)]
    lib.cp_wait_quantum.argtypes = [L.c_void_p, L.c_longlong, L.c_long,
                                    L.c_long]
    lib.cp_send_rndv.restype = L.c_longlong
    lib.cp_send_rndv.argtypes = [L.c_void_p, L.c_int, L.c_int, L.c_int,
                                 L.c_int, L.c_void_p, L.c_longlong]
    lib.cp_rndv_wire.restype = L.c_longlong
    lib.cp_rndv_wire.argtypes = [L.c_longlong]
    lib.cp_coll_tag.argtypes = [L.c_void_p, L.c_int]
    lib.cp_set_cma.argtypes = [L.c_void_p, L.c_int]
    lib.cp_cma_enabled.argtypes = [L.c_void_p]
    lib.cp_set_wired.argtypes = [L.c_void_p]
    lib.cp_wired.argtypes = [L.c_void_p]
    lib.cp_congested.argtypes = [L.c_void_p, L.c_int]
    lib.cp_rndv_stats.argtypes = [L.c_void_p, L.POINTER(L.c_ulonglong),
                                  L.POINTER(L.c_ulonglong)]
    # flat-slot collective tier + fast-path counters
    lib.cp_flat_attach.argtypes = [L.c_void_p, L.c_char_p, L.c_int]
    lib.cp_flat_ok.argtypes = [L.c_void_p]
    lib.cp_flat_disable.argtypes = [L.c_void_p]
    lib.cp_flat_base.restype = L.c_longlong
    lib.cp_flat_base.argtypes = [L.c_void_p, L.c_int, L.c_int]
    lib.cp_flat_op_ok.argtypes = [L.c_int, L.c_int]
    lib.cp_flat_payload_max.restype = L.c_long
    lib.cp_flat_nslots.restype = L.c_int
    lib.cp_flat_lanes.restype = L.c_int
    lib.cp_flat_allreduce.argtypes = [
        L.c_void_p, L.c_int, L.c_int, L.c_int, L.c_int, L.c_longlong,
        L.c_int, L.c_int, L.c_void_p, L.c_void_p, L.c_longlong,
        L.c_longlong]
    lib.cp_flat_reduce.argtypes = [
        L.c_void_p, L.c_int, L.c_int, L.c_int, L.c_int, L.c_longlong,
        L.c_int, L.c_int, L.c_int, L.c_void_p, L.c_void_p, L.c_longlong,
        L.c_longlong]
    lib.cp_flat_bcast.argtypes = [L.c_void_p, L.c_int, L.c_int, L.c_int,
                                  L.c_int, L.c_longlong, L.c_int,
                                  L.c_void_p, L.c_longlong]
    lib.cp_flat_barrier.argtypes = [L.c_void_p, L.c_int, L.c_int, L.c_int,
                                    L.c_int, L.c_longlong]
    lib.cp_flat_set_progress_cb.argtypes = [L.c_void_p, L.c_void_p]
    # hierarchical flat tier + multicast bcast (cp_flat2_*)
    lib.cp_flat2_attach.argtypes = [L.c_void_p, L.c_char_p, L.c_int]
    lib.cp_flat2_ok.argtypes = [L.c_void_p]
    lib.cp_flat2_disable.argtypes = [L.c_void_p]
    lib.cp_flat2_base.restype = L.c_longlong
    lib.cp_flat2_base.argtypes = [L.c_void_p, L.c_int, L.c_int]
    lib.cp_flat2_payload_max.restype = L.c_long
    lib.cp_flat2_group.restype = L.c_int
    lib.cp_flat2_max_ranks.restype = L.c_int
    lib.cp_flat2_lanes.restype = L.c_int
    lib.cp_flat2_poisoned.argtypes = [L.c_void_p, L.c_int, L.c_int]
    lib.cp_flat2_poison_region.argtypes = [L.c_void_p, L.c_int, L.c_int]
    lib.cp_flat2_slot_state.argtypes = [L.c_void_p, L.c_int, L.c_int,
                                        L.c_int, L.c_int,
                                        L.POINTER(L.c_longlong),
                                        L.POINTER(L.c_longlong)]
    lib.cp_flat2_allreduce.argtypes = [
        L.c_void_p, L.c_int, L.c_int, L.c_int, L.c_int, L.c_longlong,
        L.c_int, L.c_int, L.c_void_p, L.c_void_p, L.c_longlong,
        L.c_longlong]
    lib.cp_flat2_reduce.argtypes = [
        L.c_void_p, L.c_int, L.c_int, L.c_int, L.c_int, L.c_longlong,
        L.c_int, L.c_int, L.c_int, L.c_void_p, L.c_void_p, L.c_longlong,
        L.c_longlong]
    lib.cp_flat2_bcast.argtypes = [L.c_void_p, L.c_int, L.c_int, L.c_int,
                                   L.c_int, L.c_longlong, L.c_int,
                                   L.c_void_p, L.c_longlong, L.c_int]
    lib.cp_flat2_barrier.argtypes = [L.c_void_p, L.c_int, L.c_int,
                                     L.c_int, L.c_int, L.c_longlong]
    lib.cp_fp_counter.restype = L.c_ulonglong
    lib.cp_fp_counter.argtypes = [L.c_void_p, L.c_int]
    # native trace ring (MV2T_NTRACE; trace/native.py drains the file)
    lib.cp_ntrace_attach.argtypes = [L.c_void_p, L.c_char_p, L.c_int]
    lib.cp_ntrace_ok.argtypes = [L.c_void_p]
    lib.cp_ntrace_emit.argtypes = [L.c_void_p, L.c_int, L.c_longlong,
                                   L.c_longlong]


class _PyRing:
    """Pure-Python twin of the C++ layout (single segment mmap)."""

    def __init__(self, path: str, nranks: int, ring_bytes: int,
                 create: bool):
        total = nranks * nranks * ring_bytes
        flags = os.O_CREAT | os.O_RDWR if create else os.O_RDWR
        self.fd = os.open(path, flags, 0o600)
        if create:
            os.ftruncate(self.fd, total)
        self.mm = mmap.mmap(self.fd, total)
        if create:
            self.mm[:total] = b"\x00" * total
        self.nranks = nranks
        self.ring_bytes = ring_bytes
        self.cap = ring_bytes - _HEADER

    def _off(self, src: int, dst: int) -> int:
        return (src * self.nranks + dst) * self.ring_bytes

    def _head(self, off: int) -> int:
        return struct.unpack_from("<Q", self.mm, off)[0]

    def _tail(self, off: int) -> int:
        return struct.unpack_from("<Q", self.mm, off + 8)[0]

    def send(self, src: int, dst: int, payload: bytes) -> int:
        off = self._off(src, dst)
        cap = self.cap
        need = (4 + len(payload) + _ALIGN - 1) & ~(_ALIGN - 1)
        if need + _ALIGN >= cap:
            return -1
        head, tail = self._head(off), self._tail(off)
        used = tail - head
        pos = tail % cap
        contig = cap - pos
        base = off + _HEADER
        if contig < need:
            if used + contig + need > cap:
                return 0
            struct.pack_into("<I", self.mm, base + pos, _WRAP)
            tail += contig
            struct.pack_into("<Q", self.mm, off + 8, tail)
            pos = 0
        elif used + need > cap:
            return 0
        struct.pack_into("<I", self.mm, base + pos, len(payload))
        self.mm[base + pos + 4:base + pos + 4 + len(payload)] = payload
        struct.pack_into("<Q", self.mm, off + 8, tail + need)
        return 1

    def recv(self, src: int, dst: int) -> Optional[bytes]:
        off = self._off(src, dst)
        cap = self.cap
        base = off + _HEADER
        while True:
            head, tail = self._head(off), self._tail(off)
            if head == tail:
                return None
            pos = head % cap
            ln = struct.unpack_from("<I", self.mm, base + pos)[0]
            if ln == _WRAP or cap - pos < 4:
                head += cap - pos
                struct.pack_into("<Q", self.mm, off, head)
                continue
            data = bytes(self.mm[base + pos + 4:base + pos + 4 + ln])
            need = (4 + ln + _ALIGN - 1) & ~(_ALIGN - 1)
            struct.pack_into("<Q", self.mm, off, head + need)
            return data

    def close(self):
        self.mm.close()
        os.close(self.fd)


class _NativeRing:
    def __init__(self, lib, path: str, nranks: int, ring_bytes: int,
                 create: bool):
        self.lib = lib
        self.h = lib.sr_attach(path.encode(), nranks, ring_bytes,
                               1 if create else 0)
        if not self.h:
            raise OSError(f"sr_attach failed for {path}")
        self._rbuf = ctypes.create_string_buffer(ring_bytes)

    def send(self, src: int, dst: int, payload: bytes) -> int:
        return self.lib.sr_send(self.h, src, dst, payload, len(payload))

    def recv(self, src: int, dst: int) -> Optional[bytes]:
        # sr_recv itself returns <=0 on empty, so no sr_peek round-trip;
        # _rbuf is ring-sized and anything larger goes the __bigmsg__
        # path, so the buffer always fits
        got = self.lib.sr_recv(self.h, src, dst, self._rbuf, len(self._rbuf))
        if got <= 0:
            return None
        # string_at copies exactly `got` bytes; ._rbuf.raw would copy
        # the whole ring-sized buffer per message
        return ctypes.string_at(self._rbuf, got)

    def close(self):
        self.lib.sr_detach(self.h)


class ShmChannel(Channel):
    name = "shm"
    supports_rget = True

    def __init__(self, my_rank: int, local_ranks: List[int], kvs,
                 ring_bytes: Optional[int] = None, boot_card=None,
                 daemon_claim=None):
        self.my_rank = my_rank           # world rank
        self.local_ranks = sorted(local_ranks)
        self.local_index = {r: i for i, r in enumerate(self.local_ranks)}
        self.n_local = len(self.local_ranks)
        self.kvs = kvs
        # deferred card publication: everything this constructor would
        # kvs.put travels in ONE batched put_many at the end (the
        # serial-RTT collapse of the batched bootstrap)
        self._cards: Dict[str, str] = {}
        # boot_card: the node leader's light-boot segment card
        # (runtime/boot.py) — pre-created zero-filled files every rank
        # attaches without ordering on the leader's world build.
        # daemon_claim: the leader's warm-attach claim to release at
        # close (runtime/daemon.py).
        self._boot_mode = boot_card is not None
        self._daemon = bool(boot_card and boot_card.get("daemon"))
        self._daemon_claim = daemon_claim
        if ring_bytes is None:
            if boot_card is not None:
                # the leader sized the segment at light boot; geometry
                # is part of the versioned card, never recomputed
                ring_bytes = int(boot_card["ring_bytes"])
            else:
                ring_bytes = get_config()["SHM_RING_BYTES"]
            if not ring_bytes:
                # auto (the vbuf-pool sizing discipline of ibv_param.c):
                # with few co-located ranks the n^2 segment is cheap,
                # and a deeper ring keeps a 64-message window of
                # eager-size payloads in flight without backpressure
                # (64 x 64 KiB = 4 MiB). Deterministic in n_local, so
                # every rank computes the same segment layout.
                if self.n_local <= 2:
                    ring_bytes = 4 << 20
                elif self.n_local <= 4:
                    ring_bytes = 2 << 20
                else:
                    ring_bytes = 1 << 20
        ring_bytes = (ring_bytes + 7) & ~7
        leader = self.local_ranks[0]
        self._owner = my_rank == leader
        segkey = f"shm-seg-{leader}"
        if boot_card is not None:
            # pre-created at light boot: zero-filled IS the initialized
            # ring state, so every rank (owner included) attaches with
            # create=0 — no memset, no ordering
            path = boot_card["ring"]
            self._ring = self._make_ring(path, ring_bytes, create=False)
            if self._owner:
                self._cards[segkey] = path
        elif self._owner:
            base = "/dev/shm" if os.path.isdir("/dev/shm") \
                else tempfile.gettempdir()
            path = os.path.join(
                base, f"mv2t-shm-{os.getpid()}-{uuid.uuid4().hex[:8]}")
            self._ring = self._make_ring(path, ring_bytes, create=True)
            kvs.put(segkey, path)
        else:
            path = kvs.get(segkey)
            self._ring = self._make_ring(path, ring_bytes, create=False)
        self.path = path
        # -- persistent per-node scratch arena (transport/arena.py) ------
        # created (or daemon-attached) by the leader alongside the ring
        # segment; replaces the per-send scratch files for RGET exposure
        # and oversize spills. Followers attach during wiring — the
        # leader's card is guaranteed published by then — and usability
        # is agreed unanimously (like CMA) so sender and receiver always
        # dispatch handles identically.
        self.arena: Optional[ShmArena] = None
        self.cma_ok = False          # python-level CMA verdict (post-wire)
        self._arena_ready = False    # set after the unanimous agreement
        base = os.path.dirname(path)
        arena_key = f"shm-arena-{leader}"
        if self._owner:
            try:
                if self._daemon:
                    # warm attach: the claimed (reset) arena file; the
                    # zeroed spill grid is the created state
                    apath = boot_card["arena"]
                    self.arena = ShmArena(apath, self.n_local,
                                          self.local_index[my_rank],
                                          int(boot_card["part_bytes"]),
                                          create=True, exclusive=False)
                else:
                    ShmArena.sweep_stale(base)
                    apath = os.path.join(
                        base,
                        f"mv2t-arena-{os.getpid()}-{uuid.uuid4().hex[:8]}")
                    self.arena = ShmArena(apath, self.n_local,
                                          self.local_index[my_rank],
                                          create=True)
                self._cards[arena_key] = f"{apath}:{self.arena.part_bytes}"
            except Exception as e:
                log.warn("arena create failed (%s); scratch-file "
                         "rendezvous", e)
                self._cards[arena_key] = ""
        # exposure table: wire handle -> keepalive (ndarray for CMA,
        # ArenaHandle for arena blocks) — the registration-cache handle
        # table; leak-checked at close()
        self._exposed: Dict[tuple, object] = {}
        self._expose_tok = 0
        # arena-staged spill bookkeeping: dst local index -> deque of
        # (seq, ArenaHandle), reclaimed when the receiver's consumed
        # counter passes seq
        self._spill_pending: Dict[int, collections.deque] = {}
        self._spill_seq: Dict[int, int] = {}
        # spill bookkeeping lock: plane-mode sends bypass _send_lock (the
        # C injector owns ordering) but still stage spills here
        from ..analysis.lockorder import tracked
        self._spill_lock = tracked(threading.Lock(),
                                   f"shm[{my_rank}]._spill_lock")
        self._backlog: Dict[int, collections.deque] = {}
        # serializes the ring producer + backlog: the SPSC ring assumes
        # one producer per (src,dst) pair, but sends arrive from any
        # user thread (MPI-IO worker, THREAD_MULTIPLE) while poll()
        # flushes the backlog under the engine mutex. Channel-local and
        # never held across a wait, so no cross-engine cycle.
        self._send_lock = tracked(threading.Lock(),
                                  f"shm[{my_rank}]._send_lock")
        # Doorbell: a per-rank unix datagram socket. Senders fire one
        # best-effort datagram after each ring write so a receiver blocked
        # in wait_for_event wakes immediately — sched_yield on an
        # oversubscribed core only reschedules at the next tick (~350 us
        # measured), while a blocking-read wakeup is ~2 us. This is the
        # nemesis fastbox-signal discipline.
        self._bell = socket.socket(socket.AF_UNIX, socket.SOCK_DGRAM)
        bell_path = f"{path}.bell-{my_rank}"
        if len(os.fsencode(bell_path)) > 100:
            # sockaddr_un.sun_path holds 108 bytes: a segment directory
            # nested deep (a test's tmp_path, a long TMPDIR) would make
            # bind() fail and cost the job its whole shm fast path.
            # Peers learn the address from the card, so any unique
            # short name serves.
            import hashlib
            import tempfile
            bell_path = os.path.join(
                tempfile.gettempdir(), "mv2t-bell-"
                + hashlib.sha1(os.fsencode(path)).hexdigest()[:16]
                + f"-{my_rank}")
        try:
            os.unlink(bell_path)
        except OSError:
            pass
        self._bell.bind(bell_path)
        self._bell.setblocking(False)
        self._bell_path = bell_path
        self._cards[f"shm-bell-{my_rank}"] = bell_path
        # CMA probe buffer: published with the build cards; the wire
        # step reads a neighbor's copy to decide whether
        # process_vm_readv works here (kept alive for the channel
        # lifetime). Bell-card presence implies probe-card presence —
        # they ride the same batched put.
        self._cma_probe = np.frombuffer(
            f"mv2t-cma-{my_rank:012d}".encode(), dtype=np.uint8).copy()
        self._cards[f"shm-cma-{my_rank}"] = (
            f"{os.getpid()}:{self._cma_probe.ctypes.data}"
            f":{self._cma_probe.size}")
        self._peer_bells: Dict[int, str] = {}
        # liveness-lease timeout (cached: the probe runs at blocking
        # waits' sleep points; config is reloaded before channels wire)
        self._peer_timeout = float(
            get_config().get("PEER_TIMEOUT", 0.0) or 0.0)
        # Adaptive bell: a shared byte per local rank, set while that
        # rank is parked in the engine's blocking wait. Senders skip the
        # doorbell syscall (~0.15 ms on an oversubscribed host) for
        # awake receivers — those are polling anyway. The engine's
        # pre_wait (advertise) -> final poll -> sleep order makes the
        # skip race-free.
        # flags segment layout: [n_local sleep bytes][pad to 8][n_local
        # u64 liveness-lease stamps]. The lease tail is the heartbeat
        # surface of the failure-containment layer: every rank's stamp
        # is refreshed by a dedicated thread (plus the C plane's
        # advance_locked), and every blocking wait — python progress
        # waits, C flat waves, C wait quanta — scans peers' stamps
        # against MV2T_PEER_TIMEOUT so a SIGKILLed peer is a detectable
        # event instead of a hang. cplane.cpp maps the same layout.
        flags_path = boot_card["flags"] if boot_card is not None \
            else f"{path}.flags"
        lease_off = (self.n_local + _LEASE_ALIGN - 1) & ~(_LEASE_ALIGN - 1)
        flags_len = lease_off + _LEASE_STAMP * self.n_local \
            + 8 * _FPC_SLOTS * self.n_local
        if boot_card is not None:
            pass    # pre-created (zeroed) at light boot; just map it
        elif self._owner:
            # write-then-rename so followers never see a short file
            with open(flags_path + ".tmp", "wb") as f:
                f.write(b"\0" * flags_len)
            os.replace(flags_path + ".tmp", flags_path)
        else:
            deadline = time.monotonic() + 30.0
            while not (os.path.exists(flags_path)
                       and os.path.getsize(flags_path) >= flags_len):
                if time.monotonic() > deadline:
                    raise OSError(f"shm flags segment never appeared: "
                                  f"{flags_path}")
                time.sleep(0.001)
        self._flags_path = flags_path
        self._flags_f = open(flags_path, "r+b")
        self._flags = mmap.mmap(self._flags_f.fileno(), flags_len)
        self._lease = np.frombuffer(self._flags, dtype=np.uint64,
                                    count=self.n_local, offset=lease_off)
        # per-rank fast-path counter mirror (the flags-segment tail):
        # cp_create points the plane's fpctr at this rank's row, so the
        # same slots are readable here for every co-located rank — the
        # surface bin/mpistat attaches to from outside the job
        self._fpc_mirror = np.frombuffer(
            self._flags, dtype=np.uint64,
            count=self.n_local * _FPC_SLOTS,
            offset=lease_off + _LEASE_STAMP * self.n_local)
        self._lease_scan_at = 0.0      # python-probe throttle
        self._failed_seen: set = set() # C-detections already reconciled
        self._lease_stamp()
        # heartbeat thread: the stamp must stay fresh through compute-
        # silent stretches (a rank deep in user code makes no progress
        # calls), so refreshing only from the progress loop would
        # false-kill busy peers. ~10 stamps per timeout period.
        # continuous-metrics sampler state: declared BEFORE the thread
        # starts (the loop re-reads self._sampler every wake; the
        # sampler itself attaches later in __init__, after the plane)
        self._sampler = None
        self._metrics_path = f"{path}.metrics"
        self._metrics_f = None
        self._metrics_mm = None
        self._hb_stop = threading.Event()
        self._hb_thread = threading.Thread(
            target=self._hb_loop, daemon=True,
            name=f"mv2t-lease-hb-{my_rank}")
        self._hb_thread.start()
        # -- native data plane (native/cplane.cpp) -----------------------
        # C-side envelope matching for plane-owned contexts: created when
        # the native ring is live. Everything LOCAL — world map, global
        # registration for the C fast path, lease timeout, flat progress
        # hook — happens here; only the parts that need peers' cards
        # (bells, the CMA/arena/flat agreement) wait for ensure_wired().
        # Pre-wire the plane still carries eager traffic: an unset bell
        # just means a parked receiver wakes on its poll timeout.
        self.plane = None
        self._plane_recvs: Dict[int, object] = {}   # cp req id -> Request
        self._plane_cancels: Dict[int, object] = {} # sreq id -> SendRequest
        self.plane_client = None                    # Pt2ptProtocol hook
        self._ring_cap = 0
        self._flat_path = boot_card["flat"] if boot_card is not None \
            else f"{path}.fcoll"
        # hierarchical flat tier + multicast bcast segment (cp_flat2_*);
        # older boot cards / daemon manifests may predate it
        self._flat2_path = (boot_card.get("flat2")
                            if boot_card is not None else None) \
            or f"{path}.fcoll2"
        # native trace ring segment (beside the ring file; daemon mode
        # puts it beside the claimed ring, reset implicitly by the
        # monotonic timestamps — trace/native.py drops zero-ts slots)
        self._ntrace_path = f"{path}.ntrace"
        self._ntrace_f = None          # this rank's own fd on the ring
        self._flat_cb = None           # keepalive for the ctypes callback
        self.cabi_ranks = set()        # local ranks that are C-ABI procs
        if self.using_native and get_config()["USE_CPLANE"]:
            lib = self._ring.lib
            self.plane = lib.cp_create(self._ring.h, self.local_index[my_rank],
                                       self.n_local, flags_path.encode())
            self._ring_cap = lib.sr_capacity(self._ring.h)
            if self.plane:
                lib.cp_set_wait_fd(self.plane, self._bell.fileno())
                if self._owner:
                    # flat-slot collective segment (cp_flat_*): sparse
                    # per-context regions; created by the leader before
                    # its build cards publish, so followers can attach
                    # during wiring without racing the creation
                    lib.cp_flat_attach(self.plane,
                                       self._flat_path.encode(), 1)
                    # hierarchical tier segment: same sparse/idempotent
                    # creation discipline (zero IS initialized)
                    lib.cp_flat2_attach(self.plane,
                                        self._flat2_path.encode(), 1)
                for r in self.local_ranks:
                    lib.cp_set_world(self.plane, self.local_index[r], r)
                # python-rank progress hook for flat-collective waits: a
                # rank parked in a flat wave still runs forwarded python
                # work (rendezvous assists) so peers cannot deadlock.
                # Runs INSIDE cp_flat_* wait loops, so it must never
                # block (a sleep here stalls the whole node's wave).
                import ctypes as _ct

                def _flat_progress():  # mv2tlint: handler
                    from ..runtime import universe as uni
                    try:
                        u = uni.current_universe()
                        if u is not None:
                            u.engine.progress_poke()
                    except Exception:
                        pass
                self._flat_cb = _ct.CFUNCTYPE(None)(_flat_progress)
                lib.cp_flat_set_progress_cb(
                    self.plane, _ct.cast(self._flat_cb, _ct.c_void_p))
                # arm the C-side lease scans (flat waves, wait quanta)
                # with the same timeout the python probe uses
                lib.cp_set_peer_timeout(self.plane,
                                        int(self._peer_timeout * 1e6))
                lib.cp_register_global(self.plane)
                # native trace ring: armed when the MV2T_NTRACE cvar is
                # set (or follows MV2T_TRACE when left at its -1
                # default). Zero-filled is the initialized state, so
                # every rank creates/attaches without ordering; events
                # drain at Finalize into the Perfetto merge and live
                # into the watchdog/mpistat tails (trace/native.py).
                from ..trace import native as _nt
                if _nt.ntrace_enabled():
                    lib.cp_ntrace_attach(self.plane,
                                         self._ntrace_path.encode(), 1)
                    # hold our own fd on the ring: the segment OWNER
                    # unlinks the file at its close, which can precede
                    # a slower rank's Finalize drain (teardown skew) —
                    # an unlinked-but-open inode stays readable, so
                    # this rank's trace lane cannot silently vanish
                    try:
                        self._ntrace_f = open(self._ntrace_path, "rb")
                    except OSError:
                        self._ntrace_f = None
                # bind the plane counters' sources to this live plane:
                # fast-path hit-rate is the one number that says
                # whether a workload actually rides the C path — it
                # must be observable even before the node wires (eager
                # traffic flows pre-wire). Totals from earlier planes
                # in this process (latched at close) stay included.
                for idx, (name, desc) in enumerate(_PV_PLANE_DECLS):
                    pv = _mpit.pvar(name, _mpit.PVAR_CLASS_COUNTER,
                                    "shm", desc)
                    base = pv._value
                    pv.source = (lambda i=idx, b=base:
                                 b + float(self.plane_stats()[i]))
                for idx, (name, desc) in enumerate(_FP_COUNTERS):
                    pv = _mpit.pvar(name, _mpit.PVAR_CLASS_COUNTER,
                                    "fastpath", desc)
                    base = pv._value
                    pv.source = (lambda i=idx, b=base:
                                 b + float(self.fp_counter(i)))
        # -- continuous-metrics segment (<ring>.metrics) ------------------
        # per-rank time-series ring + histogram mirrors for the always-on
        # telemetry layer (mvapich2_tpu/metrics). Creation needs no
        # ordering: O_CREAT + ftruncate zero-fills, zero rows are the
        # uninitialized state readers skip, and each rank scrubs only
        # its OWN region (daemon sets reuse files across epochs). The
        # sampler rides the heartbeat thread started above.
        from .. import metrics as _metrics
        if _metrics.enabled():
            try:
                from ..metrics import ring as _mring
                from ..metrics import sampler as _msampler
                need = _mring.file_len(self.n_local)
                fd = os.open(self._metrics_path,
                             os.O_RDWR | os.O_CREAT, 0o600)
                try:
                    if os.fstat(fd).st_size < need:
                        os.ftruncate(fd, need)
                    self._metrics_f = os.fdopen(fd, "r+b")
                except OSError:
                    os.close(fd)
                    raise
                self._metrics_mm = mmap.mmap(self._metrics_f.fileno(),
                                             need)
                _metrics.ensure_live()

                def _fpc_row(idx=self.local_index[my_rank]):
                    m = self._fpc_mirror
                    if m is None:
                        return ()
                    return m[idx * _FPC_SLOTS:(idx + 1) * _FPC_SLOTS]
                smp = _msampler.Sampler(
                    self._metrics_mm, self.local_index[my_rank],
                    fpc_row=_fpc_row, now_us=self._now_us)
                # first row inline, BEFORE the heartbeat thread can see
                # the sampler (single-writer: after this handoff only
                # the hb loop ticks, until close's final tick)
                smp.maybe_tick()
                self._sampler = smp
            except OSError:
                self._sampler = None
        # -- lazy per-peer wiring state ----------------------------------
        # the deferred half of bootstrap: bells + the unanimous CMA/
        # arena/flat agreement complete on the first operation that
        # needs them (ensure_wired / opportunistic try_wire)
        self._wired = False
        self._wire_stage = 0           # 0=idle, 1=verdict published
        self._wire_eager = False       # attribution for the wiring pvars
        self._wire_try_at = 0.0        # opportunistic-probe throttle
        self._wire_deadline = 0.0      # live ensure_wired deadline
                                       # (watchdog control-plane report)
        from ..analysis.lockorder import tracked as _tracked
        self._wire_lock = _tracked(threading.Lock(),
                                   f"shm[{my_rank}]._wire_lock")
        # one batched publication for every build card (bell, CMA probe,
        # segment/arena paths) — peers' wire step peeks these
        if self._cards:
            kvs.put_many(self._cards)

    def plane_eager_max(self) -> int:
        """Largest eager payload the plane can carry: an eager blob is a
        61-byte header + payload and must fit the shm ring (with margin
        for the ring's own length/align overhead). The single source of
        truth for the clamp applied by both the python protocol layer
        and the C fast path's cached threshold."""
        return self._ring_cap - 128 if self._ring_cap else 0

    def fp_counter(self, idx: int) -> int:
        """One fast-path counter slot from the plane (index order =
        cplane.cpp FPC_* = _FP_COUNTERS)."""
        if not self.plane:
            return 0
        return int(self._ring.lib.cp_fp_counter(self.plane, idx))

    def fpc_snapshot(self, world_rank: int):
        """All _FPC_SLOTS counter slots of a CO-LOCATED rank, read from
        the flags segment's shm mirror (a stale/torn snapshot is fine —
        stat surface, one natural writer per slot). None when the rank
        is not local."""
        i = self.local_index.get(world_rank)
        if i is None or self._fpc_mirror is None:
            return None
        row = self._fpc_mirror[i * _FPC_SLOTS:(i + 1) * _FPC_SLOTS]
        return [int(v) for v in row]

    def ntrace_active(self) -> bool:
        """Is the native trace ring armed on this plane?"""
        return bool(self.plane
                    and self._ring.lib.cp_ntrace_ok(self.plane))

    def plane_stats(self):
        """(eager_tx, eager_rx, fwd_py, rndv_tx, rndv_rx) from the C
        plane."""
        if not self.plane:
            return (0, 0, 0, 0, 0)
        tx = ctypes.c_ulonglong()
        rx = ctypes.c_ulonglong()
        fwd = ctypes.c_ulonglong()
        rtx = ctypes.c_ulonglong()
        rrx = ctypes.c_ulonglong()
        self._ring.lib.cp_stats(self.plane, tx, rx, fwd)
        self._ring.lib.cp_rndv_stats(self.plane, rtx, rrx)
        return (tx.value, rx.value, fwd.value, rtx.value, rrx.value)

    # -- liveness leases (failure containment) ---------------------------
    _LEASE_DEPARTED = 0xFFFFFFFFFFFFFFFF

    @staticmethod
    def _now_us() -> int:
        return int(time.clock_gettime(time.CLOCK_MONOTONIC) * 1e6)

    def _lease_stamp(self, value: Optional[int] = None) -> None:
        try:
            self._lease[self.local_index[self.my_rank]] = np.uint64(
                self._now_us() if value is None else value)
        except (ValueError, TypeError):
            pass                      # mapping already closed

    def _hb_loop(self) -> None:
        period = max(0.02, min(1.0, self._peer_timeout / 10.0)) \
            if self._peer_timeout > 0 else 0.5
        while True:
            # the metrics sampler rides this thread (no thread of its
            # own): clamp the wait to its interval and offer a tick on
            # every wake — re-read each pass, the sampler attaches
            # after the thread starts and detaches at close
            smp = self._sampler
            p = period if smp is None or smp.dead \
                else min(period, smp.interval)
            if self._hb_stop.wait(p):
                return
            self._lease_stamp()
            if smp is not None:
                smp.maybe_tick()

    def lease_age(self, world_rank: int) -> Optional[float]:
        """Seconds since ``world_rank``'s heartbeat stamp; None when the
        rank never stamped (bootstrap) or departed cleanly (Finalize)."""
        i = self.local_index.get(world_rank)
        if i is None:
            return None
        v = int(self._lease[i])
        if v == 0 or v == self._LEASE_DEPARTED:
            return None
        return max(0.0, (self._now_us() - v) / 1e6)

    def lease_report(self) -> List[str]:
        """One line per co-located rank for the stall-watchdog dump."""
        out = []
        u = getattr(self.engine, "universe", None) \
            if hasattr(self, "engine") else None
        failed = getattr(u, "failed_ranks", set()) if u is not None else set()
        for w in self.local_ranks:
            i = self.local_index[w]
            v = int(self._lease[i])
            if w == self.my_rank:
                state = "self"
            elif v == 0:
                state = "never-stamped"
            elif v == self._LEASE_DEPARTED:
                state = "departed"
            else:
                state = f"age {(self._now_us() - v) / 1e6:.2f}s"
            if w in failed:
                state += " FAILED"
            out.append(f"world {w} (ring {i}): {state}")
        return out

    def check_peer_leases(self) -> int:  # mv2tlint: handler
        """Liveness probe run from the progress engine's idle path (and
        registered via register_liveness): declare co-located peers dead
        when their lease goes stale past MV2T_PEER_TIMEOUT. Must never
        block — it runs at the blocking waits' sleep points. Returns how
        many peers were newly declared dead."""
        if self._peer_timeout <= 0:
            return 0
        now = time.monotonic()
        if now < self._lease_scan_at:
            return self._reconcile_plane_failures()
        self._lease_scan_at = now + max(0.01, self._peer_timeout / 4.0)
        eng = getattr(self, "engine", None)
        u = getattr(eng, "universe", None) if eng is not None else None
        if u is None:
            return 0
        ndead = 0
        for w in self.local_ranks:
            if w == self.my_rank or w in u.failed_ranks:
                continue
            age = self.lease_age(w)
            if age is not None and age > self._peer_timeout:
                from ..core.errors import PeerDeadError
                from ..faults import pv_dead_peer
                from ..ft import ulfm
                err = PeerDeadError(w, age, "liveness probe")
                log.warn("%s", err)
                u.last_peer_dead = err
                pv_dead_peer.inc()
                if getattr(eng, "_in_wait", False):
                    from ..faults import pv_deadline
                    pv_deadline.inc()
                ulfm.mark_failed(u, w)
                if self.plane and w in self.local_index:
                    self._failed_seen.add(w)
                ndead += 1
        ndead += self._reconcile_plane_failures()
        return ndead

    def _reconcile_plane_failures(self) -> int:  # mv2tlint: handler
        """Feed C-side lease detections (cp_lease_scan inside flat waves
        and wait quanta) into the python ULFM sink, so posted recvs and
        in-flight rendezvous unwind with MPIX_ERR_PROC_FAILED on both
        ABIs. One atomic read when nothing has failed."""
        if not self.plane:
            return 0
        lib = self._ring.lib
        if not lib.cp_any_failed(self.plane):
            return 0
        u = getattr(getattr(self, "engine", None), "universe", None)
        if u is None:
            return 0
        ndead = 0
        for w in self.local_ranks:
            if w == self.my_rank or w in self._failed_seen:
                continue
            if lib.cp_rank_failed(self.plane, self.local_index[w]):
                self._failed_seen.add(w)
                if w not in u.failed_ranks:
                    from ..faults import pv_dead_peer, pv_deadline
                    from ..ft import ulfm
                    pv_dead_peer.inc()
                    # the C lease scan runs ONLY inside blocking waits
                    # (flat waves, wait quanta): every reconciled C
                    # detection is a wait-deadline trip by construction
                    pv_deadline.inc()
                    ulfm.mark_failed(u, w)
                    ndead += 1
        return ndead

    def _probe_cma(self) -> bool:
        """Can this process read a co-resident rank's memory via
        process_vm_readv? Reads a neighbor's published probe buffer and
        checks the bytes (the runtime capability probe the reference
        performs for CMA/LiMIC2 availability)."""
        idx = self.local_ranks.index(self.my_rank)
        left = self.local_ranks[idx - 1]
        if left == self.my_rank:
            return True          # single local rank: self-copy path
        try:
            pid, addr, n = map(
                int, self.kvs.get(f"shm-cma-{left}").split(":"))
        except Exception:
            return False
        expect = f"mv2t-cma-{left:012d}".encode()
        if n != len(expect):
            return False
        buf = ctypes.create_string_buffer(n)

        class IoVec(ctypes.Structure):
            _fields_ = [("iov_base", ctypes.c_void_p),
                        ("iov_len", ctypes.c_size_t)]

        try:
            libc = ctypes.CDLL(None, use_errno=True)
            libc.process_vm_readv.restype = ctypes.c_ssize_t
            libc.process_vm_readv.argtypes = [
                ctypes.c_int, ctypes.POINTER(IoVec), ctypes.c_ulong,
                ctypes.POINTER(IoVec), ctypes.c_ulong, ctypes.c_ulong]
            liov = IoVec(ctypes.cast(buf, ctypes.c_void_p), n)
            riov = IoVec(addr, n)
            got = libc.process_vm_readv(pid, ctypes.byref(liov), 1,
                                        ctypes.byref(riov), 1, 0)
        except Exception:
            return False
        ok = got == n and buf.raw[:n] == expect
        if not ok:
            log.warn("CMA probe failed (read %s from pid %d); using the "
                     "staged rendezvous path", got, pid)
        return ok

    # -- lazy per-peer wiring (the deferred half of bootstrap) -----------
    #
    # The eager model wired every peer at Init behind a global fence.
    # Now a channel is BUILT (segments mapped, plane registered, eager
    # pt2pt live) the moment its constructor returns, and the peer-
    # dependent half — bells, the unanimous CMA/arena/flat agreement,
    # the C-ABI membership table — completes on the first operation that
    # needs it. Two stages, both driven by batched KVS peeks:
    #
    #   stage 0->1: every co-located rank's BUILD cards (bell + CMA
    #     probe) are visible -> set bells, probe the neighbor, attach
    #     the follower-side arena/flat segments, publish my VERDICT
    #     card (one batched put).
    #   stage 1->2: every rank's verdict is visible -> apply the
    #     unanimous agreements, rebind the plane pvars, wired.
    #
    # Blocking (ensure_wired) is only entered where every participant
    # is known to arrive — collective dispatch and rendezvous — so an
    # idle peer can never deadlock a wire. Everything else degrades:
    # eager sends ride the ring bell-less, rendezvous exposes fall back
    # to the scratch-file ladder until try_wire upgrades them.

    def try_wire(self, force: bool = False) -> bool:
        """Opportunistic nonblocking wire attempt (throttled). Called
        from the progress poll path and rendezvous entries; never
        blocks and never waits on a lock."""
        if self._wired:
            return True
        now = time.monotonic()
        if not force and now < self._wire_try_at:
            return False
        if not self._wire_lock.acquire(blocking=False):
            return self._wired
        try:
            self._wire_try_at = time.monotonic() + 0.01
            return self._wire_step()
        finally:
            self._wire_lock.release()

    def ensure_wired(self, eager: bool = False) -> None:
        """Blocking wire gate: complete the per-node agreement or raise.
        Unwinds with MPIX_ERR_PROC_FAILED when a co-located peer dies
        mid-wire (lease scan / launcher events), and with MPI_ERR_INTERN
        after MV2T_WIRE_TIMEOUT — never a silent hang."""
        if self._wired:
            return
        self._wire_eager = eager or self._wire_eager
        deadline = time.monotonic() + max(
            1.0, float(get_config().get("WIRE_TIMEOUT", 120.0)))
        self._wire_deadline = deadline
        while True:
            with self._wire_lock:
                if self._wire_step():
                    return
            # containment: a peer killed mid-wire must unwind this wait
            if self._peer_timeout > 0:
                self.check_peer_leases()
            u = getattr(self.engine, "universe", None) \
                if hasattr(self, "engine") else None
            if u is not None and u.failed_ranks:
                dead = [r for r in self.local_ranks
                        if r != self.my_rank and r in u.failed_ranks]
                if dead:
                    from ..core.errors import PeerDeadError
                    raise PeerDeadError(dead[0], 0.0, "node wire gate")
            if time.monotonic() > deadline:
                from ..core.errors import MPIException, MPI_ERR_INTERN
                raise MPIException(
                    MPI_ERR_INTERN,
                    f"shm wire gate timed out after MV2T_WIRE_TIMEOUT: "
                    f"co-located ranks {self.local_ranks} never all "
                    f"published wiring cards (stage {self._wire_stage})")
            time.sleep(0.001)

    def finish_wiring(self) -> None:
        """Eager wiring (spawn bootstrap and MV2T_LAZY_WIRING=0): the
        pre-lazy entry point, kept as the blocking gate with eager
        attribution."""
        self.ensure_wired(eager=True)

    def _wire_step(self) -> bool:  # holds: _wire_lock
        """One nonblocking advance of the wire state machine."""
        if self._wired:
            return True
        from .. import faults
        faults.fire("wire")    # chaos: crash/delay mid-wire
        u = getattr(self.engine, "universe", None) \
            if hasattr(self, "engine") else None
        failed = getattr(u, "failed_ranks", None) or set()
        # a peer that died mid-wire can never publish its cards: the
        # wire completes DEGRADED without it — conservative all-False
        # agreements (eager + scratch-file rendezvous keep working),
        # never a permanent stage-1 stall
        dead = [r for r in self.local_ranks
                if r != self.my_rank and r in failed]
        peers = [r for r in self.local_ranks
                 if r != self.my_rank and r not in failed]
        if self._wire_stage == 0:   # state: wire:0
            vals = self.kvs.peek_many(
                [f"shm-bell-{r}" for r in peers]
                + [f"shm-cma-{r}" for r in peers])
            if any(v is None for v in vals):
                return False    # some peer has not built its world yet
            lib = self._ring.lib if self.plane else None
            for r, addr in zip(peers, vals[:len(peers)]):
                self._peer_bells[r] = addr
                if lib is not None:
                    lib.cp_set_bell(self.plane, self.local_index[r],
                                    addr.encode())
            # CMA is enabled only by UNANIMOUS agreement: every
            # co-resident rank publishes its probe verdict (can it read
            # a neighbor, is USE_CMA set) and reads everyone else's.
            # The receiver performs the pull, so a single incapable/
            # opted-out rank must disable the protocol for the whole
            # node. The arena and flat verdicts ride the same exchange:
            # a rank whose mapping failed would receive handles (or
            # join waves) it cannot dereference.
            # degraded wire skips the probe: the left neighbor may BE
            # the dead rank (probe card never published) and the
            # verdict is forced False at apply anyway
            my_ok = not dead and bool(get_config()["USE_CMA"]) \
                and self._probe_cma()
            if self.arena is None and not self._owner:
                self._attach_follower_arena()
            my_arena = self.arena is not None
            my_flat = False
            my_flat2 = False
            if self.plane:
                if not self._owner:
                    lib.cp_flat_attach(self.plane,
                                       self._flat_path.encode(), 0)
                    lib.cp_flat2_attach(self.plane,
                                        self._flat2_path.encode(), 0)
                my_flat = bool(lib.cp_flat_ok(self.plane))
                my_flat2 = bool(lib.cp_flat2_ok(self.plane))
            # C-ABI membership: a comm with any C-ABI rank must use the
            # C fast path's collective-tier cap (FP_COLL_MAX) on every
            # member — coll/api.py._plane_coll_max reads this set. A
            # pure python comm keeps the tuning tier above the eager
            # size (interpreter-hop schedules lose to the arena tier).
            from .. import cshim as _cshim
            my_cabi = _cshim.is_cabi_process()
            self._my_verdicts = (my_ok, my_arena, my_flat, my_flat2)
            self.kvs.put_many({
                f"shm-cma-ok-{self.my_rank}": "1" if my_ok else "0",
                f"shm-arena-ok-{self.my_rank}": "1" if my_arena else "0",
                f"shm-flat-ok-{self.my_rank}": "1" if my_flat else "0",
                f"shm-flat2-ok-{self.my_rank}": "1" if my_flat2 else "0",
                f"shm-cabi-{self.my_rank}": "1" if my_cabi else "0",
            })
            self.cabi_ranks = {self.my_rank} if my_cabi else set()
            self._wire_stage = 1
        if self._wire_stage == 1:   # state: wire:1
            vals = self.kvs.peek_many(
                [f"shm-cma-ok-{r}" for r in peers]
                + [f"shm-arena-ok-{r}" for r in peers]
                + [f"shm-flat-ok-{r}" for r in peers]
                + [f"shm-flat2-ok-{r}" for r in peers]
                + [f"shm-cabi-{r}" for r in peers])
            if any(v is None for v in vals):
                return False    # some peer has not published its verdict
            n = len(peers)
            my_ok, my_arena, my_flat, my_flat2 = self._my_verdicts
            all_ok = my_ok and all(v == "1" for v in vals[:n])
            all_arena = my_arena and all(v == "1" for v in vals[n:2 * n])
            all_flat = my_flat and all(v == "1" for v in vals[2 * n:3 * n])
            all_flat2 = my_flat2 and all(
                v == "1" for v in vals[3 * n:4 * n])
            if dead:
                # degraded wire: a local rank died before its verdict
                # landed — no unanimous agreement can include it
                all_ok = all_arena = all_flat = all_flat2 = False
                self.cabi_ranks.update(dead)
            for r, v in zip(peers, vals[4 * n:]):
                if v != "0":
                    # unknown counts as C-ABI: the conservative verdict
                    # is the shared FP_COLL_MAX cap
                    self.cabi_ranks.add(r)
            self._apply_wire(all_ok, all_arena, all_flat, my_flat,
                             all_flat2, my_flat2)
        return self._wired

    def _attach_follower_arena(self) -> None:
        """Follower-side arena attach, run inside the wire step: the
        leader's card is published with its build cards (bell presence
        implies card presence), so this never blocks."""
        try:
            card = self.kvs.peek_many(
                [f"shm-arena-{self.local_ranks[0]}"])[0]
            if card:
                apath, part = card.rsplit(":", 1)
                self.arena = ShmArena(apath, self.n_local,
                                      self.local_index[self.my_rank],
                                      int(part), create=False)
        except Exception as e:
            log.warn("arena attach failed (%s); scratch-file rendezvous",
                     e)
            self.arena = None

    def _apply_wire(self, all_ok: bool, all_arena: bool, all_flat: bool,
                    my_flat: bool, all_flat2: bool = False,
                    my_flat2: bool = False) -> None:  # holds: _wire_lock
        """Stage 2: apply the unanimous agreements and go live."""
        self.cma_ok = all_ok
        if not all_arena and self.arena is not None:
            self.arena.close(unlink=self._owner and not self._daemon)
            self.arena = None
        self._arena_ready = self.arena is not None
        if self.plane:
            lib = self._ring.lib
            if not all_flat and my_flat:
                lib.cp_flat_disable(self.plane)
            if not all_flat2 and my_flat2:
                lib.cp_flat2_disable(self.plane)
            if all_ok:
                lib.cp_set_cma(self.plane, 1)
            # open the C fast path's collective dispatch LAST: every
            # agreement verdict above must be visible first (release
            # store; fpc_enter's acquire load pairs with it)
            lib.cp_set_wired(self.plane)
        self._wired = True
        (pv_wiring_eager if self._wire_eager else pv_wiring_lazy).inc()
        log.info("node wire complete (cma=%s arena=%s flat=%s flat2=%s, "
                 "%s)", all_ok, all_arena, all_flat, all_flat2,
                 "eager" if self._wire_eager else "lazy")

    def _make_ring(self, path: str, ring_bytes: int, create: bool):
        lib = _load_native()
        if lib is not None:
            try:
                return _NativeRing(lib, path, self.n_local, ring_bytes,
                                   create)
            except OSError as e:
                log.warn("native ring attach failed (%s); python", e)
        return _PyRing(path, self.n_local, ring_bytes, create)

    @property
    def using_native(self) -> bool:
        return isinstance(self._ring, _NativeRing)

    # -- channel API ------------------------------------------------------
    def _ring_bell(self, dest_world: int) -> None:
        if self._flags[self.local_index[dest_world]] == 0:
            return    # receiver awake and polling: no doorbell needed
        addr = self._peer_bells.get(dest_world)
        if addr is None:
            addr = self.kvs.get(f"shm-bell-{dest_world}")
            self._peer_bells[dest_world] = addr
        try:
            self._bell.sendto(b"x", addr)
        except OSError:
            pass    # full/raced doorbell is fine; receiver polls anyway

    def send_packet(self, dest_world: int, pkt: Packet) -> None:
        blob = encode_packet(pkt)
        from .. import faults
        kind = faults.fire("shm_send")
        if kind == "drop":
            return                    # lost on the (simulated) wire
        if kind == "truncate":
            blob = blob[:max(1, len(blob) // 2)]
        self._inject_blob(dest_world, blob)
        if kind == "duplicate":
            self._inject_blob(dest_world, blob)

    def _inject_blob(self, dest_world: int, blob: bytes) -> None:
        # python-injected traffic only; the C plane's eager fast path
        # bypasses send_packet entirely and keeps its own counters
        # (cplane_eager_tx et al.)
        self.account_send(dest_world, len(blob))
        dst_i = self.local_index[dest_world]
        if self.plane:
            # plane mode: the C injector owns ordering + backlog; spill
            # oversize blobs first so inject never sees one
            if len(blob) > self._ring_cap:
                blob = self._spill_oversize(blob, dst_i)
            self._ring.lib.cp_inject(self.plane, dst_i, blob, len(blob))
            return
        src_i = self.local_index[self.my_rank]
        with self._send_lock:
            bl = self._backlog.setdefault(dst_i, collections.deque())
            if bl:
                bl.append(blob)
                self._flush(dst_i)
            else:
                rc = self._ring.send(src_i, dst_i, blob)
                if rc == 0:
                    bl.append(blob)  # ring full: backlog, flush from poll
                elif rc < 0:
                    # larger than the ring: stream via an arena/file spill
                    note = self._spill_oversize(blob, dst_i)
                    if self._ring.send(src_i, dst_i, note) == 0:
                        bl.append(note)
        self._ring_bell(dest_world)

    def wait_for_event(self, timeout: float) -> None:
        try:
            r, _, _ = select.select([self._bell], [], [],
                                    min(timeout, 0.002))
        except OSError:
            return
        self._drain_bell()

    def _drain_bell(self) -> None:
        while True:
            try:
                self._bell.recv(4096)
            except OSError:
                break

    def wait_fds(self):
        return [self._bell]

    def pre_wait(self) -> None:
        self._flags[self.local_index[self.my_rank]] = 1

    def post_wait(self) -> None:
        self._flags[self.local_index[self.my_rank]] = 0

    def _spill_oversize(self, blob: bytes, dst_i: int) -> bytes:
        """Spill a larger-than-ring message to the arena (falling back to
        a scratch file); returns the small ring note pointing at it.
        Never waits for ring space — a spin here would run under
        _send_lock and block poll() from draining inbound rings
        (cross-rank deadlock); a full ring just backlogs the note like
        any other blob. Arena blocks are reclaimed lazily once the
        receiver's spill-consumed counter passes the note's sequence
        number (_reclaim_spills)."""
        if self._arena_ready:
            self._reclaim_spills()
            h = self.arena.alloc(len(blob))
            if h is not None:
                self.arena.view(h.off, len(blob))[:] = \
                    np.frombuffer(blob, dtype=np.uint8)
                with self._spill_lock:
                    seq = self._spill_seq.get(dst_i, 0) + 1
                    self._spill_seq[dst_i] = seq
                    self._spill_pending.setdefault(
                        dst_i, collections.deque()).append((seq, h))
                # 0xFE discriminator: arena spill note (0xFF = file)
                return b"\xfe" + struct.pack(
                    "<qqq", self.local_index[self.my_rank], h.off,
                    len(blob))
        path = self.path + f".big-{self.my_rank}-{uuid.uuid4().hex[:8]}"
        with open(path, "wb") as f:
            f.write(blob)
        # 0xFF discriminator: not a valid PktType first byte
        return b"\xff" + path.encode()

    def _reclaim_spills(self) -> None:
        """Free arena spill blocks whose notes the receiver has consumed
        (its counter in the arena header passed their sequence)."""
        my_i = self.local_index[self.my_rank]
        with self._spill_lock:
            for dst_i, pend in self._spill_pending.items():
                if not pend:
                    continue
                c = self.arena.spill_consumed(my_i, dst_i)
                while pend and pend[0][0] <= c:
                    self.arena.free(pend.popleft()[1])

    def _consume_spill_note(self, blob) -> bytes:
        """Dereference an inbound spill note (0xFE arena / 0xFF file)."""
        if blob[0] == 0xFE:
            src_i, off, n = struct.unpack_from("<qqq", blob, 1)
            data = bytes(self.arena.view(off, n))
            self.arena.bump_spill(src_i, self.local_index[self.my_rank])
            return data
        path = bytes(blob[1:]).decode()
        with open(path, "rb") as f:
            data = f.read()
        os.unlink(path)
        return data

    def _flush(self, dst_i: int) -> None:  # holds: _send_lock
        bl = self._backlog.get(dst_i)
        if bl is None:
            return
        src_i = self.local_index[self.my_rank]
        while bl:
            rc = self._ring.send(src_i, dst_i, bl[0])
            if rc == 0:
                return
            blob = bl.popleft()
            if rc < 0:
                note = self._spill_oversize(blob, dst_i)
                if self._ring.send(src_i, dst_i, note) == 0:
                    bl.appendleft(note)   # keep FIFO order, retry later
                    return

    def poll(self) -> bool:
        # opportunistic lazy-wiring probe (throttled; one time read +
        # attr check when wired): upgrades pt2pt-only workloads to the
        # full agreement without any blocking gate
        if not self._wired:
            self.try_wire()
        if self.plane:
            return self._poll_plane()
        my_i = self.local_index[self.my_rank]
        self._drain_bell()
        did = False
        with self._send_lock:
            for dst_i in list(self._backlog):
                self._flush(dst_i)
        # racy truthiness gate is intentional: a stale read only delays
        # reclaim one poll; _reclaim_spills itself takes _spill_lock
        if self._spill_pending:  # mv2tlint: ignore[locks]
            self._reclaim_spills()
        from .. import faults
        for src_i in range(self.n_local):
            if src_i == my_i:
                continue
            while True:
                blob = self._ring.recv(src_i, my_i)
                if blob is None:
                    break
                if blob[0] in (0xFE, 0xFF):    # oversize spill note
                    blob = self._consume_spill_note(blob)
                if faults.fire("shm_recv") == "drop":
                    continue           # inbound packet lost
                self.account_recv(len(blob))
                self.engine.enqueue_incoming(decode_packet(blob))
                did = True
        if self._peer_timeout > 0:
            self.check_peer_leases()
        return did

    # -- plane mode -------------------------------------------------------
    def _poll_plane(self) -> bool:
        """Progress pass in plane mode: the C engine drains the rings and
        matches plane-owned envelopes; this drains what it forwarded —
        python-owned packets, rendezvous assists, cancel results — and
        finalizes any completed plane receives the engine is tracking."""
        lib = self._ring.lib
        self._drain_bell()
        did = lib.cp_advance(self.plane) > 0
        # liveness on the poll path too (throttled): pokers that never
        # reach progress_wait — the ULFM agreement's poke/sleep loop,
        # spin-waiters — still detect dead peers; this also reconciles
        # C-side detections (flat waves, wait quanta) into the ULFM
        # sink. One atomic read + one time read when healthy.
        if self._peer_timeout > 0:
            self.check_peer_leases()
        else:
            self._reconcile_plane_failures()
        # racy truthiness gate, same justification as poll()
        if self._spill_pending:  # mv2tlint: ignore[locks]
            self._reclaim_spills()
        from .. import faults
        while lib.cp_py_pending(self.plane):
            n = lib.cp_py_peek(self.plane)
            if n <= 0:
                break
            buf = ctypes.create_string_buffer(n)
            got = lib.cp_py_pop(self.plane, buf, n)
            if got <= 0:
                break
            blob = buf.raw[:got]
            if blob[0] in (0xFE, 0xFF):  # oversize spill note (py-owned)
                blob = self._consume_spill_note(blob)
            if faults.fire("shm_recv") == "drop":
                continue               # inbound packet lost
            self.engine.enqueue_incoming(decode_packet(blob))
            did = True
        client = self.plane_client
        while client is not None and lib.cp_assist_pending(self.plane):
            n = lib.cp_assist_peek(self.plane)
            if n <= 0:
                break
            rid = ctypes.c_longlong()
            buf = ctypes.create_string_buffer(n)
            got = lib.cp_assist_pop(self.plane, rid, buf, n)
            if got <= 0:
                break
            client.on_plane_assist(self, rid.value,
                                   decode_packet(buf.raw[:got]))
            did = True
        if self._plane_cancels:
            for sid in list(self._plane_cancels):
                res = lib.cp_cancel_result(self.plane, sid)
                if res >= 0:
                    req = self._plane_cancels.pop(sid)
                    lib.cp_cancel_forget(self.plane, sid)
                    if client is not None:
                        client.on_plane_cancel_result(req, bool(res))
        if self._plane_recvs:
            for cpid in list(self._plane_recvs):
                req = self._plane_recvs.get(cpid)
                if req is not None and req._poll_plane():
                    did = True
        return did

    # registration hooks used by the protocol layer
    def plane_track_recv(self, cpid: int, req) -> None:
        self._plane_recvs[cpid] = req

    def plane_untrack_recv(self, cpid: int) -> None:
        self._plane_recvs.pop(cpid, None)

    def plane_track_cancel(self, sreq_id: int, req) -> None:
        self._plane_cancels[sreq_id] = req

    # -- zero-copy rendezvous (RGET handle ladder: CMA > arena > file) ----
    def expose_buffer(self, array: np.ndarray):
        """Register a send buffer for remote pull. Handle ladder, best
        first: ("cma", pid, addr, tok) — the receiver reads the live
        buffer via process_vm_readv (zero staging copies); ("arena", off,
        tok) — one copy into a persistent arena block; ("file", path) —
        the legacy per-send scratch file, kept as the exhaustion/fallback
        path. The keepalive (buffer ref / ArenaHandle) lives in the
        _exposed handle table until release_buffer."""
        arr = np.ascontiguousarray(array).view(np.uint8).reshape(-1)
        if arr.size == 0:
            return ("null",)
        if not self._wired:
            # a rendezvous is the natural upgrade point: both ends are
            # live. Nonblocking — while unwired the ladder degrades to
            # the scratch-file path, which needs no agreement.
            self.try_wire(force=True)
        if self.cma_ok:
            self._expose_tok += 1
            h = ("cma", os.getpid(), arr.ctypes.data, self._expose_tok)
            self._exposed[h] = arr
            return h
        if self._arena_ready:
            ah = self.arena.alloc(arr.size)
            if ah is not None:
                self.arena.view(ah.off, arr.size)[:] = arr
                self._expose_tok += 1
                h = ("arena", ah.off, self._expose_tok)
                self._exposed[h] = ah
                return h
        path = self.path + f".rget-{self.my_rank}-{uuid.uuid4().hex[:8]}"
        with open(path, "wb") as f:
            f.write(arr.tobytes())
        return ("file", path)

    def pull_buffer(self, src_world: int, handle, nbytes: int) -> np.ndarray:
        """RGET: read the peer's exposed buffer. CMA and arena pulls are
        chunked (MV2T_RNDV_CHUNK) with a trace instant per chunk; the
        arena/file paths return views anchored to the shared/mapped
        memory (no staging copy — the caller reduces/unpacks straight
        out of the mapping before the FIN releases it)."""
        from .. import faults
        faults.fire("rndv_chunk")     # crash/delay mid-pull (RGET)
        tr = getattr(self.engine, "tracer", None) \
            if hasattr(self, "engine") else None
        kind = handle[0] if isinstance(handle, tuple) else "path"
        if kind == "cma":
            _, pid, addr, _tok = handle
            out = np.empty(nbytes, dtype=np.uint8)
            cma_read(pid, addr, out, chunk=get_config()["RNDV_CHUNK"],
                     tracer=tr)
            return out
        if kind == "arena":
            if tr is not None:
                tr.record("protocol", "rndv_chunk", "i", dir="arena",
                          bytes=nbytes)
            return self.arena.view(handle[1], nbytes)
        path = handle[1] if kind == "file" else handle
        with open(path, "rb") as f:
            mm = mmap.mmap(f.fileno(), 0, prot=mmap.PROT_READ)
        # a frombuffer view anchored to the mapping: the caller unpacks/
        # reduces out of it immediately, so no .copy() staging hop — the
        # view holds the mapping alive (unlink-while-mapped is fine)
        return np.frombuffer(mm, dtype=np.uint8, count=nbytes)

    def release_buffer(self, handle) -> None:
        if isinstance(handle, tuple):
            kind = handle[0]
            if kind == "cma" or kind == "null":
                self._exposed.pop(handle, None)
                return
            if kind == "arena":
                ah = self._exposed.pop(handle, None)
                if ah is not None:
                    self.arena.free(ah)
                return
            handle = handle[1]    # ("file", path)
        try:
            os.unlink(handle)
        except OSError:
            pass

    # a cancelled-and-retracted rendezvous send never gets its FIN; the
    # cancel-resp path releases the exposure through this alias
    unexpose_buffer = release_buffer

    def close(self) -> None:
        if self.plane:
            # latch final counters into the owned pvar values so tools
            # reading after teardown still see the job's totals
            try:
                stats = self.plane_stats()
                for (name, _), v in zip(_PV_PLANE_DECLS, stats):
                    pv = _mpit.pvar(name)
                    pv.source = None
                    pv._value += float(v)   # _value held the prior total
                for i, (name, _) in enumerate(_FP_COUNTERS):
                    pv = _mpit.pvar(name)
                    pv.source = None
                    pv._value += float(self.fp_counter(i))
            except Exception:
                pass
            try:
                self._ring.lib.cp_destroy(self.plane)
            except Exception:
                pass
            self.plane = None
        # clean departure: stamp the lease sentinel (AFTER cp_destroy so
        # a last advance_locked can't overwrite it) and stop the
        # heartbeat — peers must read "departed", never "dead"
        self._hb_stop.set()
        self._lease_stamp(self._LEASE_DEPARTED)
        # final metrics tick BEFORE detaching: a job shorter than one
        # sampling interval still publishes >= 1 row + its histograms
        smp, self._sampler = self._sampler, None
        if smp is not None:
            try:
                smp.tick()
            except Exception:
                pass
        if self._metrics_mm is not None:
            try:
                self._metrics_mm.close()
            except (OSError, ValueError, BufferError):
                pass
            self._metrics_mm = None
        if self.arena is not None:
            # Finalize leak check: every exposure must have been released
            # by its FIN/cancel; pending spills may legitimately await
            # reclaim, so free them silently first.
            with self._spill_lock:
                for pend in self._spill_pending.values():
                    while pend:
                        self.arena.free(pend.popleft()[1])
            if self._exposed or self.arena.outstanding:
                u = getattr(self.engine, "universe", None) \
                    if hasattr(self, "engine") else None
                if u is not None and u.failed_ranks:
                    # dead peers never FIN: their exposures/blocks are
                    # reclaimed state, not leaks (counted, not warned)
                    n = len(self._exposed) + self.arena.outstanding
                    for h in list(self._exposed):
                        self.release_buffer(h)
                    _mpit.pvar("arena_reclaimed_dead").inc(n)
                    log.info("reclaimed %d arena exposures/blocks "
                             "stranded by failed ranks %s", n,
                             sorted(u.failed_ranks))
                else:
                    log.warn("arena handle leak at close: %d exposures, "
                             "%d arena blocks live", len(self._exposed),
                             self.arena.outstanding)
            self.arena.close(unlink=self._owner and not self._daemon)
        try:
            self._bell.close()
            os.unlink(self._bell_path)
        except OSError:
            pass
        try:
            self._lease = None     # release the buffer exports first
            self._fpc_mirror = None
            self._flags.close()
            self._flags_f.close()
        except (OSError, ValueError, BufferError):
            pass
        try:
            self._ring.close()
        except Exception:
            pass
        if self._owner:
            if self._daemon_claim is not None:
                # warm-attach mode: the segment files belong to the node
                # daemon — release the claim (next job resets + reuses)
                from ..runtime import daemon as _daemon
                _daemon.release(self._daemon_claim)
            elif not self._daemon:
                for path in (self.path, self._flags_path,
                             self._flat_path, self._flat2_path,
                             self._ntrace_path, self._metrics_path):
                    try:
                        os.unlink(path)
                    except OSError:
                        pass
        if self._ntrace_f is not None:
            try:
                self._ntrace_f.close()
            except OSError:
                pass
            self._ntrace_f = None
        if self._metrics_f is not None:
            try:
                self._metrics_f.close()
            except OSError:
                pass
            self._metrics_f = None
