"""Persistent per-node scratch arena — the registration-cache analog.

One mmap'd /dev/shm region per node, created at bootstrap alongside the
shm ring segment and carved into size-classed blocks with a handle
table. It replaces the per-send scratch files the staged rendezvous used
to create (two full copies plus open/write/unlink syscalls per transfer,
a cost cliff at the eager->rendezvous switch): a
block is allocated once, reused across sends, and freed when the FIN
arrives — the steady-state reuse discipline of MVAPICH2's registration
cache (dreg.c) applied to a shared scratch pool.

Layout (offsets are file-absolute so they travel on the wire):

    spill-consumed grid   n*n u64   receiver's count of consumed arena
                                    spill notes per (src,dst) pair
    partition 0           PART bytes  owned by local rank 0
    ...
    partition n-1         PART bytes  owned by local rank n-1

Each rank allocates ONLY from its own partition (size-classed free
lists, local bookkeeping, no cross-process allocator locks) and any rank
may read any offset — the receiver of an RTS maps the handle straight to
a view of this mapping. Allocation/free are thread-safe within the
owning process (MPI-IO workers, THREAD_MULTIPLE).

The module also owns the cross-memory-attach read helper (the
process_vm_readv path of ch3_smp_progress.c:525) and the rendezvous
pipeline knobs/counters shared by transport/shm.py and pt2pt/protocol.py.
"""

from __future__ import annotations

import ctypes
import os
import re
import threading
from typing import Dict, List, Optional

import numpy as np

from ..utils.config import cvar, get_config
from ..utils.mlog import get_logger

log = get_logger("arena")

cvar("ARENA_BYTES", 0, int, "shm",
     "Per-rank partition size of the persistent per-node scratch arena "
     "in bytes. 0 = auto (256 MiB for 2 co-located ranks, 128 MiB for "
     "3-4, 32 MiB beyond — sized so a 64-deep window of 4 MiB sends, "
     "the OSU bw shape, stays in the arena). tmpfs allocates pages "
     "lazily, so the partition costs resident memory only for what the "
     "live traffic actually touches. Allocations larger than the "
     "partition fall back to the scratch-file path.")
cvar("RNDV_CHUNK", 256 * 1024, int, "pt2pt",
     "Pipeline chunk size in bytes for the chunked rendezvous (arena "
     "slot length / CMA read granularity — the MV2_RNDV_CHUNK analog of "
     "the RGET pipelining in ibv_rndv.c).")
cvar("RNDV_DEPTH", 4, int, "pt2pt",
     "Pipeline depth (arena slots in flight) of the chunked rendezvous: "
     "the sender refills slot k while the receiver drains slot k-1.")

from .. import mpit as _mpit  # noqa: E402  (after cvar decls, same registry)

pv_allocs = _mpit.pvar("arena_allocs", _mpit.PVAR_CLASS_COUNTER, "shm",
                       "blocks allocated from the per-node scratch arena")
pv_hwm = _mpit.pvar("arena_bytes_hwm", _mpit.PVAR_CLASS_HIGHWATERMARK,
                    "shm", "high-watermark of arena bytes in use")
pv_pipeline = _mpit.pvar("rndv_pipeline_chunks", _mpit.PVAR_CLASS_COUNTER,
                         "pt2pt",
                         "chunks moved by the pipelined rendezvous")
pv_cma_bytes = _mpit.pvar("rndv_cma_bytes", _mpit.PVAR_CLASS_COUNTER,
                          "pt2pt",
                          "bytes read via cross-memory attach "
                          "(process_vm_readv)")
pv_reclaimed_dead = _mpit.pvar(
    "arena_reclaimed_dead", _mpit.PVAR_CLASS_COUNTER, "shm",
    "arena blocks/segments reclaimed from dead ranks (failure sweep, "
    "Finalize leak-check tolerance, stale-segment sweep)")

_PAGE = 4096


# ---------------------------------------------------------------------------
# cross-memory attach (CMA) read
# ---------------------------------------------------------------------------

class _IoVec(ctypes.Structure):
    _fields_ = [("iov_base", ctypes.c_void_p),
                ("iov_len", ctypes.c_size_t)]


_libc = None


def _get_libc():
    global _libc
    if _libc is None:
        lib = ctypes.CDLL(None, use_errno=True)
        lib.process_vm_readv.restype = ctypes.c_ssize_t
        lib.process_vm_readv.argtypes = [
            ctypes.c_int, ctypes.POINTER(_IoVec), ctypes.c_ulong,
            ctypes.POINTER(_IoVec), ctypes.c_ulong, ctypes.c_ulong]
        _libc = lib
    return _libc


def cma_read(pid: int, addr: int, out: np.ndarray, chunk: int = 0,
             tracer=None) -> None:
    """Read ``out.nbytes`` bytes from ``addr`` in process ``pid`` via
    process_vm_readv, ``chunk`` bytes per syscall (0 = one shot). Counts
    into rndv_cma_bytes; emits one trace instant per chunk so the
    pipeline overlap is visible in mpitrace."""
    lib = _get_libc()
    total = out.nbytes
    if total == 0:
        return
    step = chunk if chunk and chunk < total else total
    base = out.ctypes.data
    off = 0
    while off < total:
        n = min(step, total - off)
        liov = _IoVec(base + off, n)
        riov = _IoVec(addr + off, n)
        got = lib.process_vm_readv(pid, ctypes.byref(liov), 1,
                                   ctypes.byref(riov), 1, 0)
        if got != n:
            raise OSError(ctypes.get_errno(),
                          f"process_vm_readv({pid}) read {got}/{n}")
        if tracer is not None:
            tracer.record("protocol", "rndv_chunk", "i", dir="cma",
                          offset=off, bytes=n)
        off += n
    pv_cma_bytes.inc(total)


# ---------------------------------------------------------------------------
# the arena
# ---------------------------------------------------------------------------

class ArenaHandle:
    """One allocated block (the registration-cache entry analog)."""

    __slots__ = ("off", "cls", "nbytes")

    def __init__(self, off: int, cls: int, nbytes: int):
        self.off = off
        self.cls = cls          # size-class bytes (pow2 >= nbytes)
        self.nbytes = nbytes

    def __repr__(self):
        return f"ArenaHandle(off={self.off}, cls={self.cls})"


def _auto_part_bytes(n_local: int) -> int:
    if n_local <= 2:
        return 256 << 20
    if n_local <= 4:
        return 128 << 20
    return 32 << 20


class ShmArena:
    """One rank's mapping of the per-node scratch arena."""

    MIN_CLASS = 64 * 1024

    def __init__(self, path: str, n_local: int, my_index: int,
                 part_bytes: Optional[int] = None, create: bool = False,
                 exclusive: bool = True):
        """``create`` initializes a fresh arena; ``exclusive=False``
        relaxes O_EXCL for the warm-attach path (runtime/daemon.py),
        where the file pre-exists but was reset to all-zeroes — which
        IS the created state (empty spill grid, per-process brk)."""
        if part_bytes is None or part_bytes <= 0:
            part_bytes = int(get_config()["ARENA_BYTES"]) \
                or _auto_part_bytes(n_local)
        part_bytes = (part_bytes + _PAGE - 1) & ~(_PAGE - 1)
        hdr = (n_local * n_local * 8 + _PAGE - 1) & ~(_PAGE - 1)
        total = hdr + n_local * part_bytes
        import mmap as _mmap
        flags = os.O_RDWR
        if create:
            flags |= os.O_CREAT | (os.O_EXCL if exclusive else 0)
        self.fd = os.open(path, flags, 0o600)
        if create:
            os.ftruncate(self.fd, total)   # tmpfs: zero-filled
        self.mm = _mmap.mmap(self.fd, total)
        self.path = path
        self.n_local = n_local
        self.my_index = my_index
        self.part_bytes = part_bytes
        self._buf = np.frombuffer(self.mm, dtype=np.uint8)
        self._spill = self._buf[:n_local * n_local * 8].view(np.uint64)
        self._part_lo = hdr + my_index * part_bytes
        self._part_hi = self._part_lo + part_bytes
        self._brk = self._part_lo
        self._free: Dict[int, List[int]] = {}
        from ..analysis.lockorder import tracked
        self._lock = tracked(threading.Lock(), f"arena[{my_index}]._lock")
        self._outstanding = 0
        self._in_use = 0

    # -- slot allocator (owner-local) ------------------------------------
    @classmethod
    def _class_of(cls, nbytes: int) -> int:
        c = cls.MIN_CLASS
        while c < nbytes:
            c <<= 1
        return c

    def alloc(self, nbytes: int) -> Optional[ArenaHandle]:
        """A block of >= ``nbytes`` from my partition, or None when the
        partition is exhausted (caller falls back to the scratch-file
        path — never blocks, never deadlocks)."""
        if nbytes <= 0:
            nbytes = 1
        from .. import faults
        if faults.fire("arena_alloc") == "drop":
            return None     # simulated exhaustion: caller's fallback path
        c = self._class_of(nbytes)
        if c > self.part_bytes:
            return None
        with self._lock:
            fl = self._free.get(c)
            if fl:
                off = fl.pop()
            elif self._brk + c <= self._part_hi:
                off = self._brk
                self._brk += c
            else:
                return None
            self._outstanding += 1
            self._in_use += c
            pv_allocs.inc()
            pv_hwm.mark(self._in_use)
            return ArenaHandle(off, c, nbytes)

    def free(self, h: ArenaHandle) -> None:
        with self._lock:
            self._free.setdefault(h.cls, []).append(h.off)
            self._outstanding -= 1
            self._in_use -= h.cls

    def view(self, off: int, nbytes: int) -> np.ndarray:
        """A uint8 view of the shared mapping (any rank's region)."""
        return self._buf[off:off + nbytes]

    @property
    def outstanding(self) -> int:
        """Live handle count (the Finalize leak check)."""
        with self._lock:
            return self._outstanding

    @property
    def bytes_in_use(self) -> int:
        with self._lock:
            return self._in_use

    # -- spill-consumed counters (oversize python packets staged here) ---
    def spill_consumed(self, src_i: int, dst_i: int) -> int:
        return int(self._spill[src_i * self.n_local + dst_i])

    def bump_spill(self, src_i: int, dst_i: int) -> None:
        # single writer per cell (only dst bumps for src), so a plain
        # load-add-store is race-free
        self._spill[src_i * self.n_local + dst_i] += 1

    # -- lifecycle -------------------------------------------------------
    def close(self, unlink: bool = False) -> None:
        try:
            self._buf = None
            self._spill = None
            self.mm.close()
        except (BufferError, ValueError):
            pass   # numpy views still alive — leave the mapping to GC
        try:
            os.close(self.fd)
        except OSError:
            pass
        if unlink:
            try:
                os.unlink(self.path)
            except OSError:
                pass

    @staticmethod
    def sweep_stale(dir_: str) -> int:
        """Crash cleanup: unlink arena segments whose creating process is
        gone (a SIGKILLed leader can't unlink its own). Called by the
        next leader to bootstrap on this node. Returns the sweep count."""
        n = 0
        try:
            names = os.listdir(dir_)
        except OSError:
            return 0
        for name in names:
            # arena segments AND per-job ring stems with their dotted
            # siblings (.flags/.fcoll/.fcoll2/.ntrace) — a SIGKILLed
            # leader leaves them all, and the sparse collective
            # segments' touched pages are real tmpfs memory
            m = re.match(r"mv2t-(?:arena|shm)-(\d+)-", name)
            if not m:
                continue
            pid = int(m.group(1))
            try:
                os.kill(pid, 0)
                continue             # creator alive
            except ProcessLookupError:
                pass
            except OSError:
                continue             # alive but not ours
            try:
                os.unlink(os.path.join(dir_, name))
                n += 1
            except OSError:
                pass
        if n:
            pv_reclaimed_dead.inc(n)
            log.info("swept %d stale arena segment(s) from %s", n, dir_)
        return n
