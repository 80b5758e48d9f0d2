"""Communicators (src/mpi/comm/ + MV2 2-level extensions, SURVEY §2.1).

A Comm is a Group bound to a context id pair (pt2pt ctx, coll ctx = ctx+1 —
the reference's context-id offsetting) plus the MV2-style extras: a per-comm
collective-ops table installed by the tuning layer (the
``comm_ptr->coll_fns`` seam, ch3i_comm.c:27-100) and lazily-built 2-level
sub-communicators (shmem/leader — create_2level_comm.c:57-96).
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import datatype as dtmod
from .attr import AttrCache
from .datatype import Datatype
from .errors import (ERRORS_ARE_FATAL, Errhandler, MPIException, MPI_ERR_COMM,
                     MPI_ERR_GROUP, MPI_ERR_RANK, MPI_ERR_TAG, mpi_assert)
from .group import Group
from .request import CompletedRequest, Request
from .status import ANY_SOURCE, ANY_TAG, PROC_NULL, Status, UNDEFINED

COMM_NULL = None


def _is_in_place(buf) -> bool:
    return type(buf).__name__ == "_InPlace"


from ..utils import is_device_array as _is_device  # noqa: E402


def _resolve(buf, count: Optional[int], datatype: Optional[Datatype],
             alt=None) -> Tuple[int, Datatype]:
    """Infer (count, datatype) from a numpy/device buffer when not given.
    ``alt`` is the fallback buffer when ``buf`` is MPI_IN_PLACE."""
    if _is_in_place(buf):
        buf = alt
    if datatype is None:
        if isinstance(buf, np.ndarray) or _is_device(buf):
            datatype = dtmod.from_numpy_dtype(np.dtype(buf.dtype))
        elif isinstance(buf, (bytes, bytearray, memoryview)):
            datatype = dtmod.BYTE
        elif buf is None:
            datatype = dtmod.BYTE
        else:
            raise MPIException(MPI_ERR_COMM, f"cannot infer datatype "
                               f"for {type(buf)}")
    if count is None:
        if isinstance(buf, np.ndarray) or _is_device(buf):
            count = int(buf.size)
        elif buf is None:
            count = 0
        else:
            count = len(buf) // max(datatype.size, 1)
    return count, datatype


class Comm:
    def __init__(self, universe, group: Group, context_id: int,
                 name: str = "", parent: Optional["Comm"] = None):
        self.u = universe
        self.group = group
        self.context_id = context_id
        self.name = name
        self.rank = group.rank_of_world(universe.world_rank)
        self.size = group.size
        self.attrs = AttrCache()
        self.errhandler: Errhandler = ERRORS_ARE_FATAL
        self.topo = None            # set by mvapich2_tpu.core.topo
        self.is_inter = False
        self.freed = False
        self.revoked = False        # ULFM (ft/ulfm.py)
        self._acked_failures: set = set()   # world ranks acked (ULFM)
        self._coll_seq = 0          # collective tag sequencing
        self._tag_tls = threading.local()   # call-time tag reservations
        self.coll_fns: Dict[str, Callable] = {}
        self._shmem_comm: Optional["Comm"] = None
        self._leader_comm: Optional["Comm"] = None
        self._twolevel_ready = False
        # device-mesh binding (ICI channel): set by parallel/mesh layer when
        # this comm maps onto a jax Mesh axis
        self.mesh_axis = None
        # this rank's device-collective channel (coll/device.py
        # install_device_coll): set by bind_universes on COMM_WORLD, and
        # by dup / create / create_group / split (_bind_derived) on a
        # communicator derived from a bound one, where the parent's
        # channel has one for the new group
        self.device_channel = None
        # revoke-packet routing + failure unwind need ctx -> comm
        universe.comms_by_ctx[context_id] = self
        # native data-plane ownership: when every member is co-resident
        # on this process's shm segment, the C engine (native/cplane.cpp)
        # owns envelope matching for BOTH this comm's contexts — senders
        # and receivers route identically because membership is a
        # comm-global property. Intercomm.__init__ re-evaluates with the
        # remote group included.
        self._plane_owned = False
        self._plane_bind()

    def _plane_bind(self) -> None:
        # ownership is wire-carried (PLANE_CTX_FLAG): nothing to register
        # with the C engine — sender and receiver derive the same answer
        # from the same membership. But a REUSED context id (mask
        # allocator, Comm.free -> release_context_id) may still carry
        # the C matcher's retired mark from its previous life, which
        # drops unmatched traffic: clear it for both contexts.
        pc = self.u.plane_channel
        self._plane_owned = bool(
            pc is not None and pc.plane
            and all(w in pc.local_index for w in self._plane_members()))
        if self._plane_owned and self.context_id >= 8:
            lib = pc._ring.lib
            lib.cp_ctx_enable(pc.plane, self.context_id)
            lib.cp_ctx_enable(pc.plane, self.ctx_coll)

    def _plane_members(self):
        return self.group.world_ranks

    # ------------------------------------------------------------------
    @property
    def ctx_pt2pt(self) -> int:
        return self.context_id

    @property
    def ctx_coll(self) -> int:
        return self.context_id + 1

    def world_of(self, rank: int) -> int:
        if rank in (PROC_NULL, ANY_SOURCE):
            return rank
        return self.group.world_of_rank(rank)

    def next_coll_tag(self) -> int:
        # a tag reserved at CALL time for this thread (a _CommWorker
        # running a deferred intercomm op — cshim._queued) takes
        # precedence over the live counter: the reservation preserves
        # call-order tag pairing across ranks even though the op itself
        # runs later, concurrently with DAG-scheduled collectives that
        # allocate at call time
        stack = getattr(self._tag_tls, "stack", None)
        if stack:
            return stack.pop(0)
        self._coll_seq = (self._coll_seq + 1) % 32768
        return self._coll_seq

    def push_reserved_coll_tag(self, tag: int) -> None:
        """Hand a call-time-reserved collective tag to the current
        thread; the next next_coll_tag() on this thread consumes it."""
        stack = getattr(self._tag_tls, "stack", None)
        if stack is None:
            stack = self._tag_tls.stack = []
        stack.append(tag)

    def drop_reserved_coll_tag(self, tag: int) -> None:
        """Retire an unconsumed reservation (op failed before its tag
        use) so it cannot leak into the thread's next operation."""
        stack = getattr(self._tag_tls, "stack", None)
        if stack and tag in stack:
            stack.remove(tag)

    def _check(self) -> None:
        if self.freed:
            raise MPIException(MPI_ERR_COMM, "communicator is freed")
        if self.revoked:
            from .errors import MPIX_ERR_REVOKED
            raise MPIException(MPIX_ERR_REVOKED, "communicator revoked")

    def _check_rank(self, r: int, allow_any: bool = False) -> None:
        if r == PROC_NULL or (allow_any and r == ANY_SOURCE):
            return
        mpi_assert(0 <= r < self.size, MPI_ERR_RANK,
                   f"rank {r} invalid for comm of size {self.size}")

    # ------------------------------------------------------------------
    # point-to-point
    # ------------------------------------------------------------------
    # Device buffers (pt2pt/protocol.py, "the device lane"): a jax.Array
    # given whole is sent as a device array the receiver owns. As a
    # receive buffer a jax.Array is a description (capacity, dtype,
    # shape; a device array cannot be written into, so it is neither
    # read nor written and may be the send array itself): recv, sendrecv
    # and mrecv then return the received device array, as the
    # collectives return their result, and an irecv's request holds it
    # as ``req.array`` once complete.
    def isend(self, buf, dest: int, tag: int = 0, count: Optional[int] = None,
              datatype: Optional[Datatype] = None,
              mode: str = "standard") -> Request:
        self._check()
        self._check_rank(dest)
        count, datatype = _resolve(buf, count, datatype)
        return self.u.protocol.isend(buf, count, datatype,
                                     self.world_of(dest), self.rank,
                                     self.ctx_pt2pt, tag, mode)

    def irecv(self, buf, source: int = ANY_SOURCE, tag: int = ANY_TAG,
              count: Optional[int] = None,
              datatype: Optional[Datatype] = None) -> Request:
        self._check()
        self._check_rank(source, allow_any=True)
        count, datatype = _resolve(buf, count, datatype)
        return self.u.protocol.irecv(buf, count, datatype, source,
                                     self.ctx_pt2pt, tag)

    def send(self, buf, dest: int, tag: int = 0, **kw) -> None:
        self.isend(buf, dest, tag, **kw).wait()

    def ssend(self, buf, dest: int, tag: int = 0, **kw) -> None:
        self.isend(buf, dest, tag, mode="sync", **kw).wait()

    def bsend(self, buf, dest: int, tag: int = 0, **kw) -> None:
        self.isend(buf, dest, tag, mode="buffered", **kw).wait()

    def rsend(self, buf, dest: int, tag: int = 0, **kw) -> None:
        # ready mode is deliberately treated as standard mode — an MPI
        # implementation may do so (MPI-3.1 §3.4); the erroneous-usage
        # detection (no matching receive posted) is intentionally
        # dropped, matching the reference's default RC path. Covered by
        # tests/progs/pt2pt/sendmodes_prog.py.
        self.isend(buf, dest, tag, mode="standard", **kw).wait()

    def issend(self, buf, dest: int, tag: int = 0, **kw) -> Request:
        return self.isend(buf, dest, tag, mode="sync", **kw)

    def recv(self, buf, source: int = ANY_SOURCE, tag: int = ANY_TAG,
             **kw):
        req = self.irecv(buf, source, tag, **kw)
        st = req.wait()
        return req.array if _is_device(buf) else st

    def sendrecv(self, sendbuf, dest: int, sendtag: int,
                 recvbuf, source: int, recvtag: int,
                 send_count: Optional[int] = None,
                 send_datatype: Optional[Datatype] = None,
                 recv_count: Optional[int] = None,
                 recv_datatype: Optional[Datatype] = None):
        rreq = self.irecv(recvbuf, source, recvtag, recv_count, recv_datatype)
        sreq = self.isend(sendbuf, dest, sendtag, send_count, send_datatype)
        st = rreq.wait()
        sreq.wait()
        return rreq.array if _is_device(recvbuf) else st

    def sendrecv_replace(self, buf, dest: int, sendtag: int, source: int,
                         recvtag: int):
        if _is_device(buf):     # nothing to replace in: the array comes back
            return self.sendrecv(buf, dest, sendtag, buf, source, recvtag)
        tmp = np.array(buf, copy=True)
        return self.sendrecv(tmp, dest, sendtag, buf, source, recvtag)

    def probe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG) -> Status:
        self._check()
        return self.u.protocol.probe(source, self.ctx_pt2pt, tag)

    def iprobe(self, source: int = ANY_SOURCE,
               tag: int = ANY_TAG) -> Optional[Status]:
        self._check()
        return self.u.protocol.iprobe(source, self.ctx_pt2pt, tag)

    def improbe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG):
        self._check()
        return self.u.protocol.improbe(source, self.ctx_pt2pt, tag)

    def mrecv(self, message, buf, count: Optional[int] = None,
              datatype: Optional[Datatype] = None):
        count, datatype = _resolve(buf, count, datatype)
        req = self.u.protocol.mrecv(message, buf, count, datatype)
        st = req.wait()
        return req.array if _is_device(buf) else st

    # persistent requests (MPI_Send_init / MPI_Recv_init / MPI_Start)
    def send_init(self, buf, dest: int, tag: int = 0, **kw) -> Request:
        req = Request(self.u.engine, "persistent-send")
        req.persistent = True

        def starter(r):
            i = self.isend(buf, dest, tag, **kw)
            # MPI_Cancel on the persistent handle cancels the active
            # communication (MPI-3.1 §3.9) — even one that is already
            # locally complete (eager/buffered), matching send-cancel
            # semantics; cancelled-ness lands in r.status at resolution
            r._cancel_override = True

            def pcancel():
                with self.u.engine.mutex:
                    r.complete_flag = False
                i.cancel()

                def redone(ireq):
                    r.status.cancelled = bool(
                        getattr(ireq, "cancelled", False)
                        or ireq.status.cancelled)
                    r.complete(ireq.error)
                i.add_callback(redone)
                return False
            r._cancel_fn = pcancel

            def done(ireq):
                r.status.cancelled = bool(
                    getattr(ireq, "cancelled", False)
                    or ireq.status.cancelled)
                r.complete(ireq.error)

            i.add_callback(done)

        req._start_fn = starter
        return req

    def recv_init(self, buf, source: int = ANY_SOURCE, tag: int = ANY_TAG,
                  **kw) -> Request:
        req = Request(self.u.engine, "persistent-recv")
        req.persistent = True

        def starter(r):
            i = self.irecv(buf, source, tag, **kw)
            r._cancel_fn = (lambda: (i.cancel(), False)[1]) \
                if not i.complete_flag else None

            def done(ireq):
                r.status = ireq.status
                r.array = ireq.array
                r.status.cancelled = bool(
                    getattr(ireq, "cancelled", False)
                    or ireq.status.cancelled)
                r.complete(ireq.error)

            i.add_callback(done)

        req._start_fn = starter
        return req

    # persistent collectives (MPI_Allreduce_init & friends, MPI-4 §6.12)
    def _coll_init(self, kind: str, ifn, warm=None) -> Request:
        """Generic persistent-collective factory: the inactive request
        re-launches the ``ifn`` nonblocking twin on every start().
        ``warm`` runs once at init — the device channel uses it to build
        (or exec-cache fetch) the collective's program signatures so
        each start() pays rendezvous + dispatch only (coll/device.py
        prewarm_persistent); starts that ride the device NBC tier count
        dev_persistent_starts."""
        req = Request(self.u.engine, f"persistent-{kind}")
        req.persistent = True
        if warm is not None:
            try:
                warm()
            except Exception:   # noqa: BLE001 — warm-up is best-effort
                pass

        def starter(r):
            i = ifn()
            if getattr(i, "device_nbc", False):
                from .. import mpit
                mpit.pvar("dev_persistent_starts").inc()
            if not i.complete_flag:
                def pcancel():
                    try:
                        i.cancel()
                    except MPIException:
                        pass
                    return False
                r._cancel_fn = pcancel
            else:
                r._cancel_fn = None

            def done(ireq):
                r.complete(ireq.error)

            i.add_callback(done)

        req._start_fn = starter
        return req

    def _coll_warm(self, name: str, *a):
        """Device pre-warm thunk for ``_coll_init`` (None when this comm
        has no device channel)."""
        if self.device_channel is None:
            return None
        from ..coll import device as _dev
        return lambda: _dev.prewarm_persistent(self, name, *a)

    def allreduce_init(self, sendbuf, recvbuf, op=None,
                       count: Optional[int] = None,
                       datatype: Optional[Datatype] = None) -> Request:
        from . import op as opmod
        op = op or opmod.SUM
        count, datatype = _resolve(sendbuf, count, datatype, alt=recvbuf)
        return self._coll_init(
            "allreduce",
            lambda: self.iallreduce(sendbuf, recvbuf, op, count,
                                    datatype),
            self._coll_warm("allreduce", sendbuf, recvbuf, count,
                            datatype, op))

    def bcast_init(self, buf, root: int = 0,
                   count: Optional[int] = None,
                   datatype: Optional[Datatype] = None) -> Request:
        count, datatype = _resolve(buf, count, datatype)
        return self._coll_init(
            "bcast",
            lambda: self.ibcast(buf, root, count, datatype),
            self._coll_warm("bcast", buf, count, datatype, root))

    def allgather_init(self, sendbuf, recvbuf,
                       count: Optional[int] = None,
                       datatype: Optional[Datatype] = None) -> Request:
        count, datatype = _resolve(sendbuf, count, datatype)
        return self._coll_init(
            "allgather",
            lambda: self.iallgather(sendbuf, recvbuf, count, datatype),
            self._coll_warm("allgather", sendbuf, recvbuf, count,
                            datatype))

    def alltoall_init(self, sendbuf, recvbuf,
                      count: Optional[int] = None,
                      datatype: Optional[Datatype] = None) -> Request:
        if count is None:
            count = np.asarray(sendbuf).size \
                // getattr(self, "remote_size", self.size)
        _, datatype = _resolve(sendbuf, count, datatype)
        return self._coll_init(
            "alltoall",
            lambda: self.ialltoall(sendbuf, recvbuf, count, datatype),
            self._coll_warm("alltoall", sendbuf, recvbuf, count,
                            datatype))

    def alltoallv_init(self, sendbuf, sendcounts, sdispls, recvbuf,
                       recvcounts, rdispls,
                       datatype: Optional[Datatype] = None) -> Request:
        _, datatype = _resolve(sendbuf, None, datatype)
        return self._coll_init(
            "alltoallv",
            lambda: self.ialltoallv(sendbuf, sendcounts, sdispls,
                                    recvbuf, recvcounts, rdispls,
                                    datatype),
            self._coll_warm("alltoallv", sendbuf, list(sendcounts),
                            list(sdispls) if sdispls is not None
                            else None, recvbuf, list(recvcounts),
                            list(rdispls) if rdispls is not None
                            else None, datatype))

    def reduce_init(self, sendbuf, recvbuf, op=None, root: int = 0,
                    count: Optional[int] = None,
                    datatype: Optional[Datatype] = None) -> Request:
        from . import op as opmod
        op = op or opmod.SUM
        count, datatype = _resolve(sendbuf, count, datatype, alt=recvbuf)
        return self._coll_init(
            "reduce",
            lambda: self.ireduce(sendbuf, recvbuf, op, root, count,
                                 datatype))

    def barrier_init(self) -> Request:
        return self._coll_init("barrier", lambda: self.ibarrier())

    # ------------------------------------------------------------------
    # collectives — dispatch through coll_fns (the MV2 seam)
    # ------------------------------------------------------------------
    def _coll(self, name: str):
        if not self.coll_fns:
            from ..coll.tuning import install_coll_ops
            install_coll_ops(self)
        return self.coll_fns[name]

    def _stage_if_unbound(self, sendbuf, recvbuf):
        """Device-array buffers on a comm with no device channel are
        staged through the host (result comes back as numpy). A device
        recvbuf cannot be written in place (jax arrays are immutable), so
        it needs the mesh-bound path."""
        if self.device_channel is not None:
            return sendbuf, recvbuf
        if _is_device(recvbuf):
            raise MPIException(
                MPI_ERR_COMM, "device-array recvbuf requires a mesh-bound "
                "communicator (see coll/device.py)")
        if _is_device(sendbuf):
            if self.u.device is not None:
                # the world is bound and this communicator is not (a
                # sub-mesh, shrink's, build_2level's): counted
                from ..coll.device import note_host_comm
                note_host_comm(self)
            sendbuf = np.asarray(sendbuf)
        return sendbuf, recvbuf

    def barrier(self) -> None:
        self._check()
        self._coll("barrier")(self)

    def bcast(self, buf, root: int = 0, count: Optional[int] = None,
              datatype: Optional[Datatype] = None):
        self._check()
        if self.device_channel is not None:
            # a signature this rank has decided before runs on what it
            # filed then (coll/device.py plan_of); None: decide it here
            ret = self.device_channel.plan_of("bcast", buf, count,
                                              datatype, root=root)
            if ret is not None:
                ret = self.device_channel.run_plan(self, ret, buf, buf)
                return ret if ret is not None else buf
        count, datatype = _resolve(buf, count, datatype)
        staged, _ = self._stage_if_unbound(buf, None)
        ret = self._coll("bcast")(self, staged, count, datatype, root)
        if ret is not None:
            return ret
        return staged if staged is not buf else buf

    def reduce(self, sendbuf, recvbuf=None, op=None, root: int = 0,
               count: Optional[int] = None,
               datatype: Optional[Datatype] = None):
        self._check()
        if self.device_channel is not None:
            ret = self.device_channel.plan_of("reduce", sendbuf, count,
                                              datatype, op, root)
            if ret is not None:
                ret = self.device_channel.run_plan(self, ret, sendbuf, recvbuf)
                return ret if ret is not None else recvbuf
        from . import op as opmod
        op = op or opmod.SUM
        count, datatype = _resolve(sendbuf, count, datatype, alt=recvbuf)
        sendbuf, recvbuf = self._stage_if_unbound(sendbuf, recvbuf)
        if recvbuf is None and self.rank == root and not _is_device(sendbuf):
            recvbuf = np.empty_like(np.asarray(sendbuf))
        ret = self._coll("reduce")(self, sendbuf, recvbuf, count, datatype,
                                   op, root)
        return ret if ret is not None else recvbuf

    def allreduce(self, sendbuf, recvbuf=None, op=None,
                  count: Optional[int] = None,
                  datatype: Optional[Datatype] = None):
        self._check()
        if self.device_channel is not None:
            ret = self.device_channel.plan_of("allreduce", sendbuf, count,
                                              datatype, op)
            if ret is not None:
                ret = self.device_channel.run_plan(self, ret, sendbuf, recvbuf)
                return ret if ret is not None else recvbuf
        from . import op as opmod
        op = op or opmod.SUM
        count, datatype = _resolve(sendbuf, count, datatype, alt=recvbuf)
        sendbuf, recvbuf = self._stage_if_unbound(sendbuf, recvbuf)
        if recvbuf is None and not _is_device(sendbuf):
            recvbuf = np.empty_like(np.asarray(sendbuf))
        ret = self._coll("allreduce")(self, sendbuf, recvbuf, count,
                                      datatype, op)
        return ret if ret is not None else recvbuf

    def allgather(self, sendbuf, recvbuf=None, count: Optional[int] = None,
                  datatype: Optional[Datatype] = None):
        self._check()
        if self.device_channel is not None:
            ret = self.device_channel.plan_of("allgather", sendbuf, count,
                                              datatype)
            if ret is not None:
                ret = self.device_channel.run_plan(self, ret, sendbuf, recvbuf)
                return ret if ret is not None else recvbuf
        count, datatype = _resolve(sendbuf, count, datatype, alt=recvbuf)
        sendbuf, recvbuf = self._stage_if_unbound(sendbuf, recvbuf)
        if recvbuf is None and not _is_device(sendbuf):
            sb = np.asarray(sendbuf)
            recvbuf = np.empty((self.size * count,), dtype=sb.dtype)
        ret = self._coll("allgather")(self, sendbuf, recvbuf, count, datatype)
        return ret if ret is not None else recvbuf

    def gather(self, sendbuf, recvbuf=None, root: int = 0,
               count: Optional[int] = None,
               datatype: Optional[Datatype] = None):
        self._check()
        count, datatype = _resolve(sendbuf, count, datatype, alt=recvbuf)
        if recvbuf is None and self.rank == root:
            sb = np.asarray(sendbuf)
            recvbuf = np.empty((self.size * count,), dtype=sb.dtype)
        self._coll("gather")(self, sendbuf, recvbuf, count, datatype, root)
        return recvbuf

    def scatter(self, sendbuf, recvbuf, root: int = 0,
                count: Optional[int] = None,
                datatype: Optional[Datatype] = None):
        self._check()
        count, datatype = _resolve(recvbuf, count, datatype)
        self._coll("scatter")(self, sendbuf, recvbuf, count, datatype, root)
        return recvbuf

    def alltoall(self, sendbuf, recvbuf=None, count: Optional[int] = None,
                 datatype: Optional[Datatype] = None):
        self._check()
        if self.device_channel is not None:
            ret = self.device_channel.plan_of("alltoall", sendbuf, count,
                                              datatype)
            if ret is not None:
                ret = self.device_channel.run_plan(self, ret, sendbuf, recvbuf)
                return ret if ret is not None else recvbuf
        if count is None:
            sb = recvbuf if _is_in_place(sendbuf) else sendbuf
            count = int(getattr(sb, "size", 0) or len(sb)) // self.size
        _, datatype = _resolve(sendbuf, count, datatype, alt=recvbuf)
        sendbuf, recvbuf = self._stage_if_unbound(sendbuf, recvbuf)
        if recvbuf is None and not _is_device(sendbuf):
            recvbuf = np.empty_like(np.asarray(sendbuf))
        ret = self._coll("alltoall")(self, sendbuf, recvbuf, count, datatype)
        return ret if ret is not None else recvbuf

    def reduce_scatter_block(self, sendbuf, recvbuf=None, op=None,
                             count: Optional[int] = None,
                             datatype: Optional[Datatype] = None):
        self._check()
        if self.device_channel is not None:
            ret = self.device_channel.plan_of("reduce_scatter_block",
                                              sendbuf, count, datatype, op)
            if ret is not None:
                ret = self.device_channel.run_plan(self, ret, sendbuf, recvbuf)
                return ret if ret is not None else recvbuf
        from . import op as opmod
        op = op or opmod.SUM
        if count is None:
            sb = recvbuf if _is_in_place(sendbuf) else sendbuf
            count = int(getattr(sb, "size", 0) or len(sb)) // self.size
        _, datatype = _resolve(sendbuf, count, datatype, alt=recvbuf)
        sendbuf, recvbuf = self._stage_if_unbound(sendbuf, recvbuf)
        if recvbuf is None and not _is_device(sendbuf):
            sb = np.asarray(sendbuf)
            recvbuf = np.empty((count,), dtype=sb.dtype)
        ret = self._coll("reduce_scatter_block")(self, sendbuf, recvbuf,
                                                 count, datatype, op)
        return ret if ret is not None else recvbuf

    def reduce_scatter(self, sendbuf, recvbuf=None, counts=None, op=None,
                       datatype: Optional[Datatype] = None):
        """Irregular-counts reduce_scatter (MPI-3.1 §5.10); dispatches
        through coll_fns so intercomms take the inter algorithm."""
        self._check()
        from . import op as opmod
        op = op or opmod.SUM
        if counts is None:
            sb = recvbuf if _is_in_place(sendbuf) else sendbuf
            n = int(getattr(sb, "size", 0) or len(sb)) // self.size
            counts = [n] * self.size
        _, datatype = _resolve(sendbuf, None, datatype, alt=recvbuf)
        if recvbuf is None:
            sb = np.asarray(sendbuf)
            recvbuf = np.empty((list(counts)[self.rank],), dtype=sb.dtype)
        self._coll("reduce_scatter")(self, sendbuf, recvbuf,
                                     list(counts), datatype, op)
        return recvbuf

    def scan(self, sendbuf, recvbuf=None, op=None,
             count: Optional[int] = None,
             datatype: Optional[Datatype] = None):
        self._check()
        from . import op as opmod
        op = op or opmod.SUM
        count, datatype = _resolve(sendbuf, count, datatype, alt=recvbuf)
        if recvbuf is None:
            recvbuf = np.empty_like(np.asarray(sendbuf))
        self._coll("scan")(self, sendbuf, recvbuf, count, datatype, op)
        return recvbuf

    def exscan(self, sendbuf, recvbuf=None, op=None,
               count: Optional[int] = None,
               datatype: Optional[Datatype] = None):
        self._check()
        from . import op as opmod
        op = op or opmod.SUM
        count, datatype = _resolve(sendbuf, count, datatype, alt=recvbuf)
        if recvbuf is None:
            recvbuf = np.empty_like(np.asarray(sendbuf))
        self._coll("exscan")(self, sendbuf, recvbuf, count, datatype, op)
        return recvbuf

    def allgatherv(self, sendbuf, recvbuf, counts: Sequence[int],
                   displs: Optional[Sequence[int]] = None,
                   datatype: Optional[Datatype] = None):
        self._check()
        _, datatype = _resolve(sendbuf, None, datatype)
        self._coll("allgatherv")(self, sendbuf, recvbuf, list(counts),
                                 list(displs) if displs is not None else None,
                                 datatype)
        return recvbuf

    def alltoallv(self, sendbuf, sendcounts, sdispls, recvbuf, recvcounts,
                  rdispls, datatype: Optional[Datatype] = None):
        self._check()
        _, datatype = _resolve(sendbuf, None, datatype)
        self._coll("alltoallv")(self, sendbuf, list(sendcounts), list(sdispls),
                                recvbuf, list(recvcounts), list(rdispls),
                                datatype)
        return recvbuf

    def gatherv(self, sendbuf, recvbuf, counts, displs=None, root: int = 0,
                datatype: Optional[Datatype] = None):
        self._check()
        _, datatype = _resolve(sendbuf, None, datatype)
        self._coll("gatherv")(self, sendbuf, recvbuf, list(counts),
                              list(displs) if displs is not None else None,
                              datatype, root)
        return recvbuf

    def scatterv(self, sendbuf, counts, displs, recvbuf, root: int = 0,
                 datatype: Optional[Datatype] = None):
        self._check()
        _, datatype = _resolve(recvbuf, None, datatype)
        self._coll("scatterv")(self, sendbuf,
                               list(counts) if counts is not None else None,
                               list(displs) if displs is not None else None,
                               recvbuf, datatype, root)
        return recvbuf

    # nonblocking collectives
    def ibarrier(self) -> Request:
        from ..coll import nonblocking as nb
        return nb.ibarrier(self)

    def ibcast(self, buf, root: int = 0, count: Optional[int] = None,
               datatype: Optional[Datatype] = None) -> Request:
        from ..coll import nonblocking as nb
        count, datatype = _resolve(buf, count, datatype)
        return nb.ibcast(self, buf, count, datatype, root)

    def iallreduce(self, sendbuf, recvbuf, op=None,
                   count: Optional[int] = None,
                   datatype: Optional[Datatype] = None) -> Request:
        from ..coll import nonblocking as nb
        from . import op as opmod
        op = op or opmod.SUM
        count, datatype = _resolve(sendbuf, count, datatype)
        return nb.iallreduce(self, sendbuf, recvbuf, count, datatype, op)

    def iallgather(self, sendbuf, recvbuf, count: Optional[int] = None,
                   datatype: Optional[Datatype] = None) -> Request:
        from ..coll import nonblocking as nb
        count, datatype = _resolve(sendbuf, count, datatype)
        return nb.iallgather(self, sendbuf, recvbuf, count, datatype)

    def ialltoall(self, sendbuf, recvbuf, count: Optional[int] = None,
                  datatype: Optional[Datatype] = None) -> Request:
        from ..coll import nonblocking as nb
        if count is None:
            # intercomm blocks address the REMOTE group (MPI-3.1 §5.8)
            count = np.asarray(sendbuf).size \
                // getattr(self, "remote_size", self.size)
        _, datatype = _resolve(sendbuf, count, datatype)
        return nb.ialltoall(self, sendbuf, recvbuf, count, datatype)

    def ireduce(self, sendbuf, recvbuf, op=None, root: int = 0,
                count: Optional[int] = None,
                datatype: Optional[Datatype] = None) -> Request:
        from ..coll import nonblocking as nb
        from . import op as opmod
        op = op or opmod.SUM
        count, datatype = _resolve(sendbuf, count, datatype, alt=recvbuf)
        return nb.ireduce(self, sendbuf, recvbuf, count, datatype, op,
                          root)

    def iscan(self, sendbuf, recvbuf, op=None,
              count: Optional[int] = None,
              datatype: Optional[Datatype] = None) -> Request:
        from ..coll import nonblocking as nb
        from . import op as opmod
        op = op or opmod.SUM
        count, datatype = _resolve(sendbuf, count, datatype, alt=recvbuf)
        return nb.iscan(self, sendbuf, recvbuf, count, datatype, op)

    def iexscan(self, sendbuf, recvbuf, op=None,
                count: Optional[int] = None,
                datatype: Optional[Datatype] = None) -> Request:
        from ..coll import nonblocking as nb
        from . import op as opmod
        op = op or opmod.SUM
        count, datatype = _resolve(sendbuf, count, datatype, alt=recvbuf)
        return nb.iexscan(self, sendbuf, recvbuf, count, datatype, op)

    def igather(self, sendbuf, recvbuf=None, root: int = 0,
                count: Optional[int] = None,
                datatype: Optional[Datatype] = None) -> Request:
        from ..coll import nonblocking as nb
        count, datatype = _resolve(sendbuf, count, datatype, alt=recvbuf)
        return nb.igather(self, sendbuf, recvbuf, count, datatype, root)

    def iscatter(self, sendbuf, recvbuf, root: int = 0,
                 count: Optional[int] = None,
                 datatype: Optional[Datatype] = None) -> Request:
        from ..coll import nonblocking as nb
        count, datatype = _resolve(recvbuf, count, datatype)
        return nb.iscatter(self, sendbuf, recvbuf, count, datatype, root)

    def igatherv(self, sendbuf, recvbuf, counts, displs=None,
                 root: int = 0,
                 datatype: Optional[Datatype] = None) -> Request:
        from ..coll import nonblocking as nb
        _, datatype = _resolve(sendbuf, None, datatype)
        sendcount = int(np.asarray(sendbuf).size)
        return nb.igatherv(self, sendbuf, sendcount, recvbuf,
                           list(counts) if counts is not None else None,
                           list(displs) if displs is not None else None,
                           datatype, root)

    def iscatterv(self, sendbuf, counts, displs, recvbuf,
                  root: int = 0,
                  datatype: Optional[Datatype] = None) -> Request:
        from ..coll import nonblocking as nb
        _, datatype = _resolve(recvbuf, None, datatype)
        recvcount = int(np.asarray(recvbuf).size) \
            if recvbuf is not None else 0
        return nb.iscatterv(self, sendbuf,
                            list(counts) if counts is not None else None,
                            list(displs) if displs is not None else None,
                            recvbuf, recvcount, datatype, root)

    def iallgatherv(self, sendbuf, recvbuf, counts, displs=None,
                    datatype: Optional[Datatype] = None) -> Request:
        from ..coll import nonblocking as nb
        _, datatype = _resolve(sendbuf, None, datatype)
        sendcount = int(np.asarray(sendbuf).size)
        return nb.iallgatherv(self, sendbuf, sendcount, recvbuf,
                              list(counts),
                              list(displs) if displs is not None
                              else None, datatype)

    def ialltoallv(self, sendbuf, sendcounts, sdispls, recvbuf,
                   recvcounts, rdispls,
                   datatype: Optional[Datatype] = None) -> Request:
        from ..coll import nonblocking as nb
        _, datatype = _resolve(sendbuf, None, datatype)
        return nb.ialltoallv(self, sendbuf, list(sendcounts),
                             list(sdispls) if sdispls is not None
                             else None, recvbuf, list(recvcounts),
                             list(rdispls) if rdispls is not None
                             else None, datatype)

    def ireduce_scatter(self, sendbuf, recvbuf, counts, op=None,
                        datatype: Optional[Datatype] = None) -> Request:
        from ..coll import nonblocking as nb
        from . import op as opmod
        op = op or opmod.SUM
        _, datatype = _resolve(sendbuf, None, datatype)
        return nb.ireduce_scatter(self, sendbuf, recvbuf, list(counts),
                                  datatype, op)

    def ireduce_scatter_block(self, sendbuf, recvbuf, op=None,
                              count: Optional[int] = None,
                              datatype: Optional[Datatype] = None
                              ) -> Request:
        from ..coll import nonblocking as nb
        from . import op as opmod
        op = op or opmod.SUM
        if count is None:
            count = int(np.asarray(sendbuf).size) // self.size
        _, datatype = _resolve(sendbuf, count, datatype)
        return nb.ireduce_scatter_block(self, sendbuf, recvbuf, count,
                                        datatype, op)

    # ------------------------------------------------------------------
    # communicator management
    # ------------------------------------------------------------------
    def dup(self) -> "Comm":
        self._check()
        ctx = self.u.allocate_context_id(self)
        new = Comm(self.u, self.group, ctx, self.name + "_dup", self)
        self.attrs.copy_all(self, new.attrs)
        new.errhandler = self.errhandler
        new.topo = self.topo
        return self._bind_derived(new)

    def create(self, group: Group) -> Optional["Comm"]:
        """MPI_Comm_create: collective over self; returns None for
        non-members."""
        self._check()
        # the group must be a subset of this comm's group (MPI-3.1
        # §6.4.2; errors/comm/ccreate1.c builds a high-ranks group and
        # hands it to a low-ranks comm). Checked BEFORE the context
        # collective: every member sees the same group, so the verdict
        # is symmetric and nobody is left waiting in the allreduce.
        mine = {self.group.world_of_rank(r)
                for r in range(self.group.size)}
        for r in range(group.size):
            if group.world_of_rank(r) not in mine:
                raise MPIException(
                    MPI_ERR_GROUP,
                    "Comm_create group is not a subset of the "
                    "communicator's group")
        ctx = self.u.allocate_context_id(self)
        if group.rank_of_world(self.u.world_rank) == UNDEFINED:
            # a non-member burns no budget: hand the bit straight back
            # (MPICH likewise frees the id on non-members immediately)
            self.u.release_context_id(ctx)
            return None
        return self._bind_derived(
            Comm(self.u, group, ctx, self.name + "_create", self))

    def create_group(self, group: Group, tag: int = 0) -> Optional["Comm"]:
        """MPI_Comm_create_group: collective only over ``group``'s members
        (MPI-3.1 §6.4.2) — non-members return immediately with None.
        Context agreement runs a binomial max-reduce+bcast over the group
        members using parent pt2pt with ``tag`` (the standard's contract:
        the tag namespace of the parent carries the internal traffic).
        Disjoint groups may agree on equal ctx ids concurrently; matching
        keys are (ctx, src, tag) and member sets are disjoint, so the
        namespaces cannot collide."""
        self._check()
        me = group.rank_of_world(self.u.world_rank)
        if me == UNDEFINED:
            return None
        m = group.size
        if m == 1:
            # single-member: no agreement (see alloc_context_local)
            return self._bind_derived(
                Comm(self.u, group, self.u.alloc_context_local(),
                     self.name + "_create_group", self))
        parent_of = {g: self.group.rank_of_world(group.world_of_rank(g))
                     for g in range(m)}
        # AND-combine the members' availability masks (the same
        # MPIR_Get_contextid discipline allocate_context_id runs over a
        # full comm, here as binomial reduce+bcast over group members,
        # carrying the guarded payload so concurrent-thread agreements
        # on other comms force a collective retry instead of a
        # duplicate id — threads/comm/comm_create_group_threads)
        key = (self.context_id, tag)
        while True:
            val, own = self.u.ctx_payload(key)
            try:
                other = np.empty_like(val)
                # binomial reduce (bitwise AND) to group rank 0
                mask = 1
                while mask < m:
                    if me & mask:
                        self.send(val, parent_of[me & ~mask], tag)
                        break
                    partner = me | mask
                    if partner < m:
                        self.recv(other, parent_of[partner], tag)
                        val &= other
                    mask <<= 1
                # binomial bcast of the agreed payload from group rank 0
                mask = 1
                while mask < m:
                    if me & mask:
                        self.recv(val, parent_of[me - mask], tag)
                        break
                    mask <<= 1
                mask >>= 1
                while mask > 0:
                    if me + mask < m:
                        self.send(val, parent_of[me + mask], tag)
                    mask >>= 1
            except BaseException:
                self.u.ctx_release(own, key, done=True)
                raise
            ctx = self.u.ctx_resolve(val, own, key)
            if ctx >= 0:
                break
            import time
            time.sleep(0.0002)
        return self._bind_derived(
            Comm(self.u, group, ctx, self.name + "_create_group", self))

    def _plane_gather(self, payload: np.ndarray) -> Optional[np.ndarray]:
        """Allgather one small fixed-size record from every member
        through the C engine (cp_coll_gather) in a single ctypes call —
        the comm-management control collectives are latency-bound chains
        of tiny messages, and per-STEP interpreter frames are what makes
        split/free churn (comm/ctxsplit.c) miss the suite budget.
        Returns the (size, paysz) table, or None when the plane can't
        take it (caller runs the stepped python algorithms)."""
        pc = self.u.plane_channel
        if (pc is None or not pc.plane or self.is_inter
                or not self._plane_owned or self.size > 64):
            return None
        if not pc._wired and self.size > 1:
            # lazy-wiring gate: cp_coll_gather parks in C, where this
            # rank's wiring cards would never publish — and a peer
            # blocked in ITS wire gate (e.g. a sub-comm collective)
            # may be waiting on exactly those cards. A comm-management
            # collective is a safe blocking point (all members arrive),
            # and ensure_wired publishes before it waits.
            pc.ensure_wired()
        payload = np.ascontiguousarray(payload).view(np.uint8).reshape(-1)
        paysz = payload.nbytes
        cap = pc.plane_eager_max()
        if cap and paysz > cap:
            return None
        rings = np.array([pc.local_index[w]
                          for w in self.group.world_ranks],
                         dtype=np.int32)
        table = np.empty((self.size, paysz), dtype=np.uint8)
        lib = pc._ring.lib
        rc = lib.cp_coll_gather(pc.plane, self.ctx_coll, self.rank,
                                self.size, rings.ctypes.data,
                                payload.ctypes.data, paysz,
                                table.ctypes.data)
        if rc == -2:
            from ..core.errors import MPIX_ERR_PROC_FAILED
            raise MPIException(MPIX_ERR_PROC_FAILED,
                               "peer failed during comm-management "
                               "collective")
        if rc != 0:
            return None
        return table

    def split(self, color: int, key: int = 0,
              _bind: bool = True) -> Optional["Comm"]:
        """MPI_Comm_split. ``_bind`` is for the library's own splits
        (``build_2level``, the net2 bridge): their communicators carry
        the host algorithms' host buffers and get no device channel."""
        self._check()
        my_color = int(color) if color is not None else UNDEFINED
        mine = np.array([my_color, key, self.u.world_rank],
                        dtype=np.int64)
        # fused agreement: ONE plane gather carries the (color, key,
        # world) triple AND the guarded context-id payload, replacing
        # the allgather + mask-allreduce pair (the same information the
        # reference moves in MPIR_Comm_split_impl + MPIR_Get_contextid,
        # commutil.c — here one C-engine round per attempt)
        if self.size == 1:
            # single-member: no agreement (see alloc_context_local)
            if my_color == UNDEFINED:
                return None
            return self._bind_derived(
                Comm(self.u, Group([self.u.world_rank]),
                     self.u.alloc_context_local(),
                     f"{self.name}_split", self), _bind)
        allv = None
        ctx = -1
        agree_key = (self.context_id, 0)
        while ctx < 0:
            pay, own = self.u.ctx_payload(agree_key)
            try:
                fused = np.empty(3 + len(pay), dtype=np.uint64)
                fused[:3] = mine.view(np.uint64)
                fused[3:] = pay
                table = self._plane_gather(fused)
            except BaseException:
                self.u.ctx_release(own, agree_key, done=True)
                raise
            if table is None:
                # stepped fallback: allgather triples, then the mask
                # agreement collective (release the mask first — the
                # stepped path takes it again per attempt)
                self.u.ctx_release(own, agree_key, done=True)
                allv = np.empty(3 * self.size, dtype=np.int64)
                self.allgather(mine, allv, count=3)
                ctx = self.u.allocate_context_id(self)
                if my_color == UNDEFINED:
                    # UNDEFINED color burns no budget (see create())
                    self.u.release_context_id(ctx)
                break
            rows = table.view(np.uint64).reshape(self.size, -1)
            allv = rows[:, :3].copy().view(np.int64).reshape(-1)
            agreed = np.bitwise_and.reduce(rows[:, 3:], axis=0)
            ctx = self.u.ctx_resolve(agreed, own, agree_key,
                                     claim=my_color != UNDEFINED)
            if ctx < 0:
                import time
                time.sleep(0.0002)
        if my_color == UNDEFINED:
            return None
        members = []
        for r in range(self.size):
            c, k, wr = (int(allv[3 * r]), int(allv[3 * r + 1]),
                        int(allv[3 * r + 2]))
            if c == my_color:
                members.append((k, r, wr))   # sort by key, then comm rank
        members.sort()
        return self._bind_derived(
            Comm(self.u, Group([wr for _, _, wr in members]), ctx,
                 f"{self.name}_split", self), _bind)

    def _bind_derived(self, new: "Comm", bind: bool = True) -> "Comm":
        """``new`` was derived from this communicator, its context id is
        agreed and this rank is a member: where this one is device-bound
        the parent's channel is asked for a channel of the new group
        (coll/device.py bind_derived), so that a device array handed to
        ``new``'s collectives stays on the device."""
        if bind and self.device_channel is not None:
            from ..coll.device import bind_derived
            bind_derived(self, new)
        return new

    def split_type_shared(self, key: int = 0) -> "Comm":
        """MPI_Comm_split_type(COMM_TYPE_SHARED): ranks on my node."""
        return self.split(self.u.node_ids[self.u.world_rank], key)

    def compare(self, other: "Comm") -> str:
        if self is other:
            return "ident"
        g = self.group.compare(other.group)
        if g == "ident":
            return "congruent"
        return g

    def free(self) -> None:
        if self.freed:
            return
        self.attrs.delete_all(self)
        self.u.comms_by_ctx.pop(self.context_id, None)
        if self.device_channel is not None:
            # a derived communicator's rendezvous goes with its last member
            self.device_channel.release()
        # return a mask-allocated context id to the availability pool
        # (MPIR-style reuse: dup/free loops must never exhaust the
        # 2048-comm budget — comm/ctxalloc.c, comm/ctxsplit.c)
        self.u.release_context_id(self.context_id)
        if self._plane_owned:
            pch = getattr(self.u, "plane_channel", None)
            if pch is not None and getattr(pch, "plane", None):
                # retire both contexts in the C matcher so unreceived
                # messages for the freed comm don't accumulate in the
                # unexpected/parked queues for the process lifetime
                lib = pch._ring.lib
                lib.cp_ctx_disable(pch.plane, self.context_id)
                lib.cp_ctx_disable(pch.plane, self.ctx_coll)
        self._plane_owned = False
        seg = getattr(self, "_shm_coll_seg", None)
        if seg not in (None, False):       # slotted shm collective segment
            seg.free()
        self.freed = True

    # ------------------------------------------------------------------
    # MV2-style 2-level substructure (create_2level_comm analog)
    # ------------------------------------------------------------------
    def build_2level(self) -> Tuple[Optional["Comm"], Optional["Comm"]]:
        """Returns (shmem_comm, leader_comm). shmem = ranks on my node;
        leader = lowest rank of each node (None on non-leaders)."""
        if self._twolevel_ready:
            return self._shmem_comm, self._leader_comm
        node_of_me = self.u.node_ids[self.u.world_rank]
        shmem = self.split(node_of_me, self.rank, _bind=False)
        am_leader = shmem.rank == 0
        leader = self.split(0 if am_leader else None, self.rank,
                            _bind=False)
        self._shmem_comm = shmem
        self._leader_comm = leader if am_leader else None
        self._twolevel_ready = True
        return self._shmem_comm, self._leader_comm

    # ------------------------------------------------------------------
    # topologies (src/mpi/topo/ analog; core/topo.py)
    # ------------------------------------------------------------------
    def cart_create(self, dims, periods=None, reorder: bool = False):
        from . import topo as _topo
        if periods is None:
            periods = [False] * len(dims)
        return _topo.cart_create(self, dims, periods, reorder)

    def graph_create(self, index, edges, reorder: bool = False):
        from . import topo as _topo
        return _topo.graph_create(self, index, edges, reorder)

    def dist_graph_create_adjacent(self, sources, destinations,
                                   sweights=None, dweights=None,
                                   reorder: bool = False):
        from . import topo as _topo
        return _topo.dist_graph_create_adjacent(self, sources, destinations,
                                                sweights, dweights, reorder)

    def dist_graph_create(self, sources, degrees, destinations,
                          weights=None, reorder: bool = False):
        from . import topo as _topo
        return _topo.dist_graph_create(self, sources, degrees,
                                       destinations, weights, reorder)

    def topo_test(self) -> str:
        from . import topo as _topo
        return _topo.topo_test(self)

    def cart_coords(self, rank: Optional[int] = None):
        from . import topo as _topo
        t = _topo._cart(self)
        return t.coords_of(self.rank if rank is None else rank)

    def cart_rank(self, coords) -> int:
        from . import topo as _topo
        return _topo._cart(self).rank_of(coords)

    def cart_get(self):
        from . import topo as _topo
        t = _topo._cart(self)
        return list(t.dims), list(t.periods), t.coords_of(self.rank)

    def cartdim_get(self) -> int:
        from . import topo as _topo
        return _topo._cart(self).ndims

    def cart_shift(self, direction: int, disp: int = 1):
        from . import topo as _topo
        return _topo.cart_shift(self, direction, disp)

    def cart_sub(self, remain_dims):
        from . import topo as _topo
        return _topo.cart_sub(self, remain_dims)

    def graph_neighbors(self, rank: Optional[int] = None):
        if self.topo is None:
            from .errors import MPI_ERR_TOPOLOGY
            raise MPIException(MPI_ERR_TOPOLOGY, "no topology")
        return self.topo.neighbors_of(self.rank if rank is None else rank)

    def dist_graph_neighbors(self):
        """(sources, destinations) of a dist-graph comm."""
        from . import topo as _topo
        if not isinstance(self.topo, _topo.DistGraphTopology):
            from .errors import MPI_ERR_TOPOLOGY
            raise MPIException(MPI_ERR_TOPOLOGY,
                               "not a distributed-graph communicator")
        return (list(self.topo.sources), list(self.topo.destinations))

    def neighbor_allgather(self, sendbuf, recvbuf, count=None, datatype=None):
        from . import topo as _topo
        _topo.neighbor_allgather(self, sendbuf, recvbuf, count, datatype)

    def neighbor_alltoall(self, sendbuf, recvbuf, count=None, datatype=None):
        from . import topo as _topo
        _topo.neighbor_alltoall(self, sendbuf, recvbuf, count, datatype)

    def neighbor_alltoallv(self, sendbuf, sendcounts, sdispls, recvbuf,
                           recvcounts, rdispls, datatype=None):
        from . import topo as _topo
        _topo.neighbor_alltoallv(self, sendbuf, sendcounts, sdispls, recvbuf,
                                 recvcounts, rdispls, datatype)

    # ------------------------------------------------------------------
    # RMA window constructors (SURVEY §2.1 RMA; src/mpi/rma/win_create.c)
    # ------------------------------------------------------------------
    def win_create(self, buf, disp_unit: int = 1):
        from ..rma import win as _rw
        return _rw.win_create(self, buf, disp_unit)

    def win_allocate(self, size: int, disp_unit: int = 1):
        from ..rma import win as _rw
        return _rw.win_allocate(self, size, disp_unit)

    def win_allocate_shared(self, size: int, disp_unit: int = 1):
        from ..rma import win as _rw
        return _rw.win_allocate_shared(self, size, disp_unit)

    def win_create_dynamic(self):
        from ..rma import win as _rw
        return _rw.win_create_dynamic(self)

    # ------------------------------------------------------------------
    # ULFM fault tolerance (SURVEY §5.3; ft/ulfm.py)
    # ------------------------------------------------------------------
    def revoke(self) -> None:
        from ..ft import ulfm
        ulfm.revoke(self)

    def is_revoked(self) -> bool:
        return self.revoked

    def shrink(self) -> "Comm":
        from ..ft import ulfm
        return ulfm.shrink(self)

    def agree(self, flag: int) -> int:
        from ..ft import ulfm
        return ulfm.agree(self, flag)

    def failure_ack(self) -> None:
        from ..ft import ulfm
        ulfm.failure_ack(self)

    def failure_get_acked(self) -> Group:
        from ..ft import ulfm
        return ulfm.failure_get_acked(self)

    def get_failed(self) -> Group:
        from ..ft import ulfm
        return ulfm.get_failed(self)

    # -- misc -------------------------------------------------------------
    def set_name(self, name: str) -> None:
        self.name = name

    def get_name(self) -> str:
        return self.name

    def abort(self, errorcode: int = 1) -> None:
        import os
        os._exit(errorcode)

    def __repr__(self):
        return (f"Comm({self.name or 'anon'}, rank={self.rank}/{self.size}, "
                f"ctx={self.context_id})")
