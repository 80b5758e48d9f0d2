"""Eager / rendezvous protocol state machines.

Analog of the ADI3 protocol layer (SURVEY §2.1, §3.2-3.3):
  * eager path — MPIDI_CH3_EagerContigSend (ch3u_eager.c:208): payload rides
    the first packet; sender completes locally.
  * rendezvous path — MPIDI_CH3_RndvSend (ch3u_rndv.c:48) with the mrail
    protocol set (gen2/ibv_rndv.c:45-180): RGET (receiver pulls an exposed
    buffer — the RDMA-read analog, and the default as in ibv_param.c:116),
    RPUT (sender pushes after CTS), R3 (packetized through the channel —
    here RPUT with a chunk size is exactly R3).

Thresholds are cvars with per-channel defaults (EAGER_THRESHOLD /
SMP_EAGERSIZE — the ibv_param.c:776-837,2354-2361 analog).

The device lane: a message whose payload is a ``jax.Array`` given whole
to a peer thread-rank rides one EAGER_SEND packet of protocol "DEV"
that carries a device array the *receiver* owns — the sender's
device-side copy of the message (on the receiver's device, where that
is another one) — through the same Matcher as every host message. The
sender may delete or donate its array once the send has completed; the
host never sees the payload. See ``_dev_isend`` / ``_dev_deliver``.
"""

from __future__ import annotations

import ctypes as ct
import time as _time
from typing import Optional, Tuple

import numpy as np

from ..core import datatype as dtmod
from ..core.datatype import Datatype, as_bytes_view
from ..core.errors import (MPIException, MPIX_ERR_PROC_FAILED,
                           MPI_ERR_TRUNCATE, MPI_ERR_INTERN,
                           MPI_ERR_RANK, MPI_ERR_ARG, mpi_assert)
from ..core.request import Request, CompletedRequest
from ..core.status import Status, ANY_SOURCE, ANY_TAG, PROC_NULL
from ..transport.base import PLANE_CTX_FLAG, Packet, PktType
from ..utils import is_device_array
from ..utils.config import cvar, get_config
from ..utils.mlog import get_logger
from .matching import Matcher

log = get_logger("pt2pt")

cvar("R3_CHUNK_SIZE", 1 << 18, int, "pt2pt",
     "Chunk size for packetized rendezvous data (R3 path).")
cvar("RNDV_CONGEST_MIN", 8192, int, "pt2pt",
     "When the shm ring toward a peer is backlogged, payloads at or above "
     "this size switch to the CMA rendezvous instead of deepening the "
     "backlog (the ibv_send.c:320 credit-backpressure discipline).")

from .. import mpit  # noqa: E402  (after cvar decls, same registry)

_pv_eager = mpit.pvar("pt2pt_eager_sent", mpit.PVAR_CLASS_COUNTER, "pt2pt",
                      "messages sent on the eager path")
_pv_rndv = mpit.pvar("pt2pt_rndv_sent", mpit.PVAR_CLASS_COUNTER, "pt2pt",
                     "messages sent on the rendezvous path")
_pv_bytes = mpit.pvar("pt2pt_bytes_sent", mpit.PVAR_CLASS_COUNTER, "pt2pt",
                      "total payload bytes sent")
# the device lane's counters (declared in mpit.py)
_pv_dev_send = mpit.pvar("dev_pt2pt_send")
_pv_dev_recv = mpit.pvar("dev_pt2pt_recv")
_pv_dev_bytes = mpit.pvar("dev_pt2pt_bytes")
_pv_dev_unexpected = mpit.pvar("dev_pt2pt_unexpected")
_pv_dev_d2d = mpit.pvar("dev_pt2pt_d2d")
_pv_dev_fallback = mpit.pvar("dev_pt2pt_fallback_host")

_owned_copy = None      # jit(jnp.copy), built by the first lane message


def _device_copy(x):
    """A new device array with ``x``'s contents on ``x``'s device: one
    device-side copy (read m, write m), enqueued and not awaited. The
    program is jax's to cache by shape and dtype: the first message of
    a shape compiles it, no later one does."""
    global _owned_copy
    if _owned_copy is None:
        import jax
        import jax.numpy as jnp
        _owned_copy = jax.jit(jnp.copy)
    return _owned_copy(x)


def _given_whole(x, count: int, datatype: Datatype) -> bool:
    """``x`` (a jax.Array) with the count and datatype the call gave, or
    that core/comm._resolve inferred: the array as it is, all of it, on
    one device."""
    return (count == x.size and datatype.is_contiguous
            and datatype.basic is not None
            and datatype.basic == x.dtype
            and datatype.size == x.dtype.itemsize
            and len(x.devices()) == 1)


class SendRequest(Request):
    def __init__(self, engine, dest_world: int):
        super().__init__(engine, "send")
        self.dest_world = dest_world
        self.packed: Optional[np.ndarray] = None
        self.handle = None
        self.channel = None
        self.protocol = ""

    def cancel(self) -> None:
        """Send-cancel differs from the base class: a LOCALLY-complete
        eager/buffered send is still cancellable until the receiver has
        matched it (MPI-3.1 §3.8.4); resolution is asynchronous via the
        CANCEL_SEND_RESP packet."""
        fn = getattr(self, "_cancel_fn", None)
        if fn is None or self.cancelled \
                or getattr(self, "_cancel_pending", False):
            return
        fn()


class RecvRequest(Request):
    def __init__(self, engine, match: Tuple[int, int, int], buf, count: int,
                 datatype: Datatype, like=None):
        super().__init__(engine, "recv")
        self.match = match      # (ctx, source, tag)
        self.buf = buf
        self.count = count
        self.datatype = datatype
        # a device receive: the jax.Array that describes it (capacity,
        # dtype, shape). ``buf`` is then None until a host message
        # matches and is staged (``Pt2ptProtocol._stage``), or from the
        # start the read-back of ``like`` where it was not given whole.
        self.like = like
        self.staged = False     # buf is _stage's, not like's read-back
        self.unexpected = False  # the message was there before the post
        self.dev_seq = None     # the lane message's per-pair number
        self.traced = False     # a dev_recv span is open
        self.scratch: Optional[np.ndarray] = None
        self.bytes_expected = 0
        self.bytes_received = 0
        self.truncated = False

    @property
    def capacity(self) -> int:
        return self.datatype.size * self.count


class CPlaneRecvRequest(Request):
    """Receive posted into the native data plane (native/cplane.cpp).

    The C engine completes the match/copy; this wrapper finalizes lazily
    (status fields, derived-type unpack from the scratch buffer) the
    first time completion is observed — from the owning thread's wait
    predicate or from the plane channel's progress pass."""

    def __init__(self, engine, channel, buf, count: int, datatype: Datatype,
                 match: Tuple[int, int, int]):
        super().__init__(engine, "recv")
        self.channel = channel
        self.buf = buf
        self.count = count
        self.datatype = datatype
        self.match = match
        self.capacity = datatype.size * count
        self.scratch: Optional[np.ndarray] = None
        self.cpid = -1
        self._view: Optional[np.ndarray] = None
        if buf is not None and self.capacity > 0:
            if datatype.is_contiguous:
                mv = as_bytes_view(buf)
                mpi_assert(len(mv) >= self.capacity, MPI_ERR_ARG,
                           f"recv buffer too small: {len(mv)} "
                           f"< {self.capacity}")
                self._view = np.frombuffer(mv, dtype=np.uint8,
                                           count=self.capacity)
            else:
                self.scratch = np.empty(self.capacity, dtype=np.uint8)
                self._view = self.scratch
        self._addr = self._view.ctypes.data if self._view is not None else 0

    def post(self, poster) -> None:
        """``poster(addr, cap) -> cp request id`` (cp_irecv / cp_mrecv)."""
        ch = self.channel
        self.cpid = poster(self._addr, self.capacity)
        if self.cpid < 0:
            # e.g. mrecv on a token purged by cp_ctx_disable (comm freed)
            self.complete(MPIException(MPI_ERR_INTERN,
                                       "plane request post failed"))
            return
        lib = ch._ring.lib
        st = lib.cp_req_state(ch.plane, self.cpid)
        if st == 2:
            self._finalize()
        else:
            ch.plane_track_recv(self.cpid, self)
            self._cancel_fn = self._plane_cancel

    def _plane_cancel(self) -> bool:
        # mutex-held: the retract-untrack-free sequence races the plane
        # channel's _poll_plane finalize otherwise (the progress thread
        # can observe RS_DONE and complete the request concurrently)
        ch = self.channel
        with self.engine.mutex:
            if self.complete_flag:
                return False
            if ch.plane and ch._ring.lib.cp_cancel_recv(ch.plane,
                                                        self.cpid) == 1:
                ch.plane_untrack_recv(self.cpid)
                ch._ring.lib.cp_req_free(ch.plane, self.cpid)
                return True
        return False

    def _poll_plane(self) -> bool:
        """Engine-mutex-held completion check; finalizes once."""
        if self.complete_flag:
            return True
        ch = self.channel
        if not ch.plane or self.cpid < 0:
            return False
        if ch._ring.lib.cp_req_state(ch.plane, self.cpid) != 2:
            return False
        self._finalize()
        return True

    def _finalize(self) -> None:
        ch = self.channel
        lib = ch._ring.lib

        src = ct.c_int()
        tag = ct.c_int()
        nb = ct.c_longlong()
        tr = ct.c_int()
        ec = ct.c_int()
        lib.cp_req_status(ch.plane, self.cpid, src, tag, nb, tr, ec)
        ch.plane_untrack_recv(self.cpid)
        lib.cp_req_free(ch.plane, self.cpid)
        if self.scratch is not None and self.buf is not None:
            n = min(nb.value, self.capacity)
            if n > 0:
                self.datatype.unpack(self.scratch[:n], self.buf, self.count)
        self.status.source = src.value
        self.status.tag = tag.value
        self.status.count = min(nb.value, self.capacity)
        err = None
        if ec.value:
            err = MPIException(ec.value, "plane recv failed")
        elif tr.value:
            err = MPIException(MPI_ERR_TRUNCATE,
                               f"message truncated: {nb.value} "
                               f"> {self.capacity}")
        self.complete(err)

    def test(self) -> bool:
        if not self.complete_flag and self.engine is not None:
            self.engine.progress_poke()
            with self.engine.mutex:
                self._poll_plane()
        return self.complete_flag

    def wait(self) -> Status:
        if not self.complete_flag and self.engine is not None:
            self.engine.progress_wait(self._poll_plane)
        if self.error is not None:
            raise self.error
        return self.status


class CPlaneSendRequest(Request):
    """Rendezvous send on the native CMA path (cp_send_rndv): the C
    plane exposes (pid, address) in the RTS; the receiver pulls straight
    from this buffer and answers FIN. Completion is observed by polling
    the plane request, like CPlaneRecvRequest. Holds the exposed buffer
    alive until then."""

    def __init__(self, engine, channel, keepalive):
        super().__init__(engine, "send")
        self.channel = channel
        self._keep = keepalive
        self.cpid = -1

    def _poll_plane(self) -> bool:
        if self.complete_flag:
            return True
        ch = self.channel
        if not ch.plane or self.cpid < 0:
            return False
        if getattr(self, "_cancel_pending", False) \
                and not getattr(self, "_cancel_resolved", False):
            return False        # outcome arrives via the cancel result
        lib = ch._ring.lib
        if lib.cp_req_state(ch.plane, self.cpid) != 2:
            return False
        ec = ct.c_int()
        lib.cp_req_status(ch.plane, self.cpid, None, None, None, None, ec)
        ch.plane_untrack_recv(self.cpid)
        lib.cp_req_free(ch.plane, self.cpid)
        self._keep = None
        self.complete(MPIException(ec.value, "plane rndv send failed")
                      if ec.value else None)
        return True

    def test(self) -> bool:
        if not self.complete_flag and self.engine is not None:
            self.engine.progress_poke()
            with self.engine.mutex:
                self._poll_plane()
        return self.complete_flag

    def wait(self) -> Status:
        if not self.complete_flag and self.engine is not None:
            self.engine.progress_wait(self._poll_plane)
        if self.error is not None:
            raise self.error
        return self.status


class PlaneMessage:
    """Matched-message token from an mprobe on a plane-owned context
    (the plane-side analog of the Packet returned by improbe)."""

    __slots__ = ("token", "ctx", "comm_src", "tag", "nbytes")

    def __init__(self, token: int, ctx: int, comm_src: int, tag: int,
                 nbytes: int):
        self.token = token
        self.ctx = ctx
        self.comm_src = comm_src
        self.tag = tag
        self.nbytes = nbytes


class Pt2ptProtocol:
    """Per-rank protocol instance, bound to a progress engine + channels."""

    def __init__(self, universe):
        self.u = universe
        self.engine = universe.engine
        self.matcher = Matcher()
        eng = self.engine
        eng.register_handler(PktType.EAGER_SEND, self._on_eager)
        eng.register_handler(PktType.RNDV_RTS, self._on_rts)
        eng.register_handler(PktType.RNDV_CTS, self._on_cts)
        eng.register_handler(PktType.RNDV_DATA, self._on_data)
        eng.register_handler(PktType.RNDV_FIN, self._on_fin)
        eng.register_handler(PktType.RNDV_APUB, self._on_apipe_pub)
        eng.register_handler(PktType.RNDV_AACK, self._on_apipe_ack)
        eng.register_handler(PktType.CANCEL_SEND_REQ, self._on_cancel_req)
        eng.register_handler(PktType.CANCEL_SEND_RESP,
                             self._on_cancel_resp)
        self.cfg = get_config()
        self._dev_seq: dict = {}    # dest world rank -> device messages sent
        pch = getattr(universe, "plane_channel", None)
        if pch is not None and pch.plane:
            pch.plane_client = self

    def _plane_route(self, ctx: int):
        """The plane channel, iff ``ctx`` belongs to a plane-owned comm
        (every member co-resident on this shm segment). Ownership is
        decided once at comm creation (core/comm.py) so the sender and
        receiver of any (ctx, src, dst) stream route identically."""
        comm = self.u.comms_by_ctx.get(ctx & ~1)
        if comm is not None and comm._plane_owned:
            return self.u.plane_channel
        return None

    # ------------------------------------------------------------------
    # send side
    # ------------------------------------------------------------------
    def isend(self, buf, count: int, datatype: Datatype, dest_world: int,
              comm_src: int, ctx: int, tag: int,
              mode: str = "standard") -> Request:
        """Start a send; returns the request (already complete for eager)."""
        if dest_world == PROC_NULL:
            return CompletedRequest()
        if dest_world in self.u.failed_ranks:
            raise MPIException(MPIX_ERR_PROC_FAILED,
                               f"send to failed world rank {dest_world}")
        pch = self._plane_route(ctx)
        if pch is not None:
            # plane-owned ctx: ALL wire traffic (C-built eager below,
            # python-encoded rendezvous/control here) rides the plane's
            # ordered injector — one FIFO per (src,dst), self included
            channel = pch
            is_local = True
        else:
            channel = self.u.channel_for(dest_world)
            is_local = self.u.is_local(dest_world)
        if is_device_array(buf):
            if pch is None and channel.carries_device \
                    and _given_whole(buf, count, datatype):
                return self._dev_isend(buf, channel, dest_world, comm_src,
                                       ctx, tag, mode)
            # a partial count, a derived datatype, an array sharded over
            # devices, a peer in another process, a plane-owned comm: the
            # host path, on the array read back once
            _pv_dev_fallback.inc()
            buf = np.asarray(buf)
        nbytes = datatype.size * count
        threshold = (self.cfg["SMP_EAGERSIZE"] if is_local
                     else self.cfg["EAGER_THRESHOLD"])
        if pch is not None and pch.plane_eager_max():
            # oversize configurations fall back to rendezvous instead of
            # hard-failing cp_send_eager on a blob the ring can't hold
            threshold = min(threshold, pch.plane_eager_max())

        if mode == "buffered":
            # MPI_Bsend: copy now (pack always returns a fresh buffer),
            # complete immediately; the transfer proceeds on a shadow
            # request (the attached-buffer semantics). Cancel delegates
            # to the shadow and holds completion until it resolves.
            shadow = self.isend(np.asarray(datatype.pack(buf, count)),
                                nbytes, dtmod.BYTE, dest_world, comm_src,
                                ctx, tag, "standard")
            shadow.add_callback(
                lambda r: r.error and log.error(
                    "buffered send to %d failed: %s", dest_world, r.error))
            breq = SendRequest(self.engine, dest_world)
            breq._fire()
            # any cancellable shadow gets the hook — a LARGE buffered
            # send's shadow is a CPlaneSendRequest (CMA rendezvous),
            # which is a Request but NOT a SendRequest subclass;
            # keying on SendRequest silently dropped its cancel path
            # (pt2pt/scancel.c's long Ibsend)
            if isinstance(shadow, (SendRequest, CPlaneSendRequest)):
                def bcancel():
                    with self.engine.mutex:
                        if getattr(breq, "_cancel_pending", False):
                            return False
                        breq._cancel_pending = True
                        breq.complete_flag = False
                    shadow.cancel()

                    def on_shadow(sr):
                        breq.cancelled = bool(
                            getattr(sr, "cancelled", False))
                        breq.status.cancelled = breq.cancelled
                        breq.complete()
                    shadow.add_callback(on_shadow)
                    return False
                breq._cancel_fn = bcancel
            return breq

        congested = False
        if pch is not None and nbytes >= self.cfg["RNDV_CONGEST_MIN"]:
            _plib = pch._ring.lib
            congested = bool(_plib.cp_cma_enabled(pch.plane)) and bool(
                _plib.cp_congested(pch.plane,
                                   pch.local_index[dest_world]))
        if nbytes <= threshold and mode != "sync" and not congested:
            if pch is not None:
                # C-built eager: header + payload assembled and injected
                # natively (the ibv_send_inline.h:493 moment)
                if datatype.is_contiguous:
                    mv = as_bytes_view(buf)
                    mpi_assert(len(mv) >= nbytes, MPI_ERR_ARG,
                               f"buffer too small: {len(mv)} < {nbytes}")
                    arr = np.frombuffer(mv, dtype=np.uint8, count=nbytes) \
                        if nbytes else None
                else:
                    arr = np.asarray(datatype.pack(buf, count)) \
                        .view(np.uint8).reshape(-1)
                sreq = SendRequest(self.engine, dest_world)
                from .. import faults
                fk = faults.fire("shm_send")   # plane eager is a
                # send site too (send_packet only carries control/rndv
                # traffic in plane mode)
                if fk == "drop":
                    rc = 0          # "sent" but lost on the wire
                else:
                    rc = pch._ring.lib.cp_send_eager(
                        pch.plane, pch.local_index[dest_world], ctx,
                        comm_src, tag,
                        arr.ctypes.data if arr is not None else None,
                        nbytes, sreq.req_id)
                    if fk == "duplicate" and rc == 0:
                        pch._ring.lib.cp_send_eager(
                            pch.plane, pch.local_index[dest_world], ctx,
                            comm_src, tag,
                            arr.ctypes.data if arr is not None else None,
                            nbytes, sreq.req_id)
                if rc == -2:
                    from ..ft import ulfm
                    ulfm.mark_failed(self.u, dest_world)
                    raise MPIException(
                        MPIX_ERR_PROC_FAILED,
                        f"send to failed world rank {dest_world}")
                if rc < 0:
                    raise MPIException(MPI_ERR_INTERN,
                                       "plane eager injection failed")
                _pv_eager.inc()
                _pv_bytes.inc(nbytes)
                if (tr := self.engine.tracer) is not None:
                    tr.record("protocol", "eager_send", "i",
                              dest=dest_world, bytes=nbytes, path="plane")
                sreq._fire()
                sreq._cancel_fn = lambda: self._plane_cancel_send(
                    sreq, pch, dest_world)
                return sreq
            if datatype.is_contiguous:
                # zero-copy injection: every channel's send_packet
                # copies the payload before returning (encode_packet
                # blob / LocalChannel's explicit copy), so handing a
                # view preserves eager buffer-reuse semantics while
                # skipping pack()'s extra copy
                mv = as_bytes_view(buf)
                mpi_assert(len(mv) >= nbytes, MPI_ERR_ARG,
                           f"buffer too small: {len(mv)} < {nbytes}")
                packed = mv[:nbytes]
            else:
                packed = np.asarray(datatype.pack(buf, count))
            sreq = SendRequest(self.engine, dest_world)
            pkt = Packet(PktType.EAGER_SEND, self.u.world_rank, ctx, comm_src,
                         tag, nbytes, packed,
                         sreq_id=sreq.req_id)
            self._send_pkt(channel, dest_world, pkt)
            _pv_eager.inc()
            _pv_bytes.inc(nbytes)
            if (tr := self.engine.tracer) is not None:
                tr.record("protocol", "eager_send", "i",
                          dest=dest_world, bytes=nbytes)
            # locally complete, but cancellable until matched (§3.8.4)
            sreq._fire()
            sreq._cancel_fn = lambda: self._cancel_send(
                sreq, dest_world, channel)
            return sreq

        # rendezvous (always used for Ssend so completion implies matching)
        if pch is not None and pch._ring.lib.cp_cma_enabled(pch.plane):
            # native CMA rendezvous: the receiver pulls straight from
            # this buffer via process_vm_readv and FINs — no staged copy,
            # no python packet on the data path (ibv_rndv.c RGET analog)
            lib = pch._ring.lib
            if datatype.is_contiguous:
                mv = as_bytes_view(buf)
                mpi_assert(len(mv) >= nbytes, MPI_ERR_ARG,
                           f"buffer too small: {len(mv)} < {nbytes}")
                arr = np.frombuffer(mv, dtype=np.uint8, count=nbytes) \
                    if nbytes else None
            else:
                arr = np.asarray(datatype.pack(buf, count)) \
                    .view(np.uint8).reshape(-1)
            sreq = CPlaneSendRequest(self.engine, pch, arr)
            sreq._ctx = ctx     # revoke sweep keys pending sends by ctx
            with self.engine.mutex:
                rid = lib.cp_send_rndv(
                    pch.plane, pch.local_index[dest_world], ctx, comm_src,
                    tag,
                    arr.ctypes.data if arr is not None and arr.size else
                    None, nbytes)
                if rid >= 0:
                    sreq.cpid = rid
                    pch.plane_track_recv(rid, sreq)
                    sreq._cancel_fn = lambda: self._plane_cancel_rndv(
                        sreq, pch, dest_world)
                    _pv_rndv.inc()
                    _pv_bytes.inc(nbytes)
                    if (tr := self.engine.tracer) is not None:
                        tr.record("protocol", "rndv_rts", "i",
                                  dest=dest_world, bytes=nbytes,
                                  proto="CMA")
                    return sreq
            if rid == -2:
                from ..ft import ulfm
                ulfm.mark_failed(self.u, dest_world)
                raise MPIException(
                    MPIX_ERR_PROC_FAILED,
                    f"send to failed world rank {dest_world}")
            # rid == -1: CMA raced off — fall through to staged rndv
        sreq = SendRequest(self.engine, dest_world)
        sreq.channel = channel
        sreq._ctx = ctx         # revoke sweep keys pending sends by ctx
        packed = datatype.pack(buf, count)
        sreq.packed = np.asarray(packed)
        proto = self.cfg["RNDV_PROTOCOL"]
        if proto == "RGET" and self._start_apipe(
                sreq, channel, dest_world, ctx, comm_src, tag, nbytes, pch):
            return sreq
        if proto == "RGET" and channel.supports_rget:
            sreq.protocol = "RGET"
            sreq.handle = channel.expose_buffer(sreq.packed)
        else:
            sreq.protocol = "RPUT"
        with self.engine.mutex:
            self.engine.track(sreq)
        # plane-owned ctx: flag the RTS so the receiver's C matcher claims
        # it (wire-carried ownership, PLANE_CTX_FLAG in cplane.cpp)
        wire_ctx = ctx | PLANE_CTX_FLAG if pch is not None else ctx
        pkt = Packet(PktType.RNDV_RTS, self.u.world_rank, wire_ctx, comm_src,
                     tag, nbytes, None, sreq_id=sreq.req_id,
                     protocol=sreq.protocol,
                     extra={"handle": sreq.handle} if sreq.handle is not None
                     else None)
        self._send_pkt(channel, dest_world, pkt)
        # MPI_Cancel on an unmatched rendezvous send retracts the RTS
        # from the peer's unexpected queue (the ch3 cancel-send protocol,
        # mpidpkt.h CANCEL packets); completion arrives as a RESP
        sreq._cancel_fn = lambda: self._cancel_send(sreq, dest_world,
                                                    channel)
        _pv_rndv.inc()
        _pv_bytes.inc(nbytes)
        if (tr := self.engine.tracer) is not None:
            tr.record("protocol", "rndv_rts", "i", dest=dest_world,
                      bytes=nbytes, proto=sreq.protocol)
        return sreq

    # ------------------------------------------------------------------
    # the device lane
    # ------------------------------------------------------------------
    def _dev_isend(self, x, channel, dest_world: int, comm_src: int,
                   ctx: int, tag: int, mode: str) -> Request:
        """Send the jax.Array ``x``, whole, to a peer thread-rank.

        The sender makes the receiver's array now: a device-side copy of
        ``x`` on its own device or, where the receiver is bound to
        another device of the process, the runtime's device-to-device
        copy onto that one. The copy is enqueued, not awaited; the
        packet carries it as the object it is, and the send is complete
        (all modes but ``sync``, which completes when the receiver's
        match answers with a FIN). So whatever the sender then does to
        ``x`` (delete, donate) cannot reach what the receiver holds,
        whether its receive was posted before or comes after, and a
        send never waits for a receive, as an eager host send does not.
        """
        nbytes = x.size * x.dtype.itemsize
        seq = self._dev_seq[dest_world] = \
            self._dev_seq.get(dest_world, 0) + 1
        there = channel.device_of(dest_world)
        d2d = there is not None and x.devices() != {there}
        tr = self.engine.tracer
        if tr is not None:
            # ``bytes`` once a message and end (bin/mpitrace sums it):
            # on the send's B and on the receive's E
            tr.record("device", "dev_send", "B",
                      {"dest": dest_world, "tag": tag, "bytes": nbytes,
                       "seq": seq})
            tr.record("device", "dev_p2p_copy", "B",
                      {"bytes": nbytes, "d2d": d2d})
        if d2d:
            import jax
            own = jax.device_put(x, there)
            _pv_dev_d2d.inc()
        else:
            own = _device_copy(x)
        if tr is not None:
            tr.record("device", "dev_p2p_copy", "E")
        sreq = SendRequest(self.engine, dest_world)
        sync = mode == "sync"
        pkt = Packet(PktType.EAGER_SEND, self.u.world_rank, ctx, comm_src,
                     tag, nbytes, own, sreq_id=sreq.req_id, protocol="DEV",
                     extra={"seq": seq, "sync": sync})
        if sync:
            sreq._ctx = ctx     # revoke sweep keys pending sends by ctx
            with self.engine.mutex:
                self.engine.track(sreq)
        self._send_pkt(channel, dest_world, pkt)
        _pv_dev_send.inc()
        if not sync:
            sreq._fire()        # locally complete, cancellable until matched
        sreq._cancel_fn = lambda: self._cancel_send(sreq, dest_world,
                                                    channel)
        if tr is not None:
            tr.record("device", "dev_send", "E",
                      {"dest": dest_world, "tag": tag, "seq": seq})
        return sreq

    def _device_recvbuf(self, buf, count: int, datatype: Datatype,
                        lane: bool = True):
        """``(buf, like)`` of a receive. A jax.Array given as the
        receive buffer is a description (``like``: capacity, dtype,
        shape) and no buffer (None): a lane message arrives as the
        device array it is. Where the array is not given whole, or the
        comm has no lane (plane-owned), the host path receives into its
        read-back (counted), which is put on the device when the
        receive ends."""
        if not is_device_array(buf):
            return buf, None
        if lane and _given_whole(buf, count, datatype):
            return None, buf
        _pv_dev_fallback.inc()
        return np.array(buf), buf

    def _plane_recv(self, pch, buf, count: int, datatype: Datatype,
                    match) -> "CPlaneRecvRequest":
        """A receive request of a plane-owned comm; a device receive
        buffer's read-back is received into and then put on the device."""
        buf, like = self._device_recvbuf(buf, count, datatype, lane=False)
        req = CPlaneRecvRequest(self.engine, pch, buf, count, datatype,
                                match)
        if like is not None:
            def upload(r):
                if r.error is None:
                    r.array = self._upload(buf, like)
            req.add_callback(upload)
        return req

    def _dev_posted(self, req: RecvRequest) -> None:
        """A device receive is posted: its ``dev_recv`` span opens."""
        if req.like is not None and (tr := self.engine.tracer) is not None:
            req.traced = True
            _ctx, source, tag = req.match
            tr.record("device", "dev_recv", "B",
                      {"source": source, "tag": tag,
                       "capacity": req.capacity})

    def _stage(self, req: RecvRequest) -> None:
        """A host message matched a device receive: it lands in a host
        buffer of the description's shape (counted), and ``_dev_finish``
        puts what came on the device."""
        if req.like is not None and req.buf is None:
            _pv_dev_fallback.inc()
            req.buf = np.empty(req.like.shape, req.like.dtype)
            req.staged = True

    def _upload(self, host: np.ndarray, like):
        """``host`` as a device array on this rank's device (the one its
        COMM_WORLD is bound to; unbound, the description's own)."""
        import jax
        return jax.device_put(host,
                              self.u.device or next(iter(like.devices())))

    def _dev_deliver(self, req: RecvRequest, pkt: Packet) -> None:
        """A message of the device lane meets its receive (engine mutex
        held). A device receive takes the array as it is; a host receive
        buffer has it read back (counted)."""
        if pkt.extra["sync"]:
            fin = Packet(PktType.RNDV_FIN, self.u.world_rank,
                         sreq_id=pkt.sreq_id)
            self.u.channel_for(pkt.src_world).send_packet(pkt.src_world, fin)
        arr = pkt.data
        if pkt.nbytes > req.capacity:
            pass                # MPI_ERR_TRUNCATE: nothing is delivered
        elif req.buf is not None:
            _pv_dev_fallback.inc()
            if pkt.nbytes:
                req.datatype.unpack(
                    np.asarray(arr).reshape(-1).view(np.uint8), req.buf,
                    req.count)
        elif req.like is not None:
            # the message's dtype and element count, shaped as the
            # description where the count fills it, else flat
            shape = req.like.shape if arr.size == req.like.size \
                else (arr.size,)
            req.array = arr if arr.shape == shape else arr.reshape(shape)
            req.dev_seq = pkt.extra["seq"]
            _pv_dev_recv.inc()
            _pv_dev_bytes.inc(pkt.nbytes)
        self._finish_recv(req, pkt, pkt.nbytes, pkt.comm_src, pkt.tag)

    def _dev_finish(self, req: RecvRequest, nbytes: int, src: int,
                    tag: int, ok: bool) -> None:
        """The end of a device receive: what the host path received is
        put on the device, and the ``dev_recv`` span closes."""
        # a posted receive and its cancel hook hold each other: let go,
        # so that the array goes when its holder does, not at the next
        # cycle collection
        req._cancel_fn = None
        if ok and req.array is None and req.buf is not None:
            host = req.buf
            if req.staged:
                n = min(nbytes, req.capacity) // req.like.dtype.itemsize
                if n != host.size:
                    host = host.reshape(-1)[:n]
            req.array = self._upload(host, req.like)
        if req.traced and (tr := self.engine.tracer) is not None:
            tr.record("device", "dev_recv", "E",
                      {"source": src, "tag": tag, "bytes": nbytes,
                       "seq": req.dev_seq, "unexpected": req.unexpected})

    def _plane_cancel_send(self, sreq, pch, dest_world: int) -> bool:
        """Send-cancel for a plane-injected eager: CANCEL_SEND_REQ goes
        through the plane; the C target retracts from its unexpected
        queue (or the python matcher answers); the result lands via
        cp_cancel_result, drained in the channel's progress pass."""
        eng = self.engine
        with eng.mutex:
            if sreq.cancelled or getattr(sreq, "_cancel_pending", False):
                return False
            sreq._cancel_pending = True
            sreq._cancel_was_complete = sreq.complete_flag
            sreq.complete_flag = False
            pch.plane_track_cancel(sreq.req_id, sreq)
        pch._ring.lib.cp_cancel_send(pch.plane, sreq.req_id,
                                     pch.local_index[dest_world])
        return False

    def _plane_cancel_rndv(self, sreq, pch, dest_world: int) -> bool:
        """Send-cancel for a CMA rendezvous: the target's retraction
        scan matches the namespaced WIRE id the RTS traveled under
        (cp_rndv_wire), not the raw plane request id."""
        wire = pch._ring.lib.cp_rndv_wire(sreq.cpid)
        eng = self.engine
        with eng.mutex:
            if sreq.cancelled or getattr(sreq, "_cancel_pending", False):
                return False
            sreq._cancel_pending = True
            sreq._cancel_was_complete = False
            pch.plane_track_cancel(wire, sreq)
        pch._ring.lib.cp_cancel_send(pch.plane, wire,
                                     pch.local_index[dest_world])
        return False

    def on_plane_cancel_result(self, sreq, retracted: bool) -> None:
        """Channel progress callback: the plane resolved a send-cancel
        (mirrors _on_cancel_resp)."""
        if isinstance(sreq, CPlaneSendRequest):
            sreq._cancel_resolved = True
            if sreq.complete_flag:
                return
            if retracted:
                # no FIN will ever come: reclaim the plane request
                ch = sreq.channel
                ch.plane_untrack_recv(sreq.cpid)
                ch._ring.lib.cp_req_free(ch.plane, sreq.cpid)
                sreq._keep = None
                sreq.cancelled = True
                sreq.status.cancelled = True
                sreq.complete()
            # else: the FIN completes it via _poll_plane
            return
        if sreq.complete_flag:
            return
        if retracted:
            sreq.cancelled = True
            sreq.status.cancelled = True
            sreq.complete()
        elif getattr(sreq, "_cancel_was_complete", False):
            sreq.complete()

    def on_plane_assist(self, pch, cpid: int, pkt: Packet) -> None:
        """Channel progress callback: the plane matched an RNDV_RTS to a
        C-posted receive (python- or C-origin) — run the rendezvous into
        the plane request's buffer and complete it via the plane."""

        lib = pch._ring.lib
        bufp = ct.c_void_p()
        cap = ct.c_longlong()
        lib.cp_req_buf(pch.plane, cpid, bufp, cap)
        n = int(cap.value or 0)
        view = None
        if bufp.value and n > 0:
            view = np.frombuffer((ct.c_char * n).from_address(bufp.value),
                                 dtype=np.uint8)
        shadow = RecvRequest(self.engine, (pkt.ctx, pkt.comm_src, pkt.tag),
                             view, n, dtmod.BYTE)

        def done(r):
            ec = r.error.error_class if r.error is not None else 0
            if ec == MPI_ERR_TRUNCATE:
                ec = 0        # the plane recomputes truncation from cap
            lib.cp_complete_assist(pch.plane, cpid, pkt.nbytes,
                                   pkt.comm_src, pkt.tag, ec)
            self.engine.wakeup()

        shadow.add_callback(done)
        with self.engine.mutex:
            self._rndv_recv_start(shadow, pkt)

    def _cancel_send(self, sreq, dest_world: int, channel) -> bool:
        """Initiate send-cancel; async — the RESP resolves it. A
        locally-complete eager send is held incomplete until then so
        MPI_Wait observes the cancel's outcome."""
        eng = self.engine
        with eng.mutex:
            if sreq.cancelled or getattr(sreq, "_cancel_pending", False):
                return False
            sreq._cancel_pending = True
            sreq._cancel_was_complete = sreq.complete_flag
            sreq.complete_flag = False
            eng.outstanding[sreq.req_id] = sreq
        pkt = Packet(PktType.CANCEL_SEND_REQ, self.u.world_rank,
                     sreq_id=sreq.req_id)
        self._send_pkt(channel, dest_world, pkt)
        return False

    def _on_cancel_req(self, pkt: Packet) -> None:
        ok = self.matcher.cancel_unexpected(pkt.src_world, pkt.sreq_id)
        resp = Packet(PktType.CANCEL_SEND_RESP, self.u.world_rank,
                      sreq_id=pkt.sreq_id, offset=1 if ok else 0)
        channel = self.u.channel_for(pkt.src_world)
        self._send_pkt(channel, pkt.src_world, resp)

    def _on_cancel_resp(self, pkt: Packet) -> None:
        sreq = self.engine.outstanding.get(pkt.sreq_id)
        if sreq is None or sreq.complete_flag:
            return            # already completed normally: not cancelled
        if pkt.offset:        # retracted at the target
            sreq.cancelled = True
            sreq.status.cancelled = True
            ap = getattr(sreq, "_ap", None)
            if ap is not None:    # pipelined block never gets its FIN
                ap["arena"].free(ap["block"])
                sreq._ap = None
            if sreq.handle is not None and sreq.channel is not None \
                    and hasattr(sreq.channel, "unexpose_buffer"):
                sreq.channel.unexpose_buffer(sreq.handle)
            sreq.complete()
        elif getattr(sreq, "_cancel_was_complete", False):
            sreq.complete()   # restore the eager local completion
        # else: an in-flight rendezvous completes via its normal FIN

    def _send_pkt(self, channel, dest_world: int, pkt: Packet) -> None:
        """Channel send with failure surfacing: a connection-level error
        marks the peer failed (the VC-failure analog, SURVEY §5.3) and
        raises MPIX_ERR_PROC_FAILED."""
        try:
            channel.send_packet(dest_world, pkt)
        except OSError as e:
            from ..ft import ulfm
            ulfm.mark_failed(self.u, dest_world)
            raise MPIException(
                MPIX_ERR_PROC_FAILED,
                f"transport to world rank {dest_world} failed: {e}") from e

    # ------------------------------------------------------------------
    # recv side
    # ------------------------------------------------------------------
    def irecv(self, buf, count: int, datatype: Datatype, source: int,
              ctx: int, tag: int) -> Request:
        if source == PROC_NULL:
            req = CompletedRequest()
            req.status.source = PROC_NULL
            req.status.tag = ANY_TAG
            return req
        pch = self._plane_route(ctx)
        if pch is not None:
            req = self._plane_recv(pch, buf, count, datatype,
                                   (ctx, source, tag))
            with self.engine.mutex:
                if self._recv_source_failed(ctx, source, tag):
                    req.complete(MPIException(
                        MPIX_ERR_PROC_FAILED,
                        f"recv source failed (ctx={ctx}, src={source})"))
                    return req
                req.post(lambda addr, cap: pch._ring.lib.cp_irecv(
                    pch.plane, addr, cap, ctx, source, tag))
            return req
        buf, like = self._device_recvbuf(buf, count, datatype)
        req = RecvRequest(self.engine, (ctx, source, tag), buf, count,
                          datatype, like)
        self._dev_posted(req)
        with self.engine.mutex:
            pkt = self.matcher.match_posted(ctx, source, tag)
            if pkt is not None:
                req.unexpected = True
                self._deliver(req, pkt)
            elif self._recv_source_failed(ctx, source, tag):
                req.complete(MPIException(
                    MPIX_ERR_PROC_FAILED,
                    f"recv source failed (ctx={ctx}, src={source})"))
            else:
                self.matcher.post(req)
                req._cancel_fn = lambda: self._cancel_posted(req)
        return req

    def _cancel_posted(self, req: RecvRequest) -> bool:
        """MPI_Cancel of a posted receive; a device receive's span
        closes with it."""
        gone = self.matcher.cancel_posted(req)
        if gone and req.traced and (tr := self.engine.tracer) is not None:
            tr.record("device", "dev_recv", "E", {"cancelled": True})
        return gone

    def _recv_source_failed(self, ctx: int, source: int,
                            tag: int) -> bool:
        """ULFM: a named-source recv from a failed rank (no message already
        queued) can never complete; a wildcard recv fails while the comm
        has *unacknowledged* failures (failure_ack re-arms it). A recv on
        a COLL context of a comm with ANY failed member (remote group
        included for intercomms) fails too — collectives on a damaged
        comm can never complete consistently (failure_ack does not
        re-arm collectives). Recvs in the FT tag range are the ULFM
        agreement's own exchange and are exempt (ft/ulfm.py)."""
        if not self.u.failed_ranks:
            return False
        comm = self.u.comms_by_ctx.get(ctx & ~1)
        if comm is None:
            return False
        from ..ft.ulfm import _FT_TAG_BASE, ft_members
        if (ctx & 1) and tag < _FT_TAG_BASE \
                and any(w in self.u.failed_ranks
                        for w in ft_members(comm)):
            return True
        if source == ANY_SOURCE:
            return any(w in self.u.failed_ranks
                       and w not in comm._acked_failures
                       for w in comm.group.world_ranks)
        return comm.world_of(source) in self.u.failed_ranks

    # -- probe ----------------------------------------------------------
    def _plane_peek(self, pch, ctx: int, source: int, tag: int,
                    remove: bool = False):
        """cp_probe wrapper; returns a Status-bearing PlaneMessage or
        None. (Non-removing probes reuse the token slot as scratch.)"""

        lib = pch._ring.lib
        src = ct.c_int()
        tg = ct.c_int()
        nb = ct.c_longlong()
        tok = ct.c_longlong()
        kind = lib.cp_probe(pch.plane, ctx, source, tag,
                            1 if remove else 0, src, tg, nb, tok)
        if kind == 0:
            return None
        return PlaneMessage(tok.value if remove else 0, ctx, src.value,
                            tg.value, nb.value)

    def iprobe(self, source: int, ctx: int, tag: int) -> Optional[Status]:
        pch = self._plane_route(ctx)
        if pch is not None:
            msg = self._plane_peek(pch, ctx, source, tag)
            if msg is None:
                self.engine.progress_poke()
                msg = self._plane_peek(pch, ctx, source, tag)
            if msg is None and self._recv_source_failed(ctx, source, tag):
                raise MPIException(MPIX_ERR_PROC_FAILED,
                                   f"probe source failed (src={source})")
            return self._pkt_status(msg) if msg is not None else None
        with self.engine.mutex:
            pkt = self.matcher.peek_unexpected(ctx, source, tag)
        if pkt is None:
            self.engine.progress_poke()
            with self.engine.mutex:
                pkt = self.matcher.peek_unexpected(ctx, source, tag)
        if pkt is None and self._recv_source_failed(ctx, source, tag):
            raise MPIException(MPIX_ERR_PROC_FAILED,
                               f"probe source failed (src={source})")
        return self._pkt_status(pkt) if pkt is not None else None

    def probe(self, source: int, ctx: int, tag: int) -> Status:
        pch = self._plane_route(ctx)
        box: list = []

        def pred():
            pkt = (self._plane_peek(pch, ctx, source, tag)
                   if pch is not None
                   else self.matcher.peek_unexpected(ctx, source, tag))
            if pkt is not None:
                box.append(pkt)
                return True
            # a probe on a source that can never send again must unwind,
            # like the equivalent posted recv (ULFM)
            return self._recv_source_failed(ctx, source, tag)

        self.engine.progress_wait(pred)
        if not box:
            raise MPIException(MPIX_ERR_PROC_FAILED,
                               f"probe source failed (src={source})")
        return self._pkt_status(box[0])

    def improbe(self, source: int, ctx: int, tag: int):
        """Returns a matched-message token (pkt / PlaneMessage) or None."""
        pch = self._plane_route(ctx)
        if pch is not None:
            msg = self._plane_peek(pch, ctx, source, tag, remove=True)
            if msg is None:
                self.engine.progress_poke()
                msg = self._plane_peek(pch, ctx, source, tag, remove=True)
            if msg is None and self._recv_source_failed(ctx, source, tag):
                raise MPIException(MPIX_ERR_PROC_FAILED,
                                   f"probe source failed (src={source})")
            return msg
        with self.engine.mutex:
            pkt = self.matcher.peek_unexpected(ctx, source, tag, remove=True)
        if pkt is None:
            self.engine.progress_poke()
            with self.engine.mutex:
                pkt = self.matcher.peek_unexpected(ctx, source, tag,
                                                   remove=True)
        if pkt is None and self._recv_source_failed(ctx, source, tag):
            raise MPIException(MPIX_ERR_PROC_FAILED,
                               f"probe source failed (src={source})")
        return pkt

    def mrecv(self, message, buf, count: int,
              datatype: Datatype) -> Request:
        if isinstance(message, PlaneMessage):
            pch = self.u.plane_channel
            req = self._plane_recv(pch, buf, count, datatype,
                                   (message.ctx, message.comm_src,
                                    message.tag))
            with self.engine.mutex:
                req.post(lambda addr, cap: pch._ring.lib.cp_mrecv_start(
                    pch.plane, message.token, addr, cap))
            return req
        buf, like = self._device_recvbuf(buf, count, datatype)
        req = RecvRequest(self.engine, (message.ctx, message.comm_src,
                                        message.tag), buf, count, datatype,
                          like)
        req.unexpected = True
        self._dev_posted(req)
        with self.engine.mutex:
            self._deliver(req, message)
        return req

    @staticmethod
    def _pkt_status(pkt: Packet) -> Status:
        return Status(source=pkt.comm_src, tag=pkt.tag, count=pkt.nbytes)

    # ------------------------------------------------------------------
    # delivery / handlers (engine mutex held)
    # ------------------------------------------------------------------
    def _deliver(self, req: RecvRequest, pkt: Packet) -> None:
        if pkt.type == PktType.EAGER_SEND:
            self._deliver_eager(req, pkt)
        elif pkt.type == PktType.RNDV_RTS:
            self._rndv_recv_start(req, pkt)
        else:  # pragma: no cover
            raise MPIException(MPI_ERR_INTERN, f"bad matched pkt {pkt.type}")

    def _finish_recv(self, req: RecvRequest, pkt_or_none, nbytes: int,
                     src: int, tag: int) -> None:
        req.status.source = src
        req.status.tag = tag
        req.status.count = min(nbytes, req.capacity)
        err = None
        if nbytes > req.capacity:
            err = MPIException(MPI_ERR_TRUNCATE,
                               f"message truncated: {nbytes} > {req.capacity}")
        if req.like is not None:
            self._dev_finish(req, nbytes, src, tag, err is None)
        req.complete(err)

    def _deliver_eager(self, req: RecvRequest, pkt: Packet) -> None:
        if pkt.protocol == "DEV":
            self._dev_deliver(req, pkt)
            return
        self._stage(req)
        n = min(pkt.nbytes, req.capacity)
        if n > 0 and req.buf is not None:
            req.datatype.unpack(pkt.data[:n], req.buf, req.count)
        if (tr := self.engine.tracer) is not None:
            tr.record("protocol", "eager_recv", "i", src=pkt.src_world,
                      bytes=pkt.nbytes)
        self._finish_recv(req, pkt, pkt.nbytes, pkt.comm_src, pkt.tag)

    def _rndv_recv_start(self, req: RecvRequest, pkt: Packet) -> None:
        self._stage(req)
        req.bytes_expected = pkt.nbytes
        src_world = pkt.src_world
        channel = self.u.channel_for(src_world)
        if (tr := self.engine.tracer) is not None:
            tr.record("protocol", "rndv_rts_recv", "i", src=src_world,
                      bytes=pkt.nbytes, proto=pkt.protocol)
        if pkt.protocol == "APIPE":
            self._apipe_recv_start(req, pkt)
            return
        if pkt.protocol == "RGET":
            n = min(pkt.nbytes, req.capacity)
            if n > 0:
                data = channel.pull_buffer(src_world, pkt.extra["handle"], n)
                req.datatype.unpack(data, req.buf, req.count)
            fin = Packet(PktType.RNDV_FIN, self.u.world_rank,
                         sreq_id=pkt.sreq_id)
            channel.send_packet(src_world, fin)
            self._finish_recv(req, pkt, pkt.nbytes, pkt.comm_src, pkt.tag)
            return
        # RPUT/R3: stage into scratch, ask sender to push
        req.scratch = np.empty(min(pkt.nbytes, req.capacity), dtype=np.uint8)
        req._rndv_env = (pkt.comm_src, pkt.tag, pkt.nbytes)
        self.engine.track(req)
        cts = Packet(PktType.RNDV_CTS, self.u.world_rank,
                     sreq_id=pkt.sreq_id, rreq_id=req.req_id)
        channel.send_packet(src_world, cts)

    # -- handlers --------------------------------------------------------
    def _on_eager(self, pkt: Packet) -> None:
        req = self.matcher.match_incoming(pkt)
        if req is not None:
            self._deliver_eager(req, pkt)
        elif pkt.protocol == "DEV":
            _pv_dev_unexpected.inc()

    def _on_rts(self, pkt: Packet) -> None:
        req = self.matcher.match_incoming(pkt)
        if req is not None:
            self._rndv_recv_start(req, pkt)

    def _on_cts(self, pkt: Packet) -> None:
        sreq = self.engine.outstanding.get(pkt.sreq_id)
        if sreq is None:  # pragma: no cover
            raise MPIException(MPI_ERR_INTERN, "CTS for unknown send")
        if (tr := self.engine.tracer) is not None:
            tr.record("protocol", "rndv_cts", "i", src=pkt.src_world,
                      bytes=len(sreq.packed) if sreq.packed is not None
                      else 0)
        data = sreq.packed
        chunk = self.cfg["R3_CHUNK_SIZE"]
        total = len(data)
        off = 0
        while True:
            end = min(off + chunk, total)
            dpkt = Packet(PktType.RNDV_DATA, self.u.world_rank,
                          nbytes=end - off, data=data[off:end],
                          rreq_id=pkt.rreq_id, offset=off,
                          extra={"last": end >= total})
            sreq.channel.send_packet(pkt.src_world, dpkt)
            off = end
            if off >= total:
                break
        sreq.complete()

    def _on_data(self, pkt: Packet) -> None:
        rreq = self.engine.outstanding.get(pkt.rreq_id)
        if rreq is None:  # pragma: no cover
            raise MPIException(MPI_ERR_INTERN, "DATA for unknown recv")
        cap = len(rreq.scratch)
        if pkt.offset < cap and pkt.nbytes > 0:
            n = min(pkt.nbytes, cap - pkt.offset)
            rreq.scratch[pkt.offset:pkt.offset + n] = pkt.data[:n]
        rreq.bytes_received += pkt.nbytes
        if pkt.extra and pkt.extra.get("last"):
            if cap > 0:
                rreq.datatype.unpack(rreq.scratch, rreq.buf, rreq.count)
            src, tag, nbytes = rreq._rndv_env
            self._finish_recv(rreq, pkt, nbytes, src, tag)

    def _on_fin(self, pkt: Packet) -> None:
        sreq = self.engine.outstanding.get(pkt.sreq_id)
        if sreq is None:  # pragma: no cover
            raise MPIException(MPI_ERR_INTERN, "FIN for unknown send")
        if (tr := self.engine.tracer) is not None:
            tr.record("protocol", "rndv_fin", "i", src=pkt.src_world)
        self._release_send_side(sreq)
        sreq.complete()

    # ------------------------------------------------------------------
    # pipelined arena rendezvous (APIPE): the sender copies chunk k+1
    # into persistent arena slots while the receiver drains chunk k —
    # the RGET pipelining of gen2/ibv_rndv.c over the per-node arena
    # instead of RDMA reads. Flow control is BATCHED: the receiver
    # drains every published chunk, then sends one AACK carrying the
    # highest chunk consumed; the sender refills every slot that ACK
    # freed (a chunk's slot may be overwritten once the chunk it
    # carried is consumed) and answers with one APUB carrying the new
    # publish frontier. Packets per message are ~2*nchunks/depth
    # instead of 2*nchunks — on a host where packet handling is the
    # cost, that is the difference between the pipeline winning and
    # losing to the one-shot path.
    # ------------------------------------------------------------------
    def _start_apipe(self, sreq, channel, dest_world: int, ctx: int,
                     comm_src: int, tag: int, nbytes: int, pch) -> bool:
        """Start a pipelined chunked rendezvous if the channel has an
        arena and the message spans multiple chunks. Returns False to
        fall back to the one-shot RGET ladder (which includes the
        zero-staging CMA handle when the probe passed — pipelining there
        happens inside the chunked pull)."""
        arena = getattr(channel, "arena", None)
        if arena is None or not getattr(channel, "_arena_ready", False) \
                or getattr(channel, "cma_ok", False):
            return False
        chunk = self.cfg["RNDV_CHUNK"]
        depth = max(2, self.cfg["RNDV_DEPTH"])
        if chunk <= 0 or nbytes < 2 * chunk:
            return False
        nchunks = (nbytes + chunk - 1) // chunk
        # Publish window: cover the whole message up front when it fits
        # 1/16 of the partition — a mid-message PUB/ACK round trip costs
        # a scheduling quantum on a single-core host, so zero-round-trip
        # transfers (RTS + FIN only) win whenever memory allows. The
        # cvar depth is the floor the pipeline degrades to when the
        # arena is tight (many sends in flight). The slot window is ONE
        # contiguous block sliced into chunk-sized slots (chunk k lives
        # at block + (k % nslots)*chunk): a single alloc/free, and
        # consecutive chunks publish/drain as one streaming memcpy.
        want = min(nchunks, max(depth, arena.part_bytes // 16 // chunk))
        block = None
        while want >= 2:
            block = arena.alloc(want * chunk)
            if block is not None:
                break
            want //= 2              # near-exhaustion: shallower pipeline
        if block is None:           # exhausted: one-shot/file fallback
            return False
        nslots = want
        d0 = min(nslots, nchunks)
        from ..transport import arena as arena_mod
        data = np.ascontiguousarray(sreq.packed).view(np.uint8).reshape(-1)
        tr = self.engine.tracer
        span0 = min(d0 * chunk, nbytes)   # first pass: no wraparound
        arena.view(block.off, span0)[:] = data[:span0]
        arena_mod.pv_pipeline.inc(d0)
        if tr is not None:
            tr.record("protocol", "rndv_chunk", "i", dir="pub", k=0,
                      chunks=d0, bytes=span0)
        sreq.protocol = "APIPE"
        sreq._ap = {"block": block, "arena": arena, "chunk": chunk,
                    "nslots": nslots, "nchunks": nchunks, "next": d0,
                    "data": data}
        with self.engine.mutex:
            self.engine.track(sreq)
        wire_ctx = ctx | PLANE_CTX_FLAG if pch is not None else ctx
        pkt = Packet(PktType.RNDV_RTS, self.u.world_rank, wire_ctx,
                     comm_src, tag, nbytes, None, sreq_id=sreq.req_id,
                     protocol="APIPE",
                     extra={"block": block.off, "chunk": chunk,
                            "nslots": nslots, "pub": d0})
        self._send_pkt(channel, dest_world, pkt)
        sreq._cancel_fn = lambda: self._cancel_send(sreq, dest_world,
                                                    channel)
        _pv_rndv.inc()
        _pv_bytes.inc(nbytes)
        if tr is not None:
            tr.record("protocol", "rndv_rts", "i", dest=dest_world,
                      bytes=nbytes, proto="APIPE")
        return True

    def _release_send_side(self, sreq) -> None:
        """Free the send-side rendezvous resources (arena pipeline slots
        and/or the exposure handle) — on FIN or a successful cancel."""
        ap = getattr(sreq, "_ap", None)
        if ap is not None:
            ap["arena"].free(ap["block"])
            sreq._ap = None
        if sreq.handle is not None and sreq.channel is not None:
            sreq.channel.release_buffer(sreq.handle)
            sreq.handle = None

    def _apipe_recv_start(self, req: RecvRequest, pkt: Packet) -> None:
        """Receiver side of the pipelined rendezvous (engine mutex held):
        set up the drain state, consume the chunks the RTS says are
        already published, and ACK the batch so the sender refills."""
        channel = self.u.channel_for(pkt.src_world)
        total = pkt.nbytes
        cap = req.capacity
        n = min(total, cap)
        view = None
        if n > 0 and req.buf is not None and req.datatype.is_contiguous:
            try:
                mv = as_bytes_view(req.buf)
                view = np.frombuffer(mv, dtype=np.uint8, count=cap)
            except (ValueError, TypeError):
                view = None
        if view is None and n > 0:
            # derived datatype (or no byte view): stage + unpack at end
            req.scratch = np.empty(n, dtype=np.uint8)
            view = req.scratch
        chunk = pkt.extra["chunk"]
        req._ap = {"block": pkt.extra["block"], "chunk": chunk,
                   "nslots": pkt.extra["nslots"],
                   "nchunks": (total + chunk - 1) // chunk, "drained": 0,
                   "view": view, "n": n, "src": pkt.src_world,
                   "sreq_id": pkt.sreq_id, "channel": channel,
                   "arena": channel.arena,
                   "env": (pkt.comm_src, pkt.tag, total)}
        # failure containment: the ULFM sweep recognizes in-flight
        # rendezvous recvs by _rndv_env — without it a receiver parked
        # mid-pipeline on a dead sender's next APUB hangs forever
        req._rndv_env = (pkt.comm_src, pkt.tag, total)
        self.engine.track(req)
        self._apipe_drain(req, pkt.extra["pub"])

    def _apipe_drain(self, req: RecvRequest, upto: int) -> None:
        from .. import faults
        from ..transport import arena as arena_mod
        faults.fire("rndv_chunk")     # crash/delay mid-pipeline (drain)
        ap = req._ap
        tr = self.engine.tracer
        from .. import metrics as _metrics
        mx = _metrics.LIVE
        t0 = _time.perf_counter() if mx is not None else 0.0
        chunk, n = ap["chunk"], ap["n"]
        nslots, block = ap["nslots"], ap["block"]
        upto = min(upto, ap["nchunks"])
        k = k0 = ap["drained"]
        while k < upto:
            # drain slot-contiguous runs in one streaming copy: chunks
            # k..k+run-1 are consecutive in the block (no slot wrap)
            run = min(upto - k, nslots - (k % nslots))
            lo = k * chunk
            span = min(run * chunk, n - lo) if lo < n else 0
            if span > 0:
                off = block + (k % nslots) * chunk
                ap["view"][lo:lo + span] = ap["arena"].view(off, span)
            arena_mod.pv_pipeline.inc(run)
            if tr is not None:
                tr.record("protocol", "rndv_chunk", "i", dir="drain",
                          k=k, chunks=run, bytes=span)
            k += run
        ap["drained"] = k
        if mx is not None and k > k0:
            ap["channel"].account_rndv_chunk(t0)
        if ap["drained"] < ap["nchunks"]:
            # one ACK for the whole batch: everything <= drained-1 is
            # consumed, so the sender may refill those chunks' slots
            ack = Packet(PktType.RNDV_AACK, self.u.world_rank,
                         sreq_id=ap["sreq_id"], rreq_id=req.req_id,
                         offset=ap["drained"] - 1)
            ap["channel"].send_packet(ap["src"], ack)
        else:
            if req.scratch is not None and req.buf is not None and n > 0:
                req.datatype.unpack(req.scratch, req.buf, req.count)
            fin = Packet(PktType.RNDV_FIN, self.u.world_rank,
                         sreq_id=ap["sreq_id"])
            ap["channel"].send_packet(ap["src"], fin)
            src, tag, total = ap["env"]
            req._ap = None
            self._finish_recv(req, None, total, src, tag)

    def _on_apipe_pub(self, pkt: Packet) -> None:
        req = self.engine.outstanding.get(pkt.rreq_id)
        if req is None or getattr(req, "_ap", None) is None:
            return     # raced completion/cancel: drop
        self._apipe_drain(req, pkt.offset + 1)

    def _on_apipe_ack(self, pkt: Packet) -> None:
        from .. import faults
        from ..transport import arena as arena_mod
        faults.fire("rndv_chunk")     # crash/delay mid-pipeline (refill)
        sreq = self.engine.outstanding.get(pkt.sreq_id)
        if sreq is None or getattr(sreq, "_ap", None) is None:
            return
        ap = sreq._ap
        if ap["next"] >= ap["nchunks"]:
            return                 # everything already published
        chunk = ap["chunk"]
        nbytes = len(ap["data"])
        nslots = ap["nslots"]
        block = ap["block"]
        tr = self.engine.tracer
        # chunks <= pkt.offset are consumed; chunk j reuses the slot
        # chunk j-nslots carried, so everything through offset+nslots
        # may be published now (slot-contiguous runs, one copy each)
        hi = min(pkt.offset + nslots + 1, ap["nchunks"])
        k = ap["next"]
        if hi <= k:
            return
        from .. import metrics as _metrics
        mx = _metrics.LIVE
        t0 = _time.perf_counter() if mx is not None else 0.0
        while k < hi:
            run = min(hi - k, nslots - (k % nslots))
            lo = k * chunk
            span = min(run * chunk, nbytes - lo)
            off = block.off + (k % nslots) * chunk
            ap["arena"].view(off, span)[:] = ap["data"][lo:lo + span]
            arena_mod.pv_pipeline.inc(run)
            if tr is not None:
                tr.record("protocol", "rndv_chunk", "i", dir="pub", k=k,
                          chunks=run, bytes=span)
            k += run
        ap["next"] = hi
        if mx is not None:
            sreq.channel.account_rndv_chunk(t0)
        pub = Packet(PktType.RNDV_APUB, self.u.world_rank,
                     rreq_id=pkt.rreq_id, offset=hi - 1)
        sreq.channel.send_packet(pkt.src_world, pub)
