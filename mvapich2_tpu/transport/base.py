"""Channel interface — the L3→L2 seam.

Analog of the CH3 channel API (SURVEY §1: MPIDI_CH3_iStartMsg / iSendv /
Rndv_transfer / MPIDI_CH3I_Progress, declared in
/root/reference/src/mpid/ch3/include/mpidimpl.h:1510-1640). A channel moves
opaque packets between world ranks; the protocol layer above it implements
matching and eager/rendezvous semantics. Channels in-tree:

  * local  — in-process threaded fabric (unit tests; nemesis-shm analog)
  * tcp    — sockets between rank processes (sock channel analog)
  * shm    — shared-memory rings between co-located processes (mrail SMP
             analog; C++ fast path)
  * ici    — the TPU path: collectives don't go through packets at all but
             lower to XLA ops on the device mesh (SURVEY §5.8)
"""

from __future__ import annotations

import enum
from typing import Any, Dict, Optional

import numpy as np


class PktType(enum.IntEnum):
    """Wire packet types — analog of MPIDI_CH3_Pkt_type_t
    (/root/reference/src/mpid/ch3/include/mpidpkt.h:96-182)."""

    EAGER_SEND = 1
    RNDV_RTS = 2           # request-to-send (no payload)
    RNDV_CTS = 3           # clear-to-send (receiver matched)
    RNDV_DATA = 4          # RPUT/R3 payload chunk
    RNDV_FIN = 5           # transfer complete
    RNDV_APUB = 6          # pipelined arena rendezvous: chunk published
    RNDV_AACK = 7          # pipelined arena rendezvous: chunk consumed
    # one-sided (SURVEY §2.1 RMA)
    RMA_PUT = 10
    RMA_GET = 11
    RMA_GET_RESP = 12
    RMA_ACC = 13
    RMA_GET_ACC = 14
    RMA_GET_ACC_RESP = 15
    RMA_CAS = 16
    RMA_CAS_RESP = 17
    RMA_FOP = 18
    RMA_FOP_RESP = 19
    RMA_LOCK = 20
    RMA_LOCK_GRANTED = 21
    RMA_UNLOCK = 22
    RMA_FLUSH = 23
    RMA_FLUSH_ACK = 24
    RMA_PSCW_POST = 25
    RMA_PSCW_COMPLETE = 26
    # control
    BARRIER_CTL = 30
    REVOKE = 31            # ULFM comm revoke propagation
    SHUTDOWN = 32
    CANCEL_SEND_REQ = 33   # retract an unmatched send (mpidpkt.h CANCEL)
    CANCEL_SEND_RESP = 34
    # CMA rendezvous — consumed entirely inside the C plane
    # (native/cplane.cpp): RTS carries (pid, address); the receiver
    # pulls via process_vm_readv and answers FIN (status in offset)
    RNDV_RTS_CMA = 40
    RNDV_FIN_CMA = 41


class Packet:
    """One wire message. ``data`` is a contiguous uint8 ndarray or None."""

    __slots__ = ("type", "src_world", "ctx", "comm_src", "tag", "nbytes",
                 "data", "sreq_id", "rreq_id", "protocol", "offset", "extra")

    def __init__(self, type: PktType, src_world: int, ctx: int = 0,
                 comm_src: int = 0, tag: int = 0, nbytes: int = 0,
                 data: Optional[np.ndarray] = None, sreq_id: int = 0,
                 rreq_id: int = 0, protocol: str = "", offset: int = 0,
                 extra: Optional[Dict[str, Any]] = None):
        self.type = type
        self.src_world = src_world
        self.ctx = ctx
        self.comm_src = comm_src
        self.tag = tag
        self.nbytes = nbytes
        self.data = data
        self.sreq_id = sreq_id
        self.rreq_id = rreq_id
        self.protocol = protocol
        self.offset = offset
        self.extra = extra

    def __repr__(self):
        return (f"Packet({self.type.name}, src={self.src_world}, "
                f"ctx={self.ctx}, tag={self.tag}, nbytes={self.nbytes})")


# ---------------------------------------------------------------------------
# binary wire codec
# ---------------------------------------------------------------------------
# Fixed struct header + optional pickled `extra` + raw payload, replacing
# whole-packet pickling: on the small-message path pickle.dumps/loads and
# its extra payload copy were ~30% of the per-message cost (the vbuf
# header of mpidpkt.h, in spirit). Layout:
#   _PKT_HDR | extra (exlen bytes, pickle) | payload (rest of the blob)
# `protocol` is an 8-byte NUL-padded field (RGET/RPUT/R3 fit).

import pickle as _pickle
import struct as _struct

_PKT_HDR = _struct.Struct("<Biiiiqqqq8si")
PKT_HDR_SIZE = _PKT_HDR.size

# Wire-carried plane ownership (native/cplane.cpp PLANE_CTX_FLAG): the
# sender sets bit 30 of ctx on EAGER/RTS packets whose communicator is
# plane-owned; the C matcher claims exactly those. decode_packet strips
# it so a python fallback receiver (no native plane) still matches.
PLANE_CTX_FLAG = 1 << 30


def encode_packet(pkt: "Packet") -> bytes:
    """Serialize to one contiguous blob (single payload copy)."""
    ex = b"" if pkt.extra is None else _pickle.dumps(pkt.extra, 5)
    hdr = _PKT_HDR.pack(int(pkt.type), pkt.src_world, pkt.ctx,
                        pkt.comm_src, pkt.tag, pkt.nbytes, pkt.sreq_id,
                        pkt.rreq_id, pkt.offset,
                        pkt.protocol.encode("ascii"), len(ex))
    if pkt.data is None:
        return hdr + ex
    # b"".join accepts buffer-protocol objects: the payload (an ndarray
    # or memoryview) is copied exactly once, into the blob
    return b"".join((hdr, ex, memoryview(pkt.data).cast("B")))


def decode_packet(blob) -> "Packet":
    """Inverse of encode_packet; ``blob`` is bytes or a memoryview."""
    (ptype, src_world, ctx, comm_src, tag, nbytes, sreq_id, rreq_id,
     offset, proto, exlen) = _PKT_HDR.unpack_from(blob, 0)
    pos = PKT_HDR_SIZE
    extra = None
    if exlen:
        extra = _pickle.loads(bytes(blob[pos:pos + exlen]))
        pos += exlen
    data = None
    if len(blob) > pos:
        data = np.frombuffer(blob, dtype=np.uint8, offset=pos)
    return Packet(PktType(ptype), src_world, ctx & ~PLANE_CTX_FLAG,
                  comm_src, tag, nbytes, data, sreq_id, rreq_id,
                  proto.rstrip(b"\0").decode("ascii"), offset, extra)


class Channel:
    """Transport ABC — the seam where mrail/nemesis/psm/sock plug in."""

    name = "abstract"
    # True if RTS packets may carry a zero-copy handle the receiver can pull
    # from directly (RGET analog). Local/shm channels support this.
    supports_rget = False
    # True for channels whose progress is pure memory polling (shm): the
    # engine spins instead of sleeping — the reference's CQ polling
    # discipline (SURVEY §3.5: "this polling loop is THE cpu hot loop").
    busy_poll = False
    # True where the peer is a thread of this process, so a packet can
    # carry a device array as the object it is (the device lane of
    # pt2pt/protocol.py) and ``device_of`` names the peer's device.
    carries_device = False

    def attach(self, engine) -> None:
        """Bind to the owning rank's progress engine."""
        self.engine = engine

    # -- traffic accounting (MPI_T per-channel counters + trace events) ---
    def _acct_pvars(self):
        """Lazily-declared per-channel-name pvars (the mv2_mpit.c channel
        counter discipline: bytes/messages per direction). Shared by
        every instance of a channel class in the process — same
        aggregation scope as every other pvar here."""
        pv = getattr(self, "_acct_pv", None)
        if pv is None:
            from .. import mpit
            n = self.name
            pv = (mpit.pvar(f"chan_{n}_msgs_sent",
                            mpit.PVAR_CLASS_COUNTER, "channel",
                            f"packets sent on the {n} channel"),
                  mpit.pvar(f"chan_{n}_bytes_sent",
                            mpit.PVAR_CLASS_COUNTER, "channel",
                            f"wire bytes sent on the {n} channel"),
                  mpit.pvar(f"chan_{n}_msgs_recv",
                            mpit.PVAR_CLASS_COUNTER, "channel",
                            f"packets received on the {n} channel"),
                  mpit.pvar(f"chan_{n}_bytes_recv",
                            mpit.PVAR_CLASS_COUNTER, "channel",
                            f"wire bytes received on the {n} channel"))
            self._acct_pv = pv
        return pv

    def account_send(self, dest_world: int, nbytes: int) -> None:
        pv = self._acct_pvars()
        pv[0].inc()
        pv[1].inc(nbytes)
        eng = getattr(self, "engine", None)
        if eng is not None and (tr := eng.tracer) is not None:
            tr.record("channel", f"{self.name}_send", "i",
                      dest=dest_world, bytes=nbytes)

    def account_recv(self, nbytes: int) -> None:
        pv = self._acct_pvars()
        pv[2].inc()
        pv[3].inc(nbytes)
        eng = getattr(self, "engine", None)
        if eng is not None and (tr := eng.tracer) is not None:
            tr.record("channel", f"{self.name}_recv", "i", bytes=nbytes)

    def account_rndv_chunk(self, t0: float) -> None:
        """Rendezvous chunk-batch completion: elapsed seconds since the
        caller's ``t0`` into the lat_rndv_chunk histogram. Callers gate
        on ``metrics.LIVE`` themselves (same one-attribute-check
        discipline as the tracer sites), so the off-path cost is the
        caller's check, not a call."""
        from .. import metrics as _metrics
        mx = _metrics.LIVE
        if mx is not None:
            mx.rec_since("lat_rndv_chunk", t0)

    def send_packet(self, dest_world: int, pkt: Packet) -> None:
        raise NotImplementedError

    def poll(self) -> bool:
        """Advance I/O; return True if any packet was processed."""
        raise NotImplementedError

    def wait_for_event(self, timeout: float) -> None:
        """Block up to ``timeout`` seconds for inbound traffic (may return
        early spuriously). Default: busy-poll granularity sleep."""
        import time
        time.sleep(min(timeout, 0.0002))

    def wait_fds(self):
        """File objects that become readable when this channel has inbound
        traffic; the engine selects on the union across channels so a
        blocked rank wakes immediately (doorbells/sockets)."""
        return []

    def pre_wait(self) -> None:
        """Called by the engine BEFORE its last empty poll ahead of a
        blocking wait — a channel can advertise 'receiver sleeping' so
        senders know a doorbell is needed (see ShmChannel's adaptive
        bell). The order closes the race: advertise, then final poll,
        then sleep."""

    def post_wait(self) -> None:
        """Called after the blocking wait returns."""

    # -- zero-copy rendezvous hooks (RGET path) ---------------------------
    def expose_buffer(self, array: np.ndarray) -> Any:
        """Register a send buffer for remote pull; returns an opaque handle
        carried in the RTS (the rkey analog, gen2/ibv_rndv.c:171)."""
        raise NotImplementedError

    def pull_buffer(self, src_world: int, handle: Any, nbytes: int) -> np.ndarray:
        """RGET: read the peer's exposed buffer."""
        raise NotImplementedError

    def release_buffer(self, handle: Any) -> None:
        pass

    def close(self) -> None:
        pass
