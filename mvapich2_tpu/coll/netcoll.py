"""net2: the node-leader networking tier past the np=64 flat2 ceiling.

Three-level hierarchy's outermost ring (create_2level_comm.c's
leader_comm, scaled out): ranks are folded round-robin into
``ceil(size/64)`` groups, each group small enough to ride the
single-node machinery (flat2 waves through the plane when the group is
plane-owned and the payload fits; the scheduled binomial/recursive-
doubling shapes otherwise), and the per-group leaders bridge the
KVS/TCP lanes with one small inter-leader exchange. np 64 -> 256 (and
up to NET2_MAX_RANKS) without widening any single wave.

Group color is ``rank % ngroups`` — round-robin, not blocked — so a
group's members sit at distinct node-local indices and the flat2 lane
(MIN local index of the group) stays inside the 8-lane window even
when several groups share a node. Leaders are then exactly global
ranks ``0..ngroups-1`` (the minimum-rank member of each group under a
rank-keyed split), which keeps the leader subcomm's membership
deterministic for the KVS rendezvous.

Subcomms are built lazily with ``comm.split`` *inside* the algorithm
(a collective, but every rank of the comm reaches the same algorithm
for the same call — the tuning verdict is uniform by construction) and
cached on the comm for its lifetime. When the split cannot produce the
two-level shape (degenerate group count, failed rendezvous), the
algorithms degrade internally to the scheduled single-level shapes so
the dispatch verdict stays uniform across ranks: no rank ever takes a
different *table* row than its peers, only a different interior.

Each phase mirrors api.py's plane branch: try the flat tiers first,
fall to the scheduled algorithm — that composition (node-local flat2
wave + tiny leader exchange) is what buys the latency win over running
one 128-wide recursive doubling across the TCP lanes.
"""

from __future__ import annotations

import math
import time as _time
from typing import Optional

import numpy as np

from .. import metrics as _metrics
from ..utils.config import get_config
from ..utils.mlog import get_logger
from . import algorithms as alg

log = get_logger("netcoll")

_STATE_ATTR = "_net2_state"


def _trace_net2(name: str, comm, **args) -> None:
    """Drop a 'cplane'-lane instant at a net2 phase boundary. Python-
    side (unlike the flat/flat2 instants, which the C ring emits) —
    the leader bridge runs above the plane, so the ring never sees
    it."""
    try:
        tr = getattr(comm.u.engine, "tracer", None)
        if tr is not None:
            tr.record("cplane", f"net2_{name}", "i", **args)
    except Exception:   # tracing must never kill a collective
        pass


def _bump(name: str) -> None:
    try:
        from .. import mpit
        mpit.pvar(name).inc()
    except Exception:
        pass


class _Net2State:
    """Cached two-level split of one comm: intra group + leader ring."""

    __slots__ = ("ngroups", "intra", "leaders", "is_leader")

    def __init__(self, ngroups, intra, leaders, is_leader):
        self.ngroups = ngroups
        self.intra = intra
        self.leaders = leaders
        self.is_leader = is_leader


def net2_enabled() -> bool:
    try:
        return bool(get_config()["NET2"])
    except Exception:
        return True


def net2_applicable(comm) -> bool:
    """Gate shared by every net2 algorithm AND api.py's plane branch:
    uniform across ranks (size + launcher-uniform cvars only)."""
    from .tuning import net2_max_ranks
    if not net2_enabled():
        return False
    if getattr(comm, "is_inter", False):
        return False
    return 64 < comm.size <= net2_max_ranks()


def _state(comm) -> Optional[_Net2State]:
    """The comm's cached two-level split; built on first use (all ranks
    reach here together — split is collective but safe). None when the
    shape cannot be built, and the miss is cached too (a failed split
    must not be retried asymmetrically)."""
    st = getattr(comm, _STATE_ATTR, "__unset__")
    if st != "__unset__":
        return st
    st = None
    try:
        ngroups = math.ceil(comm.size / 64)
        if 1 < ngroups < comm.size:
            color = comm.rank % ngroups
            # host buffers of the host schedule: no device channel
            intra = comm.split(color, key=comm.rank, _bind=False)
            is_leader = intra is not None and intra.rank == 0
            leaders = comm.split(0 if is_leader else None, key=comm.rank,
                                 _bind=False)
            if intra is not None and (not is_leader or leaders is not None):
                st = _Net2State(ngroups, intra, leaders, is_leader)
    except Exception as e:   # degrade, never desync: every rank that
        log.warn("net2 split failed (%s): scheduled fallback", e)
        st = None            # got here falls to the same sched shape
    try:
        setattr(comm, _STATE_ATTR, st)
    except Exception:
        pass
    if st is not None:
        log.dbg(1, "net2: %d ranks -> %d groups (leader=%s)",
                  comm.size, st.ngroups, st.is_leader)
    return st


# ---------------------------------------------------------------------------
# per-phase sub-collectives: flat tier first, sched second — the same
# gate order as api.py's plane branch, applied to the SUBcomm
# ---------------------------------------------------------------------------

def _sub_plane(sub):
    from .api import _plane_engine
    return _plane_engine(sub)


def _sub_allreduce(sub, arr: np.ndarray, op, tag: int) -> np.ndarray:
    pch = _sub_plane(sub)
    if pch is not None and sub.size > 1:
        from .api import _plane_coll_max, _plane_red_ok
        if arr.nbytes <= _plane_coll_max(pch, sub) \
                and _plane_red_ok(op, arr):
            from . import flatcoll
            got = flatcoll.try_allreduce(pch, sub, np.ascontiguousarray(arr),
                                         op)
            if got is not None:
                return got
    return alg.allreduce_recursive_doubling(sub, arr, op, tag)


def _sub_reduce(sub, arr: np.ndarray, op, tag: int) -> Optional[np.ndarray]:
    """Reduce to sub rank 0; the folded array there, None elsewhere."""
    pch = _sub_plane(sub)
    if pch is not None and sub.size > 1:
        from .api import _plane_coll_max, _plane_red_ok
        if arr.nbytes <= _plane_coll_max(pch, sub) \
                and _plane_red_ok(op, arr):
            from . import flatcoll
            taken, got = flatcoll.try_reduce(pch, sub,
                                             np.ascontiguousarray(arr),
                                             op, 0)
            if taken:
                return got
    return alg.reduce_binomial(sub, arr, op, 0, tag)


def _sub_bcast(sub, data: np.ndarray, root: int, tag: int) -> None:
    pch = _sub_plane(sub)
    if pch is not None and sub.size > 1:
        from .api import _plane_coll_max
        if data.nbytes <= _plane_coll_max(pch, sub):
            from . import flatcoll
            if flatcoll.try_bcast(pch, sub, data, root):
                return
    alg.bcast_binomial(sub, data, root, tag)


def _sub_barrier(sub, tag: int) -> None:
    pch = _sub_plane(sub)
    if pch is not None and sub.size > 1:
        from . import flatcoll
        if flatcoll.try_barrier(pch, sub):
            return
    alg.barrier_dissemination(sub, tag)


# ---------------------------------------------------------------------------
# ALGOS entries (tuning-table signatures)
# ---------------------------------------------------------------------------

def allreduce_net2(comm, arr: np.ndarray, op, tag: int) -> np.ndarray:
    """fold-in-group -> leader allreduce -> fan-out-in-group. The
    fan-in-first property holds per level: no leader publishes on the
    bridge before its whole group folded (reduce completes on the
    leader), and no member reads a result its leader has not
    republished — the PR 11 wave ordering, one level up."""
    st = _state(comm) if net2_applicable(comm) else None
    if st is None:
        return alg.allreduce_reduce_scatter_allgather(comm, arr, op, tag)
    mx = _metrics.LIVE
    t0 = _time.perf_counter() if mx is not None else 0.0
    _bump("coll_level_net")
    _trace_net2("fold", comm, groups=st.ngroups, bytes=int(arr.nbytes))
    folded = _sub_reduce(st.intra, arr, op, st.intra.next_coll_tag())
    if st.is_leader:
        _trace_net2("bridge", comm, groups=st.ngroups,
                    bytes=int(arr.nbytes))
        folded = _sub_allreduce(st.leaders, folded, op,
                                st.leaders.next_coll_tag())
    else:
        folded = np.empty_like(arr)
    _trace_net2("fanout", comm, groups=st.ngroups, bytes=int(arr.nbytes))
    out = np.ascontiguousarray(folded)
    _sub_bcast(st.intra, out, 0, st.intra.next_coll_tag())
    if mx is not None:
        mx.rec_since("lat_coll_net2", t0)
    return out


def bcast_net2(comm, data: np.ndarray, root: int, tag: int) -> None:
    """root -> its leader (when distinct) -> leader bridge -> groups.
    With round-robin colors the root's group leader is global rank
    ``root % ngroups``; the root forwards to it inside the group, so
    the bridge always radiates from a leader."""
    st = _state(comm) if net2_applicable(comm) else None
    if st is None:
        alg.bcast_binomial(comm, data, root, tag)
        return
    mx = _metrics.LIVE
    t0 = _time.perf_counter() if mx is not None else 0.0
    _bump("coll_level_net")
    in_root_group = (comm.rank % st.ngroups) == (root % st.ngroups)
    if in_root_group:
        # root's group: in-group bcast from the ROOT's sub-rank first,
        # which lands the payload on the group leader (sub rank 0)...
        root_sub = root // st.ngroups
        _trace_net2("fold", comm, groups=st.ngroups,
                    bytes=int(data.nbytes))
        _sub_bcast(st.intra, data, root_sub, st.intra.next_coll_tag())
    if st.is_leader:
        # ...then the bridge radiates from that leader...
        _trace_net2("bridge", comm, groups=st.ngroups,
                    bytes=int(data.nbytes))
        _sub_bcast(st.leaders, data, root % st.ngroups,
                   st.leaders.next_coll_tag())
    if not in_root_group:
        # ...and every other group fans out from ITS leader.
        _trace_net2("fanout", comm, groups=st.ngroups,
                    bytes=int(data.nbytes))
        _sub_bcast(st.intra, data, 0, st.intra.next_coll_tag())
    if mx is not None:
        mx.rec_since("lat_coll_net2", t0)


def barrier_net2(comm, tag: int) -> None:
    """group barrier (arrival) -> leader barrier -> group release
    bcast. The release is a bcast, not a second barrier: members may
    not leave until their leader has crossed the bridge (first-wave
    sync per level)."""
    st = _state(comm) if net2_applicable(comm) else None
    if st is None:
        alg.barrier_dissemination(comm, tag)
        return
    mx = _metrics.LIVE
    t0 = _time.perf_counter() if mx is not None else 0.0
    _bump("coll_level_net")
    _trace_net2("fold", comm, groups=st.ngroups, bytes=0)
    _sub_barrier(st.intra, st.intra.next_coll_tag())
    if st.is_leader:
        _trace_net2("bridge", comm, groups=st.ngroups, bytes=0)
        _sub_barrier(st.leaders, st.leaders.next_coll_tag())
    _trace_net2("fanout", comm, groups=st.ngroups, bytes=0)
    release = np.zeros(1, dtype=np.uint8)
    _sub_bcast(st.intra, release, 0, st.intra.next_coll_tag())
    if mx is not None:
        mx.rec_since("lat_coll_net2", t0)
