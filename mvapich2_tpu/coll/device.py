"""The ICI device-collective channel — XLA collectives behind the MPI seam.

This is the analog of the mrail channel installing tuned collectives
per-communicator in init_MV2_collops (reference:
src/mpid/ch3/channels/mrail/src/rdma/ch3i_comm.c:27-100): a mesh-bound
``Comm`` gets its ``coll_fns`` entries overwritten with wrappers that
dispatch to the XLA-native ops (ops/collectives.py) when the tuning layer
selects the device transport, and fall back to the host algorithm zoo
otherwise.

Execution model (TPU-first): MPI ranks are bound 1:1 to the devices of a
1-D ``jax.sharding.Mesh``. A collective call is executed *once* as a jitted
``shard_map`` program over the mesh — each rank deposits its local shard at
a rendezvous, the lowest rank runs the XLA op (which lowers to ICI
ring/tree collectives in one fused program), and every rank picks up its
output shard. This is exactly how a single-controller JAX job drives a TPU
pod slice; on a multi-controller (multi-host) job the same ops run under
``jax.distributed`` with each host contributing its local shards.

One leader serves the three bindings (``DeviceCollChannel._leader``:
stage, look the program up and call it, hand out); the 1:1 mesh channel,
the one-device slot channel and the leaders-per-chip fold channel differ
in two hooks, ``_stage`` (the program's key and operands out of what was
deposited) and ``_hand_out`` (every rank's result out of the output).
No leader waits for the device: a result is handed out at the enqueue
and its caller waits for it.
Which kernel a program holds is not decided here: the lowering asks the
kernel modules' one tier rule (``ops/pallas_ici.planned_tier``, with its
amendments for the op, the collective and the mesh inside it), and
``_decide_tier`` asks the same rule for what the call counts.

A communicator derived from a bound one (dup, split, create, the
topology constructors) is bound too, where the parent's channel has a
channel for the new group (``derive``, asked by ``bind_derived``): its
members share a rendezvous of their own, which the world's keeps; where
it has none the communicator keeps the host arm, and a device array
handed to it is counted (``note_host_comm``).

The rendezvous requires all bound ranks to share one process (rank threads
— the virtual-pod harness, ``mpirun --vpod``) or one jax.distributed
runtime; process-mode ranks without either keep the host path (the install
is a no-op, logged).
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import metrics as _metrics
from .. import mpit
from ..utils.config import cvar, get_config
from ..utils.mlog import get_logger

log = get_logger("device_coll")

cvar("USE_DEVICE_COLL", True, bool, "coll",
     "Enable the ICI device-collective channel on mesh-bound comms "
     "(analog of MV2_USE_RDMA_COLL-style channel toggles).")
cvar("DEVICE_COLL_MIN_BYTES", 16384, int, "coll",
     "Host->device transport crossover: host-buffer collectives below "
     "this size keep the host path (device dispatch has fixed "
     "rendezvous+dispatch overhead). Device-resident buffers always take "
     "the device path. Measured profiles override this.")
cvar("DEVICE_NBC_SEG_BYTES", 1 << 20, int, "coll",
     "Segment size (bytes per shard) of device NONBLOCKING collectives: "
     "elementwise-safe ops (iallreduce/ibcast) split into independent "
     "program segments, each an async dispatch the NBC DAG's poll "
     "vertices pump to completion — compute overlaps the still-flying "
     "segments. 0 = one segment (no split).")
cvar("DEVICE_NBC_MAX_SEGS", 8, int, "coll",
     "Upper bound on device nonblocking-collective segments per call "
     "(each segment is one cached program signature; unbounded "
     "splitting would thrash the program/executable caches).")

from ..utils import is_device_array  # noqa: E402 — shared predicate

# counted in every rank's every call, so bound once, not fetched by name
_DEPOSIT_AS_IS = mpit.pvar("dev_deposit_as_is")
_PLAN_HIT = mpit.pvar("dev_call_plan_hit")
_PLAN_FILED = mpit.pvar("dev_call_plan_filed")
_DERIVED = mpit.pvar("dev_coll_derived")
_config = get_config()

# -- MV2T_JAX_PROFILE: hardware-profiler bracket ------------------------
# When the cvar names a directory, the FIRST device collective starts a
# jax.profiler trace there and an atexit hook stops it — one xplane
# trace covering the whole device-collective region of the run, the
# input the TPU-hardware tuning pass (ROADMAP item 1: ici_chunk_bytes /
# ICI_PIPELINE_DEPTH at the 64 MiB point) reads in TensorBoard/XProf.
# Declared in mpit.py (cvar JAX_PROFILE) so MPI_T enumerates it early.
_jax_profile_started = False
_jax_profile_lock = threading.Lock()


def _maybe_start_jax_profile() -> None:
    global _jax_profile_started
    if _jax_profile_started:          # one attr check once started
        return
    out_dir = str(get_config().get("JAX_PROFILE", "") or "")
    if not out_dir:
        return    # cheap re-check per call: device dispatch is ms-scale
    with _jax_profile_lock:
        if _jax_profile_started:
            return
        _jax_profile_started = True
        try:
            import atexit

            import jax
            # the runtime's events and the ``dev_<coll>`` annotations are
            # what trace/xprof.py reads; jax's Python tracer, on by
            # default, would trace every rank thread frame by frame
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(out_dir, profiler_options=opts)
            atexit.register(_stop_jax_profile)
            log.info("jax.profiler trace started -> %s "
                     "(MV2T_JAX_PROFILE)", out_dir)
        except Exception as e:   # profiling must never kill a collective
            log.warn("MV2T_JAX_PROFILE start failed: %r", e)


def _stop_jax_profile() -> None:
    try:
        import jax
        jax.profiler.stop_trace()
    except Exception:
        pass


def _op_name(op) -> Optional[str]:
    """Map a core.op builtin to an XLA reduction name (None = no analog)."""
    from ..core import op as opmod
    table = {id(opmod.SUM): "sum", id(opmod.MAX): "max",
             id(opmod.MIN): "min", id(opmod.PROD): "prod"}
    return table.get(id(op))


def _dtype_lowers(dtype: np.dtype) -> bool:
    """True when the dtype round-trips through the device unchanged:
    the float, integer and unsigned kinds, bfloat16 among the floats
    (numpy, which knows it only through ml_dtypes, says kind 'V').
    With jax x64 disabled, 64-bit types would be silently downcast —
    wrong answers, so they stay on the host path. One answer for every
    collective of the mesh, slot and fold channels; a call it turns
    away is counted (``_note_turned_away``)."""
    import jax
    if dtype.itemsize == 8 and not jax.config.jax_enable_x64:
        return False
    return _kind_lowers(dtype)


@functools.lru_cache(maxsize=None)
def _kind_lowers(dtype: np.dtype) -> bool:
    """The dtype's half of ``_dtype_lowers``, asked once per dtype: the
    gate sits on every call's path, in every rank's slice."""
    from ..ops._compat import dtype_kind
    return dtype_kind(dtype) in "fiu"


# -- daemon device-executable cache (ISSUE 14) --------------------------
# The PiP attach-not-construct model applied to compiled programs: with
# MV2T_DAEMON + MV2T_DAEMON_EXEC_CACHE on, a program build first asks
# the node daemon's exec-cache for a serialized executable under the
# (kernel, shape, mesh, jax/profile fingerprint) key and deserializes
# it — skipping jax tracing + Mosaic compile, the dominant cold-start
# cost of a device job. A miss builds as before and exports the traced
# program after its first successful call (the only point the concrete
# input layout exists). Every failure path degrades to the plain build:
# the cache can be absent, stale-epoch, or unexportable (pre-export
# jax, interpreter callbacks) without ever breaking a collective.

class _ExportingProgram:
    """Built program that serializes itself into the daemon exec-cache
    after its first successful call."""

    __slots__ = ("fn", "key", "_stored")

    def __init__(self, fn, key: str):
        self.fn = fn
        self.key = key
        self._stored = False

    def __call__(self, *xs):
        out = self.fn(*xs)
        if not self._stored:
            self._stored = True    # one export attempt per process
            from ..ops import _compat
            from ..runtime import daemon
            blob = _compat.serialize_executable(self.fn, *xs)
            if blob is not None:
                daemon.exec_cache_put(self.key, blob)
        return out


class _ImportedProgram:
    """Deserialized cached executable; a failure on the FIRST call
    (corrupt entry, incompatible artifact that slipped the fingerprint)
    rebuilds from source instead of failing the collective."""

    __slots__ = ("fn", "rebuild", "_proven")

    def __init__(self, fn, rebuild):
        self.fn = fn
        self.rebuild = rebuild
        self._proven = False

    def __call__(self, *xs):
        if self._proven:
            return self.fn(*xs)
        try:
            out = self.fn(*xs)
        except Exception as e:   # noqa: BLE001 — cache must not break calls
            log.warn("cached executable failed on first call (%r); "
                     "rebuilding from source", e)
            self.fn = self.rebuild()
            out = self.fn(*xs)
        self._proven = True
        return out


# -- phase spans inside dev_<coll> (device lane) --------------------------
# One blocking device collective is one ``dev_<coll>`` B/E span per rank
# (``_run``); inside it the rendezvous (``_execute``) and the one leader
# (``DeviceCollChannel._leader``, with the channel's ``_stage`` and
# ``_hand_out`` hooks) open these, all with the collective's ``seq`` and
# ``coll`` in their args so a reader can join rank 0's leader phases
# with the other ranks' waits:
#
#   dev_arrive       every rank: slot deposit -> counted in at the gate;
#                    on rank 0 also its wait for the last rank
#   dev_stage        rank 0: ``_stage``, the program's key and operands
#                    out of what was deposited
#   dev_chip_fold    rank 0, fold channel, inside dev_stage: level 1
#                    as far as the host does it: the look at every
#                    chip's deposits and, where one does not lie flat
#                    on its chip, the staging and a fold dispatch a
#                    chip; its E says ``k``, ``chips``, ``stacked`` (the
#                    planar copies of a chip's deposits made this call:
#                    0 where the deposits were the fold's operands) and
#                    ``fused`` (the fold ran inside the mesh program:
#                    nothing was launched in this span) and ``in_ring``
#                    (... and there inside the ring kernel's rounds)
#   dev_dispatch     rank 0: program-cache lookup + enqueue, in
#                    ``_leader``'s own frame; its E says ``built`` when
#                    the call made or loaded the program
#   dev_collect      rank 0, in ``_hand_out``: one result per rank out of
#                    the output; its
#                    E says ``parts``, the arrays cut out of it by eager
#                    device ops: 0 where the ranks share one array or get
#                    the program's own outputs (the mesh and slot
#                    channels, always), k*ndev for the fold channel's
#                    reduce_scatter_block
#   dev_release      rank 0: opening the gate; the others: their wait
#                    in line, from their arrival to being let go; their
#                    E says ``turn``, the rank's place in the line the
#                    leader let go (0 left first)
#   dev_deliver      every rank, after dev_<coll> E: _deliver; its E says
#                    ``relaid``, 1 when _deliver issued a reshape (0
#                    for every program here: their results are flat)
#
# Names are literals at the call sites (analysis/events.py resolves them
# through ``_phase``'s callers) and every E sits in ``__exit__``.

_NO_PHASE = contextlib.nullcontext()    # ``with`` target while untraced
_profiler = None    # ``jax.profiler``, bound by the first traced ``_run``


class _Phase:
    """One open phase span: the recorder, the span's name and the args
    its B went into the ring with. A site puts what it learns meanwhile
    into ``args`` (a dict of its own, made on first use); leaving
    records the E, with the B's args as they are, or a copy with the
    site's beside them: a B in the ring is never changed."""

    __slots__ = ("_tr", "_name", "_began", "_more")

    def __init__(self, tr, name: str, began: dict):
        self._tr = tr
        self._name = name
        self._began = began
        self._more = None

    @property
    def args(self) -> dict:
        more = self._more
        if more is None:
            more = self._more = {}
        return more

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        more = self._more
        began = self._began if more is None else {**self._began, **more}
        # the name _phase's B went under, which the events lint read
        self._tr.record("device", self._name, "E",  # mv2tlint: ignore[events]
                        began)
        return False


def _device_resident(recvbuf) -> bool:
    """The caller keeps the result on the device (no host recvbuf to
    write through)."""
    return recvbuf is None or is_device_array(recvbuf) \
        or type(recvbuf).__name__ == "_InPlace"


class _CallPlan:
    """What one blocking device collective decided that its arguments,
    the cvars and the loaded profile settle: the first call of a
    signature on a rank walks ``_select_transport``, ``_as_local``,
    ``_op_name`` and ``_decide_tier`` and files their answers here; every
    later call of that signature finds them with one lookup
    (``DeviceCollChannel.plan_of``) and runs ``_run`` on them
    (``run_plan``). Only a call that took the device with the caller's
    own flat array is filed, so a plan's existence says both. ``key`` is
    what the call was found by; ``writes`` the cvar write count
    (``Config.writes``) it was decided under: once that has moved the
    plan is stale, and the call decides, and files, again. ``name``,
    ``op`` and ``root`` are what ``_run`` was given. Pvars are kept as
    objects."""

    __slots__ = ("key", "writes", "name", "op", "root", "tier", "fallback",
                 "bumps", "wire")

    def __init__(self, key=None, writes: int = 0):
        self.key = key
        self.writes = writes
        self.fallback = None    # the XLA lowering's (reason, nbytes)
        self.bumps = ()         # (pvar, by how much), once per call
        self.wire = None        # (instant, wire bytes per rank per call)


# The collectives whose streaming kernel says what it puts on the wire:
# collective -> (the instant's name, which is also the stem of its
# ``_bytes`` pvar; the function of the kernel module that said the tier
# reckoning the bytes a rank sends in one call from ``(n, dtype, p)``).
# ``_decide_tier`` holds the conditions, once.
_WIRE = {"alltoall": ("dev_a2a_wire", "alltoall_wire_bytes"),
         "allgather": ("dev_ag_wire", "all_gather_wire_bytes"),
         "reduce_scatter_block": ("dev_rs_wire",
                                  "reduce_scatter_wire_bytes"),
         "bcast": ("dev_bc_wire", "bcast_wire_bytes")}
# The collectives whose result, every rank's deposit, is what must fit
# the engine: the tier rule is asked with the result's bytes.
_KEYED_ON_RESULT = ("allgather",)


class _Gate:
    """The blocking collectives' one meeting point. Every rank counts
    itself in (``arrive``) and only the leader waits for the count; the
    others wait to be let out (``leave``), which the leader does as
    soon as the program is enqueued and every rank's result, an array
    the device may still be computing, lies in ``rv.result`` (``open``):
    no leader waits for the device. They leave one at a time, in the
    order they came, each letting the next go before it does anything
    else: first in, first out, so every rank's call lasts one period of
    the loop. Woken all at once, as a ``threading.Barrier`` wakes them,
    eight rank threads take the interpreter lock in whatever order the
    OS deals it, and a rank early in and late out makes the slowest
    rank of a call a millisecond slower than the period, differently
    from run to run (PERF.md, PR 34).

    ``last_first`` turns the line round: the last to come leaves first.
    It is for ranks that share one device (the slot channel), whose
    callers all wait on one completion: the runtime hands a result to
    the threads that wait for it last come, first served, so after a
    first-in-first-out line the first rank in is the last to have its
    result, the order turns over every call, and the slowest rank of a
    call sits three slices above the period; let go last in, first out,
    the ranks enter their wait in the reverse of the order they came
    and come back in the order they came, the same every call
    (PERF.md, PR 50). Ranks on devices of their own wait on completions
    of their own and keep the first-in-first-out line.

    One ``threading.Lock`` a rank is its semaphore: taken at the start,
    released by whoever lets the rank go. ``abort`` breaks the gate for
    good, as ``threading.Barrier.abort`` did: whoever waits or comes
    later raises ``threading.BrokenBarrierError``."""

    def __init__(self, size: int, last_first: bool = False):
        self.size = size
        self.last_first = last_first
        self.broken = False
        self._lock = threading.Lock()
        self._arrived = 0
        self._line: List[int] = []      # the waiting ranks, as they came
        self._after: List[Optional[int]] = [None] * size
        self._turn = [0] * size         # each rank's place in the line let go
        self._sems = [threading.Lock() for _ in range(size)]
        for sem in self._sems:
            sem.acquire()
        self._leader_sem = threading.Lock()
        self._leader_sem.acquire()
        self._leader_waits = False

    @property
    def n_waiting(self) -> int:
        return self._arrived

    def arrive(self, rank: int, leader: bool) -> None:
        """Count ``rank`` in; the leader returns when every rank is."""
        with self._lock:
            if self.broken:
                raise threading.BrokenBarrierError
            self._arrived += 1
            full = self._arrived == self.size
            if leader:
                self._leader_waits = not full
            else:
                self._line.append(rank)
                if full and self._leader_waits:
                    self._leader_waits = False
                    self._leader_sem.release()
        if leader and not full:
            self._leader_sem.acquire()
            if self.broken:
                raise threading.BrokenBarrierError

    def open(self) -> None:
        """The leader lets the line go, its head first (its tail first
        where the gate is ``last_first``)."""
        with self._lock:
            line, self._line = self._line, []
            if self.last_first:
                line.reverse()
            self._arrived = 0
            for turn, (rank, nxt) in enumerate(
                    zip(line, line[1:] + [None])):
                self._after[rank] = nxt
                self._turn[rank] = turn
        if line:
            self._sems[line[0]].release()
        if self.broken:
            raise threading.BrokenBarrierError

    def leave(self, rank: int) -> int:
        """Wait to be let go, and let the next in line go. Returns the
        rank's place in the line the leader let go (0 left first): the
        order the gate chose, for whoever traces it."""
        self._sems[rank].acquire()
        turn = self._turn[rank]
        nxt, self._after[rank] = self._after[rank], None
        if nxt is not None:
            self._sems[nxt].release()
        if self.broken:
            raise threading.BrokenBarrierError
        return turn

    def abort(self) -> None:
        with self._lock:
            self.broken = True
            line, self._line = self._line, []
            if self._leader_waits:
                self._leader_waits = False
                self._leader_sem.release()
        for rank in line:       # a line no leader will open any more
            self._sems[rank].release()


class _Rendezvous:
    """Per-bound-comm meeting point: slots for each rank's shard and one
    gate per collective (deposit -> leader compute -> pickup).
    MPI already requires every rank to issue collectives on a comm in the
    same order, so one in-flight collective per comm is the contract."""

    def __init__(self, size: int, last_first: bool = False,
                 root: Optional["_Rendezvous"] = None, key=None):
        self.size = size
        self.gate = _Gate(size, last_first)
        self.slots: List = [None] * size
        self.result: List = [None] * size
        self.error: Optional[BaseException] = None
        # nonblocking rendezvous: no gate to block in — ranks deposit
        # under nb_lock into per-sequence call records and the NBC DAG's
        # poll vertices observe arrival/launch/completion state instead
        self.nb_lock = threading.Lock()
        self.nb_calls: Dict[int, dict] = {}
        self.nb_failed = False
        # the rendezvous ``bind_universes`` made is the root of those
        # of the communicators derived from its own (``derive``): it
        # keeps them, under ``_reg_lock``, by ``key`` = (context id,
        # the group's world ranks), each with the members still holding
        # it. The colours of one split share a context id, so the id
        # alone is no key
        self.root = root or self
        self.key = key
        self._reg_lock = threading.Lock()
        self._derived: Dict[tuple, list] = {}   # key -> [rendezvous, holders]

    def derive(self, key: tuple) -> "_Rendezvous":
        """The one rendezvous of the derived communicator ``key``, for
        a member that binds it: the first to come makes it, with this
        one's gate order, the rest find it; each holds it until its
        ``release``."""
        with self._reg_lock:
            held = self._derived.get(key)
            if held is None:
                held = self._derived[key] = [
                    _Rendezvous(len(key[1]), self.gate.last_first, self,
                                key), 0]
            held[1] += 1
            return held[0]

    def release(self, rv: "_Rendezvous") -> None:
        """A member freed its communicator; the last one out takes the
        entry with it."""
        with self._reg_lock:
            held = self._derived.get(rv.key)
            if held is not None and held[0] is rv:
                held[1] -= 1
                if held[1] <= 0:
                    del self._derived[rv.key]

    def live(self, world_rank: Optional[int] = None) -> List["_Rendezvous"]:
        """The derived rendezvous held now (those ``world_rank`` is a
        member of, if given)."""
        with self._reg_lock:
            return [rv for (_ctx, world), (rv, _n) in self._derived.items()
                    if world_rank is None or world_rank in world]

    def abort(self) -> None:
        """Break the gate so peers blocked in a device collective see
        a failure instead of deadlocking (called when a rank dies).
        In-flight NONBLOCKING device collectives have no gate to
        break: the sticky nb_failed flag makes every later poll raise
        MPIX_ERR_PROC_FAILED so survivor DAGs unwind."""
        self.nb_failed = True
        self.gate.abort()


class _VDeposit:
    """One rank's alltoallv contribution at the rendezvous: the densely
    packed send payload (canonical packed order — peer 0's elements
    first) plus this rank's scounts row, from which the leader assembles
    the full static counts matrix."""

    __slots__ = ("data", "scounts")

    def __init__(self, data, scounts):
        self.data = data
        self.scounts = tuple(int(c) for c in scounts)


class DeviceCollChannel:
    """One rank's handle on the mesh-bound collective engine."""

    # hierarchy levels one call on this channel exercises — the
    # coll_level_* pvars bumped per call in _run (three-level contract:
    # chip = HBM slot fold, ici = mesh ring phases, net = node leaders)
    LEVELS: Tuple[str, ...] = ("ici",)
    # collectives this channel routes to the device tier; the rest keep
    # their host entries at install time
    SUPPORTED: Tuple[str, ...] = ("allreduce", "reduce", "bcast",
                                  "allgather", "alltoall",
                                  "reduce_scatter_block", "alltoallv")
    # what _phase reads, set per call by _run: the rank's recorder while
    # its blocking collective is traced, its ``seq`` (the blocking
    # collectives this rank has begun on this channel), and the one args
    # dict every phase event of the call carries, ``seq``, ``coll`` and
    # ``ctx``
    _tr = None
    _seq = 0
    _args: Optional[dict] = None
    # the tier ``_note_tier`` said the call in ``_run`` takes
    _tier: Optional[str] = None
    # the filed plan the call in ``_run`` runs on (``plan_of`` found
    # it), and the draft a call that has to decide files on its way out
    _plan: Optional[_CallPlan] = None
    _draft: Optional[_CallPlan] = None

    def __init__(self, mesh, axis, rendezvous: _Rendezvous, rank: int):
        self.mesh = mesh
        # ``axis``: one mesh axis name, or an ordered tuple of names —
        # then ranks span the product extent row-major and the programs
        # lower through the multi-axis torus decomposition
        # (ops/pallas_ici.ici_*_mesh, ISSUE 20)
        if isinstance(axis, (tuple, list)):
            self.axes: Tuple[str, ...] = tuple(str(a) for a in axis)
        else:
            self.axes = (str(axis),)
        self.axis = self.axes[0]
        self.rv = rendezvous
        self.rank = rank
        devices = list(np.asarray(mesh.devices).reshape(-1))
        self.device = devices[rank]
        self.devices = devices
        self.size = len(devices)
        # per-instance program cache (a class-level lru_cache would pin
        # freed channels + their compiled executables for process life)
        self._programs: Dict = {}
        self._nb_seq = 0     # per-rank nonblocking-collective sequence
        self._bind_calls()

    def _bind_calls(self) -> None:
        """What every blocking call on this rank's channel reads: its
        filed plans by signature (one dict a rank: each rank has its own
        channel object, so no lock; they go with the channel) and the
        level pvars it bumps."""
        self._plans: Dict[tuple, _CallPlan] = {}
        self._level_pvars = tuple(mpit.pvar(f"coll_level_{lv}")
                                  for lv in self.LEVELS)
        # whose channel this is: its communicator's ``ctx_coll`` (set by
        # ``install_device_coll``), its ranks' world ranks, and whether
        # ``derive`` made it (``_adopt`` then sets the last two)
        self.ctx: Optional[int] = None
        self.world: Tuple[int, ...] = tuple(range(self.size))
        self.derived = False

    @property
    def multi_axis(self) -> bool:
        return len(self.axes) > 1

    def _axis_sizes(self) -> Tuple[Tuple[str, int], ...]:
        return tuple((a, self.mesh.shape[a]) for a in self.axes)

    def _pspec0(self):
        """The leading PartitionSpec entry covering this channel's
        ranks: the bare axis name (1-D, the classic binding) or the
        ordered axes tuple (row-major flattened rank order)."""
        return self.axes if self.multi_axis else self.axis

    def _mesh_extent(self) -> int:
        """Participant count of the mesh program: the comm size on the
        1:1 binding, the chip count on the fold channel (where each
        mesh shard carries a whole chip's folded contribution)."""
        return self.size

    def abort(self) -> None:
        """This rank is dying: break its rendezvous here and every live
        one derived from the same root that it is a member of, so that
        whoever waits for it in a row communicator's collective raises
        as on the world's, and no other group is touched."""
        self.rv.abort()
        for rv in self.rv.root.live(self.world[self.rank]):
            rv.abort()

    # -- channels of derived communicators -------------------------------
    def derive(self, members: Sequence[int], ctx: int
               ) -> Optional["DeviceCollChannel"]:
        """This rank's channel for a communicator derived from the one
        this channel is bound to, or None where the host arm has to
        carry it (counted: dev_coll_fallback_host_comm). ``members``
        are the new group's ranks in this communicator, in the new
        rank order; ``ctx`` is the new context id.

        A group that is this communicator's whole group in its order
        (MPI_Comm_dup; a split with one colour and keys in rank order;
        cart_create without reorder) gets a channel over the same mesh
        and axis with a rendezvous of its own: it runs this channel's
        programs. A ring over some chips of the mesh, or in another
        order, has no program here yet."""
        if tuple(members) != tuple(range(self.size)):
            return None
        return self._adopt(self._twin(self._derived_rv(members, ctx)))

    def _twin(self, rv: _Rendezvous) -> "DeviceCollChannel":
        """A channel like this one, of the same rank, on ``rv``."""
        return DeviceCollChannel(self.mesh, self.axes, rv, self.rank)

    def _derived_rv(self, members: Sequence[int], ctx: int) -> _Rendezvous:
        """The rendezvous the members of the derived communicator share:
        kept by the root's, found by (context id, world ranks)."""
        return self.rv.root.derive(
            (ctx, tuple(self.world[m] for m in members)))

    def _adopt(self, child: "DeviceCollChannel") -> "DeviceCollChannel":
        """``child`` is the channel of a communicator derived from this
        one's: it counts its calls (dev_coll_derived, beside the level
        pvars), knows its ranks' world ranks, and where it is of this
        channel's size its programs are this channel's (same
        ``_chan_desc``, same keys: nothing is traced or compiled
        again)."""
        child.derived = True
        child.world = child.rv.key[1]
        child._level_pvars += (_DERIVED,)
        if child.size == self.size:
            child._programs = self._programs
        return child

    def release(self) -> None:
        """``Comm.free``: a derived channel lets go of its rendezvous
        (the last member out removes it from the root's keeping)."""
        if self.derived:
            self.rv.root.release(self.rv)

    # -- jitted program cache (per mesh, keyed by op signature) ----------
    def _program(self, name: str, n: int, dtype_str: str, op: str,
                 root: int, extra=None):
        key = (name, n, dtype_str, op, root, extra)
        got = self._programs.get(key)
        if got is None:
            got = self._programs[key] = self._cached_build(
                name, n, dtype_str, op, root, extra)
        return got

    def _phase(self, name: str):
        """Context manager for one phase span of the collective _run is
        in (module comment above): B now, E on leaving, both with its
        ``seq`` and ``coll``. Untraced it is the shared no-op, so a site
        costs this attribute check; ``as`` then binds None."""
        tr = self._tr
        if tr is None:
            return _NO_PHASE
        args = self._args
        tr.record("device", name, "B", args)
        return _Phase(tr, name, args)

    def _chan_desc(self) -> str:
        """The mesh half of the executable-cache key: channel flavor,
        extent and platform (two geometries must never share an
        artifact). Multi-axis channels key on every (axis, extent)
        pair — a 2x4 and a 4x2 mesh must never share an artifact
        either."""
        if self.multi_axis:
            shape = "x".join(f"{a}{s}" for a, s in self._axis_sizes())
            return (f"mesh{self.size}x{self.device.platform}"
                    f"@{shape}")
        return (f"mesh{self.size}x{self.device.platform}"
                f"@{self.axis}")

    def _cached_build(self, name: str, n: int, dtype_str: str, op: str,
                      root: int, extra=None):
        """The exec-cache seam around ``_build``: deserialize on hit,
        build + export-on-first-call on miss, plain build whenever the
        cache is off or this jax cannot export. ``extra`` is the
        per-signature static payload (the alltoallv counts matrix) —
        part of both cache keys."""
        from ..runtime import daemon
        if not daemon.exec_cache_enabled():
            return self._build(name, n, op, root, extra)
        from ..ops import _compat
        ck = "|".join(("mv2t-exec-v6", self._chan_desc(), name,
                       f"n{n}", dtype_str, f"op:{op}", f"root:{root}",
                       f"x:{extra!r}", _compat.exec_fingerprint()))
        blob = daemon.exec_cache_get(ck)
        if blob is not None:
            fn = _compat.deserialize_executable(blob)
            if fn is not None:
                return _ImportedProgram(
                    fn, lambda: self._build(name, n, op, root, extra))
        return _ExportingProgram(self._build(name, n, op, root, extra), ck)

    def _build(self, name: str, n: int, op: str, root: int, extra=None):
        if self.multi_axis:
            return self._build_mesh(name, n, op, root, extra)
        import jax
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import shard_map
        axis, p = self.axis, self._mesh_extent()

        # every f takes its rank's flat [n] block as deposited and
        # returns a flat block: the kernel wrappers speak flat arrays
        if name in ("allreduce", "reduce"):
            def f(x):
                # tier dispatch: VMEM flat ring / HBM-streaming chunked
                # ring / XLA, by shard bytes (coll/tuning.device_tier)
                from ..ops import pallas_ici
                return pallas_ici.ici_all_reduce(x, axis, p, op=op)
            out_specs = P(None)             # replicated [n]
        elif name == "bcast":
            def f(x):
                # tier dispatch: the streaming chain from the root's
                # shard (no other shard is read), or the XLA lowering
                from ..ops import pallas_ici
                return pallas_ici.ici_bcast(x, axis, p, root)
            out_specs = P(None)
        elif name == "allgather":
            def f(x):
                from ..ops import pallas_ici
                return pallas_ici.ici_all_gather(x, axis, p)
            out_specs = P(None)             # replicated [p*n]
        elif name == "alltoall":
            def f(x):                       # [p*c] -> [p*c]
                # tier dispatch: chunked HBM remote-DMA pairwise streamer
                # or the XLA lowering (ops/pallas_alltoall)
                from ..ops import pallas_alltoall
                return pallas_alltoall.ici_all_to_all(x, axis, p)
            out_specs = P(axis)             # global [p*n]
        elif name == "alltoallv":
            counts = extra                  # static p x p matrix

            def f(x):                       # [in_len] -> [out_len]
                from ..ops import pallas_alltoall
                return pallas_alltoall.ici_all_to_allv(x, axis, p, counts)
            out_specs = P(axis)             # global [p*out_len]
        elif name == "reduce_scatter_block":
            def f(x):                       # [p*c] -> [c]
                # tier dispatch: the ring's fold rounds alone on the
                # chunked HBM streamer, or the XLA lowering
                from ..ops import pallas_ici
                return pallas_ici.ici_reduce_scatter(x, axis, p, op=op)
            out_specs = P(axis)             # global [p*c]
        else:  # pragma: no cover
            raise KeyError(name)

        sm = shard_map(f, mesh=self.mesh, in_specs=(P(axis),),
                       out_specs=out_specs, check_vma=False)
        return jax.jit(sm)

    def _flat_rank(self):
        """Traced flattened rank over this channel's axes (row-major) —
        the SPMD analog of ``self.rank`` inside a mesh program."""
        from jax import lax
        idx = lax.axis_index(self.axes[0])
        for a in self.axes[1:]:
            idx = idx * self.mesh.shape[a] + lax.axis_index(a)
        return idx

    def _build_mesh(self, name: str, n: int, op: str, root: int,
                    extra=None):
        """Multi-axis programs: reductions ride the per-axis RS/AG torus
        decomposition (ici_*_mesh), bcast composes per-axis phases from
        the root's coordinates innermost-first, and the structural
        collectives (alltoall(v)) lower through XLA over the flattened
        axes tuple — the per-axis pairwise streamer is 1-D-addressed
        (the kernel half is future hardware work, ROADMAP item 2)."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from .. import ops
        from ..parallel.mesh import shard_map
        axes, p = self.axes, self._mesh_extent()
        sizes = self._axis_sizes()
        spec0 = self._pspec0()

        if name in ("allreduce", "reduce"):
            def f(x):                       # flat blocks, as in _build
                from ..ops import pallas_ici
                return pallas_ici.ici_all_reduce_mesh(x, sizes, op=op)
            out_specs = P(None)             # replicated [n]
        elif name == "bcast":
            # root's per-axis coordinates, innermost phase first: after
            # axis k's bcast the root's whole k-line carries the payload
            coords, r = [], root
            for a in reversed(axes):
                coords.append(r % self.mesh.shape[a])
                r //= self.mesh.shape[a]
            coords.reverse()

            def f(x):
                for a, c in reversed(tuple(zip(axes, coords))):
                    x = ops.bcast(x, a, c)
                return x
            out_specs = P(None)
        elif name == "allgather":
            def f(x):
                from ..ops import pallas_ici
                return pallas_ici.ici_all_gather_mesh(x, sizes)
            out_specs = P(None)             # replicated [p*n]
        elif name == "alltoall":
            c = n // p

            def f(x):                       # [p*c] -> [p*c]
                return lax.all_to_all(x.reshape(p, c), axes, split_axis=0,
                                      concat_axis=0,
                                      tiled=False).reshape(-1)
            out_specs = P(spec0)            # global [p*n]
        elif name == "alltoallv":
            counts = extra                  # static p x p matrix
            from ..ops.pallas_alltoall import packed_displs
            sdisp, rdisp, in_len, out_len = packed_displs(counts)

            def f(x):                       # [in_len] -> [out_len]
                # gather every rank's packed payload, then assemble ALL
                # receive rows statically (counts are static) and keep
                # this rank's — O(p) memory, but structurally correct on
                # any torus shape
                g = x
                for a in reversed(axes):
                    g = lax.all_gather(g, a, tiled=True)
                g = g.reshape(p, in_len)
                rows = []
                for dst in range(p):
                    parts = [lax.slice_in_dim(
                                g[src], sdisp[src][dst],
                                sdisp[src][dst] + counts[src][dst])
                             for src in range(p) if counts[src][dst]]
                    row = (jnp.concatenate(parts) if parts
                           else g[0][:0])
                    pad = out_len - row.shape[0]
                    if pad > 0:
                        row = jnp.pad(row, (0, pad))
                    rows.append(row)
                me = self._flat_rank()
                return lax.dynamic_index_in_dim(
                    jnp.stack(rows), me, axis=0, keepdims=False)
            out_specs = P(spec0)            # global [p*out_len]
        elif name == "reduce_scatter_block":
            def f(x):
                from ..ops import pallas_ici
                return pallas_ici.ici_reduce_scatter_mesh(x, sizes, op=op)
            out_specs = P(spec0)            # global [n]
        else:  # pragma: no cover
            raise KeyError(name)

        sm = shard_map(f, mesh=self.mesh, in_specs=(P(spec0),),
                       out_specs=out_specs, check_vma=False)
        return jax.jit(sm)

    # -- the rendezvous execution ----------------------------------------
    @staticmethod
    def _slot_extent(slot):
        """(n, dtype) of a deposited slot without pulling device arrays
        back to the host."""
        if isinstance(slot, _VDeposit):
            slot = slot.data
        if is_device_array(slot):
            return slot.size, slot.dtype
        arr = np.asarray(slot)
        return int(arr.size), arr.dtype

    def _execute(self, name: str, local: np.ndarray, op: str = "sum",
                 root: int = 0):
        """Run one device collective; ``local`` is this rank's shard
        ([n] host numpy or device array). Deposit at the rendezvous,
        rank 0 runs the channel's ``_leader`` hook, everyone picks up
        its result. Returns whatever the leader deposited for this rank
        (device array)."""
        rv = self.rv
        leader = self.rank == 0
        with self._phase("dev_arrive"):
            rv.slots[self.rank] = local
            try:
                rv.gate.arrive(self.rank, leader)
            except threading.BrokenBarrierError:
                raise RuntimeError(
                    "device collective aborted: a peer rank failed"
                ) from None
        if leader:
            try:
                rv.result = self._leader(name, op, root)
                rv.error = None
            except BaseException as e:   # noqa: BLE001 — must release peers
                rv.error = e
                rv.result = [None] * self.size
        with self._phase("dev_release") as ph:
            try:
                if leader:
                    rv.gate.open()
                elif ph is None:
                    rv.gate.leave(self.rank)
                else:       # its place in the line, on the span's E
                    ph.args["turn"] = rv.gate.leave(self.rank)
            except threading.BrokenBarrierError:
                rv.slots[self.rank] = None
                raise RuntimeError(
                    "device collective aborted: a peer rank failed"
                ) from None
        # release this rank's references promptly — retained slots/results
        # would pin device memory for the life of an idle comm
        res, rv.result[self.rank] = rv.result[self.rank], None
        rv.slots[self.rank] = None
        if rv.error is not None:
            raise RuntimeError(
                f"device collective {name} failed on the leader"
            ) from rv.error
        return res

    def _leader(self, name: str, op: str, root: int) -> List:
        """Leader compute, the one of every channel: ``_stage`` makes
        the program's key (``_program``'s arguments) and operands out of
        what was deposited, the program is looked up and called, and
        ``_hand_out`` gives every rank its result out of the output. The
        channels differ in the two hooks alone."""
        with self._phase("dev_stage"):
            key, operands = self._stage(name, op, root)
        # spelled out here, in the leader's own frame, and the hooks
        # return before it: a frame more under the program's first call
        # moved its lowering from 20 s to 44 s on the chip's host
        # (PERF.md, PR 26)
        with self._phase("dev_dispatch") as ph:
            had = len(self._programs)
            out = self._program(*key)(*operands)
            if ph is not None:      # this call made or loaded the program
                ph.args["built"] = len(self._programs) > had
        return self._hand_out(name, out)

    def _stage(self, name: str, op: str, root: int) -> Tuple[tuple, tuple]:
        """The deposited flat arrays are the shards of the one
        mesh-sharded global array the jitted shard_map program runs on.
        alltoallv: the static counts matrix is assembled from every
        rank's deposited scounts row and the packed payloads are padded
        to the mesh-wide length; the matrix is part of the program's
        (and the executable cache's) key."""
        slots = self.rv.slots
        n, dtype = self._slot_extent(slots[0])
        if name != "alltoallv":
            return ((name, n, str(dtype), op, root),
                    (self._global(self._shards(slots), n),))
        from ..ops.pallas_alltoall import packed_displs
        counts = tuple(tuple(s.scounts) for s in slots)
        _, _, in_len, _ = packed_displs(counts)
        return (("alltoallv", in_len, str(dtype), "none", 0, counts),
                (self._global(self._shards(slots, in_len), in_len),))

    def _hand_out(self, name: str, out) -> List:
        """Every rank gets its own device's shard of the output."""
        return self._per_rank(out)

    def _global(self, shards: List, n: int):
        """The mesh-sharded flat ``[len(shards) * n]`` program input over
        one ``[n]`` shard per mesh device; the shards are not copied."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.make_array_from_single_device_arrays(
            (len(shards) * n,),
            NamedSharding(self.mesh, P(self._pspec0())), shards)

    def _per_rank(self, out) -> List:
        """Each rank's own device's shard of the program's output."""
        with self._phase("dev_collect") as ph:
            if ph is not None:
                ph.args["parts"] = 0    # the program's own outputs
            per_dev = {s.device: s.data for s in out.addressable_shards}
            return [per_dev[self.devices[r]] for r in range(self.size)]

    def _shards(self, slots, pad_to: int = 0) -> List:
        """One flat ``[n]`` array per rank's device out of what the
        ranks deposited. A device array on its rank's own device goes in
        as it lies: no reshape, no eager op, no copy. A host buffer (or
        an array committed elsewhere) is put there from the host; an
        alltoallv payload shorter than the mesh-wide ``pad_to`` is
        padded (the shard_map shapes must be uniform). A call in which
        every deposit lay counts (dev_mesh_operands)."""
        import jax
        shards, lay = [], True
        for r, dep in enumerate(slots):
            s = dep = dep.data if isinstance(dep, _VDeposit) else dep
            short = max(0, pad_to - int(s.size))
            if _lies_on(s, self.devices[r]):
                if short:
                    import jax.numpy as jnp
                    s = jnp.pad(s, (0, short))
            else:
                s = np.asarray(s).reshape(-1)   # a view
                if short:
                    s = np.pad(s, (0, short))
                s = jax.device_put(s, self.devices[r])
            lay = lay and s is dep
            shards.append(s)
        if lay:
            mpit.pvar("dev_mesh_operands").inc()
        return shards

    # -- per-call tier accounting (the observable-fallback contract) -----
    def _note_tier(self, comm, name: str, local, op: Optional[str]) -> str:
        """Count which device tier THIS call runs (pvars
        dev_coll_tier_{vmem,hbm} / dev_coll_fallback_*) and drop a trace
        instant when the XLA lowering is taken — the once-invisible
        VMEM-cap cliff. Per call, unlike the per-traced-shape counting
        at the kernel wrappers (programs are cached per signature).
        Returns the tier label the call will run on ('vmem'/'hbm'/
        'quant'/'xla', 'slot' on the single-device channel) — the
        dispatch span and the lat_dev_<tier> histogram key off it.

        Which tier, and what that counts, is decided by ``_decide_tier``
        unless ``plan_of`` found the call a filed plan: then it is the
        plan's, decided by that same function on an earlier call. The
        counting and the instants are this call's either way."""
        plan = self._plan
        if plan is None:
            plan = self._draft or _CallPlan()
            self._decide_tier(plan, name, local, op)
        for pv, by in plan.bumps:
            pv.inc(by)
        if plan.wire is None and plan.fallback is None:
            return plan.tier
        tr = getattr(comm.u.engine, "tracer", None)
        if tr is not None:
            if plan.fallback is not None:
                tr.record("channel", "dev_coll_fallback", "i", coll=name,
                          nbytes=plan.fallback[1], reason=plan.fallback[0])
            else:
                # what the kernel this call runs puts on the wire, per
                # rank, by the kernel module's own reckoning; noted
                # here, in a frame that has returned before the leader
                # runs (PERF.md, PR 26), under the seq _run is about to
                # give the call
                tr.record("device", plan.wire[0], "i",  # mv2tlint: ignore[events]
                          {"coll": name, "seq": self._seq + 1,
                           "ctx": self.ctx, "wire_bytes": plan.wire[1]})
        return plan.tier

    def _decide_tier(self, plan: _CallPlan, name: str, local,
                     op: Optional[str]) -> None:
        """What a device collective counts for the tier it takes:
        ``plan.tier``, the pvars to bump (``bumps``), the XLA lowering's
        ``fallback`` instant, the kernel's ``wire`` instant. The tier is
        the kernel modules' rule's (``pallas_ici.planned_tier``,
        ``pallas_alltoall.planned_a2a_tier``), asked as the program's
        lowering asks it: of the shard the mesh program sees (the rank's
        deposit, or on the fold channel the chip's fold of them). Reads
        the call's extent, the cvars and the loaded profile, nothing
        else."""
        if self.mesh is None:
            plan.tier = "slot"  # single-device slot channel: no ICI tiers
            return
        n, dtype = self._slot_extent(local)
        nbytes = n * dtype.itemsize * (self.size
                                       if name in _KEYED_ON_RESULT else 1)
        p = self._mesh_extent()
        if name in ("alltoall", "alltoallv"):
            from ..ops import pallas_alltoall as kernels
            tier, reason = kernels.planned_a2a_tier(max(1, nbytes), dtype)
        else:
            from ..ops import pallas_ici as kernels
            tier, reason = kernels.planned_tier(
                name, nbytes, dtype, op, num_devices=p,
                multi_axis=self.multi_axis)
        plan.tier = tier
        if reason is not None:
            plan.fallback = (reason, int(nbytes))
            plan.bumps = ((mpit.pvar(f"dev_coll_fallback_{reason}"), 1),)
            return
        if tier == "xla":       # a lowering with no ring kernel to count
            return
        bumps = [(mpit.pvar(f"dev_coll_tier_{tier}"), 1)]
        # a kernel's wire is reckoned where the rank's deposit is the
        # kernel's operand: the streaming tier of a 1-D mesh on the 1:1
        # binding
        if (name in _WIRE and tier == "hbm" and not self.multi_axis
                and p == self.size):
            instant, reckon = _WIRE[name]
            plan.wire = (instant, getattr(kernels, reckon)(n, dtype, p))
            bumps.append((mpit.pvar(instant + "_bytes"), plan.wire[1]))
        if tier == "quant":
            # the measurable half of the quant claim: bytes kept off
            # the ICI wire by this call, per rank
            from ..ops import pallas_quant
            exact_b, wire_b = pallas_quant.wire_stats(n, dtype, p)
            bumps.append((mpit.pvar("dev_coll_quant_bytes_saved"),
                          max(0, exact_b - wire_b)))
        plan.bumps = tuple(bumps)

    def _run(self, comm, name: str, local, as_is: bool, op: str = "sum",
             root: int = 0):
        """Traced dispatch: one B/E span in the 'device' lane around the
        whole rendezvous+execute, its B carrying tier/op/bytes, the
        phase spans inside it (``_phase``), and the MV2T_JAX_PROFILE
        bracket for hardware runs. Every span of one collective carries
        its ``seq``: this rank's count of blocking collectives on the
        channel, equal on every rank because MPI orders collectives,
        and its ``ctx``, the communicator's ``ctx_coll``: every channel
        counts from 1, so a call is ``(ctx, seq)``.
        The B also says ``derived`` (the channel is a derived
        communicator's) and ``as_is``, ``_as_local``'s word that
        ``local`` is the caller's own array object. The span's length
        is its two
        stamps' difference; no clock is read for a call that is neither
        traced nor metered (``metrics.LIVE``).
        While a recorder is attached the call also lies on the jax
        profiler's host plane as a TraceAnnotation of the same name, so
        an MV2T_JAX_PROFILE trace shows it beside the device's ops; it
        says ``seq``, ``ctx``, ``derived`` and ``rank``, by which
        trace/xprof.py ties the runtime's events on this thread's line
        to the recorder's spans."""
        global _profiler
        tier = self._tier = self._note_tier(comm, name, local, op)
        for lv in self._level_pvars:    # the hierarchy levels it rides
            lv.inc()
        self._seq += 1
        tr = self._tr = getattr(comm.u.engine, "tracer", None)
        note = _NO_PHASE
        if tr is not None:
            if _profiler is None:
                import jax
                _profiler = jax.profiler
            span = f"dev_{name}"
            # the one dict the call's E and every phase event carry
            args = self._args = {"seq": self._seq, "coll": name,
                                 "ctx": self.ctx}
            tr.record("device", span, "B",
                      {"tier": tier, "op": op,
                       "bytes": int((local.data
                                     if isinstance(local, _VDeposit)
                                     else local).nbytes),
                       "seq": self._seq, "coll": name, "as_is": as_is,
                       "planned": self._plan is not None,
                       "ctx": self.ctx, "derived": self.derived})
            note = _profiler.TraceAnnotation(span, seq=self._seq,
                                             rank=self.rank, ctx=self.ctx,
                                             derived=self.derived)
        _maybe_start_jax_profile()
        mx = _metrics.LIVE
        if mx is not None:
            import time as _time
            t0 = _time.perf_counter()
        try:
            with note:
                out = self._execute(name, local, op=op, root=root)
        finally:
            if tr is not None:
                tr.record("device", span, "E", args)
        if mx is not None:
            # the rank's time in rendezvous + leader, per tier: it ends
            # at the enqueue, not at the result
            mx.rec_us(f"lat_dev_{tier}",
                      (_time.perf_counter() - t0) * 1e6)
        if self._draft is not None:     # decided on this call, and it ran
            self._file(name, local, as_is, op, root)
        return out

    # -- the call plan: a signature's decisions, made once ---------------
    def plan_of(self, name: str, sendbuf, count, datatype, op=None,
                root: int = 0) -> Optional[_CallPlan]:
        """``comm.<coll>``'s first question once the comm is known to be
        alive: has this rank decided a call of this signature before,
        under the cvars as they stand? Then here is what it filed, for
        ``run_plan``. Else None, and the caller goes on down today's
        chain, which decides and, where the call takes the device with
        the caller's own flat array, files (``_file``, out of ``_run``).

        The signature is what the call can observe in its arguments:
        the collective, the buffer's type, shape and dtype, the count
        and datatype as given, the op object and the root. A host
        buffer, a shaped or partial one, MPI_IN_PLACE and a type or op
        that does not lower are never filed, so they are never found."""
        try:
            key = (name, type(sendbuf), sendbuf.shape, sendbuf.dtype, count,
                   datatype, op, root)
            plan = self._plans.get(key)
        except (AttributeError, TypeError):     # no array, or unhashable
            self._draft = None
            return None
        if plan is None or plan.writes != _config.writes:
            self._draft = _CallPlan(key, _config.writes)
            return None
        return plan

    def run_plan(self, comm, plan: _CallPlan, sendbuf, recvbuf):
        """One call of a filed signature: the counters, ``_run`` as it
        is and with what it was given when the plan was filed (so the
        program is that call's: a planned call never builds one), the
        result handed back. ``comm.<coll>``'s lines, ``entry``,
        ``_select_transport``, ``_as_local``, ``_op_name`` and
        ``_decide_tier`` are not walked again."""
        self._draft = None
        self._plan = plan
        try:
            out = self._run(comm, plan.name, sendbuf, True, plan.op,
                            plan.root)
        finally:
            self._plan = None
        if plan.name == "reduce" and comm.rank != plan.key[-1]:
            return None     # the caller's root: the key's last word
        return self._hand_back(out, recvbuf)

    def _file(self, name: str, local, as_is: bool, op, root: int) -> None:
        """File the draft ``plan_of`` keyed and ``_decide_tier`` filled,
        now that the call it was decided on has run: ``_run`` got here,
        so ``_select_transport`` said device, and ``as_is`` is
        ``_as_local``'s word that the deposit is the caller's own flat
        array. From the next call on ``_note_tier`` counts the deposit
        too, which ``_as_local`` did on this one. A 64-bit type lowers
        or not by a jax flag no write count sees: decided every call."""
        plan, self._draft = self._draft, None
        if not as_is or local.dtype.itemsize == 8:
            return
        plan.name, plan.op, plan.root = name, op, root
        plan.bumps += ((_DEPOSIT_AS_IS, 1), (_PLAN_HIT, 1))
        self._plans[plan.key] = plan
        _PLAN_FILED.inc()

    def _hand_back(self, out, recvbuf, *v):
        """``_deliver`` (``_deliver_v`` given alltoallv's ``rcounts,
        rdispls``) inside the collective's dev_deliver phase span."""
        with self._phase("dev_deliver") as ph:
            if v:
                return self._deliver_v(out, recvbuf, *v)
            res = _deliver(out, recvbuf)
            if ph is not None:      # _deliver issued an eager reshape
                ph.args["relaid"] = int(res is not None and res is not out)
            return res

    # -- MPI-shaped entry points (match coll_fns signatures) -------------
    def allreduce(self, comm, sendbuf, recvbuf, count, datatype, op):
        local, as_is = _as_local(sendbuf, recvbuf, count)
        out = self._run(comm, "allreduce", local, as_is, op=_op_name(op))
        return self._hand_back(out, recvbuf)

    def reduce(self, comm, sendbuf, recvbuf, count, datatype, op, root):
        local, as_is = _as_local(sendbuf, recvbuf, count)
        out = self._run(comm, "reduce", local, as_is, op=_op_name(op))
        if comm.rank != root:
            return None
        return self._hand_back(out, recvbuf)

    def bcast(self, comm, buf, count, datatype, root):
        out = self._run(comm, "bcast", *_as_local(buf, buf, count),
                        root=root)
        return self._hand_back(out, buf)

    def allgather(self, comm, sendbuf, recvbuf, count, datatype):
        local, as_is = _as_local(sendbuf, recvbuf, count,
                                 in_place_start=comm.rank * count)
        out = self._run(comm, "allgather", local, as_is, op=None)
        return self._hand_back(out, recvbuf)

    def alltoall(self, comm, sendbuf, recvbuf, count, datatype):
        local, as_is = _as_local(sendbuf, recvbuf, count * comm.size)
        out = self._run(comm, "alltoall", local, as_is)
        return self._hand_back(out, recvbuf)

    def alltoallv(self, comm, sendbuf, scounts, sdispls, recvbuf,
                  rcounts, rdispls, datatype):
        """MoE-shaped variable-count alltoall: each rank packs its sends
        densely, deposits payload + scounts row, the leader assembles
        the static counts matrix and runs the counts-keyed kernel; the
        canonical packed result is rearranged to the caller's rdispls
        on the way out."""
        dep = _VDeposit(_pack_v(sendbuf, scounts, sdispls), scounts)
        self._draft = None  # its key would be a count vector: never filed
        out = self._run(comm, "alltoallv", dep, False, op=None)
        return self._hand_back(out, recvbuf, rcounts, rdispls)

    def _deliver_v(self, out, recvbuf, rcounts, rdispls):
        """Scatter the canonical packed device result (dense sender
        order — rank knows its own rcounts column, so no matrix needed)
        into the caller's layout."""
        rtotal = int(sum(rcounts))
        dense = _dense_displs(rcounts)
        if _device_resident(recvbuf):
            if list(rdispls) == dense:
                return out[:rtotal]
            # non-dense user layout: assemble on the host, push back
            import jax
            host = np.asarray(out)
            ext = max((rdispls[j] + rcounts[j]
                       for j in range(len(rcounts))), default=0)
            dst = np.zeros(ext, host.dtype)
            off = 0
            for j, cnt in enumerate(rcounts):
                dst[rdispls[j]:rdispls[j] + cnt] = host[off:off + cnt]
                off += cnt
            return jax.device_put(dst, self.device)
        host = np.asarray(out).reshape(-1)
        dst = np.asarray(recvbuf).reshape(-1)
        off = 0
        for j, cnt in enumerate(rcounts):
            dst[rdispls[j]:rdispls[j] + cnt] = host[off:off + cnt]
            off += cnt
        return None

    def reduce_scatter_block(self, comm, sendbuf, recvbuf, count, datatype,
                             op):
        local, as_is = _as_local(sendbuf, recvbuf, count * comm.size)
        out = self._run(comm, "reduce_scatter_block", local, as_is,
                        op=_op_name(op))
        return self._hand_back(out, recvbuf)

    # -- nonblocking device collectives on the NBC DAG (ISSUE 18) --------
    # The blocking path rendezvouses on a threading.Barrier; that cannot
    # ride a schedule vertex (DAG issue must never block). Instead the
    # i-collective becomes a small DAG: one CALL deposits this rank's
    # shard into a per-sequence call record, per-segment POLL vertices
    # launch the async jitted dispatch (first poller past full arrival)
    # and then re-read its completion state on every engine progress
    # pass, and a final CALL lands this rank's output shards. drain_all
    # pumps the parked polls exactly like shm work — communication
    # overlaps whatever compute the rank does between Icoll and Wait.

    def _nb_segments(self, name: str, n: int, dtype) -> List[tuple]:
        """[(off, len)] program segments. Elementwise-safe collectives
        (allreduce/bcast) stream segment-wise — early segments complete
        while later ones are still flying; structural ones (allgather,
        alltoall(v)) run as one dispatch."""
        if name not in ("allreduce", "bcast") or n <= 1:
            return [(0, n)]
        cfg = get_config()
        seg_bytes = int(cfg["DEVICE_NBC_SEG_BYTES"])
        if seg_bytes <= 0:
            return [(0, n)]
        seg = max(1, seg_bytes // max(1, dtype.itemsize))
        nseg = min(int(cfg["DEVICE_NBC_MAX_SEGS"]),
                   (n + seg - 1) // seg)
        if nseg <= 1:
            return [(0, n)]
        per = (n + nseg - 1) // nseg
        return [(o, min(per, n - o)) for o in range(0, n, per)]

    def nonblocking(self, comm, name: str, *a, plan: bool = False):
        """Build the device-tier request for one i-collective; None when
        this call cannot route (caller counts dev_coll_fallback_nbc).
        ``plan=True`` is the MPI_*_init pre-warm: run the same routing
        gates, then build the program signatures through the exec-cache
        seam instead of launching (returns True/False)."""
        if self.mesh is None or self.derived:
            # the slot channel keeps the host schedule, and so does a
            # derived communicator's channel for now
            return None
        opn, op_sel, root = None, None, 0
        rcounts = rdispls = None
        if name == "allreduce":
            sendbuf, recvbuf, count, datatype, op_sel = a
            opn = _op_name(op_sel)
            if opn is None:
                return None
            send_eff, n = sendbuf, count
            wire = count * datatype.size
        elif name == "bcast":
            buf, count, datatype, root = a
            sendbuf = recvbuf = send_eff = buf
            n = count
            wire = count * datatype.size
        elif name == "allgather":
            sendbuf, recvbuf, count, datatype = a
            send_eff, n = sendbuf, count
            wire = count * datatype.size * self.size
        elif name == "alltoall":
            sendbuf, recvbuf, count, datatype = a
            send_eff, n = sendbuf, count * self.size
            wire = count * datatype.size * self.size
        elif name == "alltoallv":
            (sendbuf, scounts, sdispls, recvbuf, rcounts, rdispls,
             datatype) = a
            if sdispls is None:
                sdispls = _dense_displs(scounts)
            if rdispls is None:
                rdispls = _dense_displs(rcounts)
            send_eff, n = sendbuf, int(sum(scounts))
            wire = n * datatype.size
        else:
            return None
        if type(sendbuf).__name__ == "_InPlace" \
                or type(recvbuf).__name__ == "_InPlace":
            return None
        if recvbuf is None or is_device_array(recvbuf):
            # jax arrays are immutable: the completion CALL needs a host
            # recv it can write through at wait() time
            return None
        if not _dtype_ok(send_eff) or not _dtype_ok(recvbuf):
            return None
        if _select_transport(comm, name, wire, op_sel,
                             send_eff) != "device":
            return None
        if plan:
            if name == "alltoallv":
                # the counts MATRIX is cross-rank state: the first
                # start() assembles it and builds (the build then sticks
                # in the program + exec caches for every later start)
                return False
            return self.prewarm(name, n, np.dtype(send_eff.dtype),
                                opn or "sum", root)
        if name == "alltoallv":
            local = _VDeposit(_pack_v(sendbuf, scounts, sdispls), scounts)
        else:
            local, _ = _as_local(sendbuf, recvbuf, n)
        return self._build_nonblocking(comm, name, local, opn or "sum",
                                       root, recvbuf, rcounts, rdispls)

    def _build_nonblocking(self, comm, name: str, local, op: str,
                           root: int, recvbuf, rcounts=None,
                           rdispls=None):
        """The i-collective as an NBC DAG (deposit CALL -> per-segment
        POLLs -> completion CALL); returns the schedule's Request."""
        from ..core.errors import MPIException, MPIX_ERR_PROC_FAILED
        from .nbc import engine as nbc_engine
        from .nbc.dag import SchedDAG
        rv = self.rv
        rank = self.rank
        seq = self._nb_seq
        self._nb_seq += 1
        n, dtype = self._slot_extent(local)
        segs = self._nb_segments(name, n, dtype)
        dag = SchedDAG()

        def deposit():
            with rv.nb_lock:
                if rv.nb_failed:
                    raise MPIException(
                        MPIX_ERR_PROC_FAILED,
                        f"device nonblocking {name}: a peer rank failed")
                rec = rv.nb_calls.get(seq)
                if rec is None:
                    rec = rv.nb_calls[seq] = {
                        "slots": [None] * self.size, "arrived": 0,
                        "shards": None,
                        "outs": [None] * len(segs),
                        "t0": [None] * len(segs),
                        "landed": [False] * len(segs),
                        "picked": 0}
                rec["slots"][rank] = local
                rec["arrived"] += 1
        dep = dag.call(deposit)
        polls = []
        for si, (off, ln) in enumerate(segs):
            polls.append(dag.poll(
                lambda si=si, off=off, ln=ln: self._nb_poll(
                    comm, name, seq, si, off, ln, dtype, op, root,
                    len(segs)),
                after=(dep,)))
        dag.call(lambda: self._nb_finish(name, seq, recvbuf, rcounts,
                                         rdispls),
                 after=tuple(polls))
        req = nbc_engine.start(comm, dag, f"dev-i{name}")
        req.device_nbc = True
        return req

    def _nb_poll(self, comm, name: str, seq: int, si: int, off: int,
                 ln: int, dtype, op: str, root: int, nseg: int) -> bool:
        """One engine pump of a parked device segment. False while peers
        are still arriving or the dispatch is in flight; the launch
        itself happens here, on the first poll past full arrival."""
        import time as _time

        from ..core.errors import MPIException, MPIX_ERR_PROC_FAILED
        rv = self.rv
        if rv.nb_failed:
            raise MPIException(
                MPIX_ERR_PROC_FAILED,
                f"device nonblocking {name}: a peer rank failed")
        with rv.nb_lock:
            rec = rv.nb_calls.get(seq)
            if rec is None or rec["arrived"] < self.size:
                return False
            out = rec["outs"][si]
            if out is None:
                out = rec["outs"][si] = self._nb_launch(
                    rec, name, si, off, ln, dtype, op, root)
                rec["t0"][si] = _time.perf_counter()
                mpit.pvar("dev_nbc_segments").inc()
                tr = getattr(comm.u.engine, "tracer", None)
                if tr is not None:
                    tr.record("device", "nbc_dev_issue", "i", coll=name,
                              seg=si, of=nseg, n=int(ln))
        ready = True
        if hasattr(out, "is_ready"):
            try:
                ready = bool(out.is_ready())
            except Exception:   # dispatch already resolved: treat as done
                ready = True
        if not ready:
            return False
        with rv.nb_lock:
            rec = rv.nb_calls.get(seq)
            if rec is not None and not rec["landed"][si]:
                rec["landed"][si] = True
                dt = _time.perf_counter() - (rec["t0"][si] or 0.0)
                tr = getattr(comm.u.engine, "tracer", None)
                if tr is not None:
                    tr.record("device", "nbc_dev_complete", "i",
                              coll=name, seg=si, us=round(dt * 1e6, 3))
                mx = _metrics.LIVE
                if mx is not None:
                    mx.rec_us("lat_dev_nbc", dt * 1e6)
        return True

    def _nb_launch(self, rec: dict, name: str, si: int, off: int,
                   ln: int, dtype, op: str, root: int):
        """Dispatch one program segment (under nb_lock, by whichever
        rank's poll got there first). Staging happens once per call;
        segment launches are plain async jit dispatches."""
        if name == "alltoallv":
            from ..ops.pallas_alltoall import packed_displs
            counts = tuple(tuple(s.scounts) for s in rec["slots"])
            _, _, in_len, _ = packed_displs(counts)
            return self._program(
                "alltoallv", in_len, str(dtype), "none", 0, counts)(
                    self._global(self._shards(rec["slots"], in_len),
                                 in_len))
        if rec["shards"] is None:
            rec["shards"] = self._shards(rec["slots"])
        shards = rec["shards"]
        seg = shards if (off, ln) == (0, int(shards[0].size)) else \
            [s[off:off + ln] for s in shards]
        return self._program(name, ln, str(dtype), op, root)(
            self._global(seg, ln))

    def _nb_finish(self, name: str, seq: int, recvbuf, rcounts,
                   rdispls) -> None:
        """Completion CALL: every segment polled ready — land this
        rank's output shards in recvbuf, retire the call record once the
        last rank picked up."""
        rv = self.rv
        with rv.nb_lock:
            rec = rv.nb_calls[seq]
            outs = list(rec["outs"])
        parts = []
        for out in outs:
            mine = None
            for s in out.addressable_shards:
                if s.device == self.device:
                    mine = s.data
                    break
            parts.append(np.asarray(mine).reshape(-1))
        res = parts[0] if len(parts) == 1 else np.concatenate(parts)
        if name == "alltoallv":
            self._deliver_v(res, recvbuf, rcounts, rdispls)
        else:
            _deliver(res, recvbuf)
        with rv.nb_lock:
            rec["picked"] += 1
            if rec["picked"] >= self.size:
                rv.nb_calls.pop(seq, None)

    def prewarm(self, name: str, n: int, dtype, op: str = "sum",
                root: int = 0, extra=None) -> bool:
        """Persistent-init hook: build (or exec-cache fetch) every
        program signature a start() of this call will dispatch, so the
        per-start cost is rendezvous + dispatch only. Returns False when
        a build fails (start falls back to building lazily)."""
        try:
            dt = np.dtype(dtype)
            for _, ln in self._nb_segments(name, n, dt):
                self._program(name, ln, str(dt), op, root, extra)
            return True
        except Exception:   # noqa: BLE001 — warm-up must never fail init
            return False


def _slot_fold(xs, op: str):
    """The slot reduction, ``xs -> [n]``, traced where it is called: in
    the slot channel's programs, alone in the fold channel's
    ``_fold_prog``, and in its fused mesh program in front of a level-2
    collective that cannot fold the operands itself (ops/pallas_ici.py
    ``_fold_unless_ring_does``: every engine but the streaming ring on
    whole-tile blocks, whose fold rounds read the operands as they lie).
    ``xs`` is the deposited flat ``(n,)`` arrays of the ranks sharing a
    device, or one staged planar ``(R, n)`` array; the body reads which
    from its operands. The fused Pallas slot kernel (ops/pallas_hbm)
    carries the sum: over flat operands of whole 128-lane rows it reads
    each buffer where it lies; the planar operand, or a ragged length
    stacked inside the trace, it takes as one slot array. Another op is
    the XLA reduction over that slot array. A kernel that fails to
    compile or run fails the collective — there is no second lowering
    to hide behind."""
    import jax.numpy as jnp

    from ..ops import pallas_hbm as ph
    if op == "sum" and xs[0].ndim == 1 and xs[0].shape[0] % 128 == 0:
        return ph.hbm_slot_allreduce_operands(xs)
    slots = xs[0] if xs[0].ndim == 2 else jnp.stack(xs)
    if op == "sum":
        return ph.hbm_slot_allreduce(slots)
    return {"max": jnp.max, "min": jnp.min, "prod": jnp.prod}[op](
        slots, axis=0)


def _lies_on(s, dev) -> bool:
    """A device array committed to exactly ``dev``: a deposit a program
    on that device takes as its operand where it lies."""
    return is_device_array(s) and s.devices() == {dev}


class HBMSlotChannel(DeviceCollChannel):
    """All bound ranks share ONE device: collectives run through an HBM
    slot segment — the device-side analog of the reference's slotted
    shared-memory collective segment (ch3_shmem_coll.c:527-528; see
    ops/pallas_hbm.py). Every rank deposits at the rendezvous and the
    leader runs one program on what was deposited and hands its output
    out at the enqueue, as the mesh and fold leaders do: the ranks go
    round under the kernel, and each waits for its own result where it
    uses it (its ``block_until_ready``, ``_deliver``'s copy into a host
    ``recvbuf``); a failure the runtime reports after the enqueue
    reaches every rank from there. Device arrays on the
    slot device go in as they lie, ``R`` operands and no eager op; host
    buffers are stacked on the host and staged as one ``(R, n)``
    operand. Either way the program is:

      * allreduce/reduce: one fused slot-reduce pass writing the result
        ONCE; the broadcast is zero-copy (every rank's result is a view
        of the shared slot) — ``R*m`` read + ``m`` written instead of
        the materialized ``2*R*m``. On ``R`` operands of whole 128-lane
        rows the kernel reads each buffer where it lies.
      * allgather: the slot array *is* the result (no device compute).
      * alltoall: ``R`` flat outputs, one per rank: output ``r`` is
        block ``r`` of every operand in sender order, written once in
        its final layout (each operand read once; no stack, no
        transposed intermediate, nothing cut out afterwards).
      * reduce_scatter_block: slot-reduce, then ``R`` ``(c,)`` outputs
        cut inside the program, one per rank.
      * bcast: the root slot only; all ranks share it.

    Used when more ranks than devices are bound (the mpirun-on-one-chip
    model); the 1:1 mesh binding uses DeviceCollChannel above.
    """

    LEVELS = ("chip",)
    SUPPORTED = ("allreduce", "reduce", "bcast", "allgather", "alltoall",
                 "reduce_scatter_block")

    def __init__(self, device, rendezvous: _Rendezvous, rank: int,
                 size: int):
        self.mesh = None
        self.axis = None
        self.rv = rendezvous
        self.rank = rank
        self.device = device
        self.devices = [device] * size
        self.size = size
        self._programs: Dict = {}
        self._nb_seq = 0
        self._bind_calls()

    def _chan_desc(self) -> str:
        return f"slot{self.size}x{self.device.platform}"

    def derive(self, members: Sequence[int], ctx: int
               ) -> Optional["HBMSlotChannel"]:
        """Any two or more of the chip's ranks, in any order, are a slot
        channel of their own on the same device: the rows and columns
        of a pencil, a reversed key, ``cart_sub``, MPI_Comm_create over
        a subset. A group of one gets none: the host entry of a
        one-rank communicator copies nothing."""
        if len(members) < 2:
            return None
        return self._adopt(
            HBMSlotChannel(self.device, self._derived_rv(members, ctx),
                           list(members).index(self.rank), len(members)))

    def _build(self, name: str, n: int, op: str, root: int, extra=None):
        """One jitted ``f(*xs)`` per signature. ``xs`` is what the
        leader had: the ``R`` deposited ``(n,)`` arrays, or one staged
        ``(R, n)`` array (bcast: the root's ``(n,)`` alone). The body
        reads which from its operands; ``extra``, the leader's operand
        count, only keeps the two forms apart in the program and
        executable caches. It returns one array every rank shares, or
        (alltoall, reduce_scatter_block) a tuple of ``R`` flat arrays,
        rank ``r``'s own result at ``r``; no operand is donated or
        aliased, the callers keep their send buffers."""
        import jax
        import jax.numpy as jnp
        R = self.size

        if name in ("allreduce", "reduce"):
            def f(*xs):                     # -> [n]
                return _slot_fold(xs, op)
        elif name == "reduce_scatter_block":
            c = n // R

            def f(*xs):                     # -> R x [c], rank r's block
                y = _slot_fold(xs, op)      # cut inside the program
                return tuple(y[r * c:(r + 1) * c] for r in range(R))
        elif name == "bcast":
            def f(x):                       # the root slot [n]
                return x
        elif name == "allgather":
            def f(*xs):                     # -> [R*n], no compute
                return (jnp.concatenate(xs) if xs[0].ndim == 1
                        else xs[0].reshape(R * n))
        elif name == "alltoall":
            c = n // R

            def f(*xs):                     # -> R x [n], rank r's result:
                # block r of every sender in sender order, written once
                # where it stays (compiled: one fusion per operand,
                # reading it once and writing into all R outputs)
                if xs[0].ndim == 2:
                    return tuple(xs[0][:, r * c:(r + 1) * c].reshape(n)
                                 for r in range(R))
                return tuple(jnp.concatenate(
                    [x[r * c:(r + 1) * c] for x in xs]) for r in range(R))
        else:  # pragma: no cover
            raise KeyError(name)
        return jax.jit(f)

    def _stage(self, name: str, op: str, root: int) -> Tuple[tuple, tuple]:
        """The program is handed what was deposited. Device arrays on
        the slot device are its operands as they lie (counted:
        dev_slot_operands); anything else is stacked on the host and
        staged once."""
        rv = self.rv
        n, dtype = self._slot_extent(rv.slots[root])
        xs = (rv.slots[root],) if name == "bcast" else tuple(rv.slots)
        if all(_lies_on(s, self.device) for s in xs):
            mpit.pvar("dev_slot_operands").inc()
        else:
            # host slots, or device arrays committed elsewhere on a
            # multi-device host: stage everything onto the slot device
            import jax
            host = [np.asarray(s).reshape(n) for s in xs]
            xs = (jax.device_put(
                host[0] if name == "bcast" else np.stack(host),
                self.device),)
        return (name, n, str(dtype), op, root, len(xs)), xs

    def _hand_out(self, name: str, out) -> List:
        """Shares the program's one result or hands out its per-rank
        outputs as the enqueue returned them: no leader waits for the
        device, every caller waits for its own result."""
        with self._phase("dev_collect") as ph:
            if ph is not None:
                ph.args["parts"] = 0    # no eager op cuts anything out
            # a program that returned one output per rank hands them
            # out; one array is the zero-copy share, every rank gets it
            return (list(out) if isinstance(out, tuple)
                    else [out] * self.size)


class DeviceFoldChannel(DeviceCollChannel):
    """Leaders-per-chip fold: more ranks than devices, but more than one
    device — the middle binding between the 1:1 mesh channel and the
    single-device slot channel (the two-level shmem/leader split of
    create_2level_comm.c, with the chip standing in for the node).

    ``n`` ranks over ``ndev`` devices, ``k = n // ndev`` ranks per chip,
    rank ``r`` on chip ``r // k`` (blocked, so a chip's ranks own
    contiguous result blocks). Each collective runs in two levels:

      * **chip fold** — every chip's ``k`` deposited slots are folded in
        HBM (the fused slot-reduce kernel for sum, the XLA reduction
        otherwise), exactly the slot channel's move applied per chip;
      * **ICI phase** — the ``ndev`` folded shards ride the ordinary
        mesh collective (ring RS/AG tiers, per-axis torus phases when
        the mesh is multi-axis), built over the CHIP count
        (``_mesh_extent``).

    Both levels of a reduction are one program, one launch a call, where
    every deposit is a flat device array on its own chip (and the mesh
    is 1-D): ``k`` mesh-sharded operands made of the deposits as they
    lie, handed to the level-2 dispatcher as they are. Where that is the
    streaming ring and the deposits make whole-tile ring blocks, level 1
    runs inside the ring kernel (counted: dev_fold_in_ring): its fold
    rounds read every chunk from all ``k`` deposits and fold them in
    VMEM as they use it, so no slot-reduce kernel runs in front of the
    ring and no fold result is written. Everywhere else (the flat VMEM
    ring, the quantized wire, the XLA lowering, a ragged length) the
    dispatcher folds them first, the slot reduction and then the
    collective on its result, still one program. A call with
    a host deposit, a shaped array or one committed to another chip
    among its ranks (and any call on a multi-axis mesh) folds chip by
    chip instead, a launch each (a chip that does not lie is staged as
    one planar ``(k, n)`` array first), and the ``ndev`` folds form the
    one global array of the unfused mesh program. allgather's fold is
    concatenation and always stages that array; bcast stages the root.

    Results fan back zero-copy per chip: every rank on a chip shares its
    chip's output shard (slices of it for reduce_scatter_block).
    alltoall(v) has no fold composition (per-peer payloads cross chips
    pairwise) and keeps the host path; nonblocking calls take the host
    schedule (counted dev_coll_fallback_nbc).
    """

    LEVELS = ("chip", "ici")
    SUPPORTED = ("allreduce", "reduce", "bcast", "allgather",
                 "reduce_scatter_block")
    # planar (k, n) copies ``_chip_stack`` made in the leader call under
    # way: the dev_chip_fold E's ``stacked`` and the dev_fold_stacked pvar
    _stacked = 0

    def __init__(self, mesh, axis, rendezvous: _Rendezvous, rank: int,
                 nranks: int):
        self.mesh = mesh
        if isinstance(axis, (tuple, list)):
            self.axes: Tuple[str, ...] = tuple(str(a) for a in axis)
        else:
            self.axes = (str(axis),)
        self.axis = self.axes[0]
        self.rv = rendezvous
        self.rank = rank
        mesh_devs = list(np.asarray(mesh.devices).reshape(-1))
        self.ndev = len(mesh_devs)
        self.k = nranks // self.ndev
        self.size = nranks
        self.chip = rank // self.k
        self.device = mesh_devs[self.chip]
        # rank -> its chip's device (the _leader/_deliver contract)
        self.devices = [mesh_devs[r // self.k] for r in range(nranks)]
        self._mesh_devices = mesh_devs
        self._programs: Dict = {}
        self._nb_seq = 0
        self._bind_calls()

    def _mesh_extent(self) -> int:
        return self.ndev

    def _twin(self, rv: _Rendezvous) -> "DeviceFoldChannel":
        return DeviceFoldChannel(self.mesh, self.axes, rv, self.rank,
                                 self.size)

    def _chan_desc(self) -> str:
        return f"fold{self.size}r{self.ndev}d_{super()._chan_desc()}"

    def nonblocking(self, comm, name: str, *a, plan: bool = False):
        return None     # host NBC schedule (fold has no DAG segments yet)

    def _fold_prog(self, op: str):
        """Per-chip fold program, ``_slot_fold`` jitted alone as
        ``f(*xs)`` on one chip's ``k`` deposited ``(n,)`` arrays or one
        staged planar ``(k, n)`` array (cached like any program): the
        arm of a call some deposit of which does not lie flat on its
        chip. No operand is donated or aliased."""
        key = ("chipfold", 0, "", op, 0, None)
        got = self._programs.get(key)
        if got is None:
            import jax

            def f(*xs):
                return _slot_fold(xs, op)
            got = self._programs[key] = jax.jit(f)
        return got

    def _build(self, name: str, n: int, op: str, root: int, extra=None):
        """``extra`` is the count of operands a chip folds inside the
        program: None for the mesh program over one shard a chip (the
        1:1 channel's, unchanged), ``k`` for the fused one: ``k``
        mesh-sharded flat operands, shard ``j`` of operand ``i`` rank
        ``j * k + i``'s deposit as it lies, and per chip the
        collective's tier dispatcher on the ``k`` of them: the
        streaming ring folds them in its rounds, any other engine gets
        their ``_slot_fold`` (``pallas_ici.ring_folds`` says which).
        One launch where the unfused arm makes one a chip and the
        ring's. 1-D meshes only (``_stage`` asks); no operand is donated
        or aliased."""
        if extra is None:
            return super()._build(name, n, op, root)
        import jax
        from jax.sharding import PartitionSpec as P

        from ..ops import pallas_ici
        from ..parallel.mesh import shard_map
        axis, p = self.axis, self._mesh_extent()
        # the collective's mesh body, as ``DeviceCollChannel._build``
        # has it: k x [p*c] -> [c], or k x [n] -> replicated [n]
        ring, out_specs = (
            (pallas_ici.ici_reduce_scatter, P(axis))
            if name == "reduce_scatter_block"
            else (pallas_ici.ici_all_reduce, P(None)))     # and reduce

        def f(*xs):
            return ring(xs, axis, p, op=op)
        sm = shard_map(f, mesh=self.mesh, in_specs=(P(axis),) * extra,
                       out_specs=out_specs, check_vma=False)
        return jax.jit(sm)

    def _chip_stack(self, j: int, n: int, dtype):
        """Chip ``j``'s k deposited slots as one planar (k, n) array on
        its device (device-resident slots stack in place): a copy of
        the chip's deposits either way, counted for ``_stage``."""
        import jax
        import jax.numpy as jnp
        self._stacked += 1
        sl = self.rv.slots[j * self.k:(j + 1) * self.k]
        dev = self._mesh_devices[j]
        if all(_lies_on(s, dev) for s in sl):
            return jnp.stack([s.reshape(n) for s in sl])
        return jax.device_put(
            np.stack([np.asarray(s).reshape(n) for s in sl]), dev)

    def _fold_chip(self, j: int, n: int, dtype, op: str):
        """What chip ``j`` contributes to level 2. Flat device arrays on
        the chip's device are handed back as they lie, a tuple of the
        chip's ``k`` deposits for the program to fold (no launch, no
        reshape, no eager op); host deposits, shaped arrays and arrays
        committed elsewhere are staged by ``_chip_stack`` and folded
        here, to one [n] array."""
        import jax
        dev = self._mesh_devices[j]
        if self.k == 1:
            s = self.rv.slots[j]
            if _lies_on(s, dev):
                return s.reshape(n)
            return jax.device_put(np.asarray(s).reshape(n), dev)
        sl = self.rv.slots[j * self.k:(j + 1) * self.k]
        if all(_lies_on(s, dev) and s.ndim == 1 for s in sl):
            return tuple(sl)
        return self._fold_prog(op)(self._chip_stack(j, n, dtype))

    def _stage(self, name: str, op: str, root: int) -> Tuple[tuple, tuple]:
        """Level 1 per chip, as far as the host has a hand in it, and
        the mesh program's operands over the chips. Where every chip of
        a reduction handed its ``k`` deposits over as they lie (and the
        mesh is 1-D) level 1 runs inside the mesh program, one launch a
        call (counted: dev_fold_fused), and inside its ring kernel where
        ``pallas_ici.ring_folds`` says so of the tier this call takes
        (counted: dev_fold_in_ring; the program's trace asked the same
        rule); otherwise each chip is folded by its own launch and the
        mesh program takes the folds."""
        import jax

        rv = self.rv
        nd, k = self.ndev, self.k
        n, dtype = self._slot_extent(rv.slots[0])
        shards, prog_root, prog_n, fused, in_ring = [], 0, n, False, False
        # the look at the deposits, and the staging and fold launches of
        # a call that does not fuse, issued from this one thread; the E
        # says how many planar copies it made and whether the fold went
        # into the mesh program, and there into the ring kernel
        with self._phase("dev_chip_fold") as fold:
            self._stacked = 0
            if name == "bcast":
                # only the root chip's shard matters: stage the root
                # rank's payload there, zero-fill the rest (the mesh
                # bcast program overwrites them)
                prog_root = root // k
                for j, dev in enumerate(self._mesh_devices):
                    if j != prog_root:
                        s = jax.device_put(np.zeros(n, dtype), dev)
                    else:
                        s = rv.slots[root]
                        if not _lies_on(s, dev):
                            s = jax.device_put(np.asarray(s).reshape(-1),
                                               dev)
                    shards.append(s)
            elif name == "allgather":
                # chip fold is CONCATENATION: blocked rank->chip mapping
                # makes the stacked chip payload already rank-ordered
                prog_n = k * n
                for j in range(nd):
                    shards.append(self._chip_stack(j, n, dtype)
                                  .reshape(prog_n))
            else:   # allreduce / reduce / reduce_scatter_block
                shards = [self._fold_chip(j, n, dtype, op)
                          for j in range(nd)]
                fused = not self.multi_axis and all(
                    isinstance(c, tuple) and len(c) == k for c in shards)
                if fused:
                    mpit.pvar("dev_fold_fused").inc()
                    from ..ops.pallas_ici import ring_folds
                    in_ring = ring_folds(self._tier, n, dtype, nd)
                    if in_ring:
                        mpit.pvar("dev_fold_in_ring").inc()
                else:   # a launch for every chip still unfolded
                    shards = [self._fold_prog(op)(*c)
                              if isinstance(c, tuple) else c
                              for c in shards]
                if not self._stacked:
                    mpit.pvar("dev_fold_operands").inc()
            if self._stacked:
                mpit.pvar("dev_fold_stacked").inc(self._stacked)
            if fold is not None:
                fold.args.update(k=k, chips=nd, stacked=self._stacked,
                                 fused=fused, in_ring=in_ring)
        if fused:
            # operand i: the chips' i-th deposits, shard j rank
            # j*k + i's array as it lies
            operands = tuple(self._global([c[i] for c in shards], n)
                             for i in range(k))
        else:
            operands = (self._global(shards, prog_n),)
        return ((name, prog_n, str(dtype), op, prog_root,
                 k if fused else None), operands)

    def _hand_out(self, name: str, out) -> List:
        """The chip outputs fanned back to their ranks: every rank
        shares its chip's shard, zero-copy, but for
        reduce_scatter_block, where a chip's shard is its ``k`` ranks'
        contiguous blocks and each rank gets its slice."""
        if name != "reduce_scatter_block":
            return self._per_rank(out)
        k, c = self.k, out.shape[0] // self.size
        with self._phase("dev_collect") as ph:
            if ph is not None:
                ph.args["parts"] = self.size    # one eager slice each
            per_dev = {s.device: s.data for s in out.addressable_shards}
            return [per_dev[self.devices[r]][(r % k) * c:(r % k + 1) * c]
                    for r in range(self.size)]


def _dense_displs(counts) -> List[int]:
    """Dense prefix displacements (the canonical packed layout)."""
    out, off = [], 0
    for c in counts:
        out.append(off)
        off += int(c)
    return out


def _pack_v(sendbuf, scounts, sdispls):
    """This rank's alltoallv sends packed densely in peer order (the
    canonical layout the device kernel's displacement tables assume).
    Device arrays stay on device; dense user layouts are zero-copy."""
    if is_device_array(sendbuf):
        flat = sendbuf.reshape(-1)
        if list(sdispls) == _dense_displs(scounts):
            return flat[:int(sum(scounts))]
        import jax.numpy as jnp
        parts = [flat[sdispls[j]:sdispls[j] + scounts[j]]
                 for j in range(len(scounts)) if scounts[j]]
        return jnp.concatenate(parts) if parts else flat[:0]
    arr = np.asarray(sendbuf).reshape(-1)
    if list(sdispls) == _dense_displs(scounts):
        return np.ascontiguousarray(arr[:int(sum(scounts))])
    parts = [arr[sdispls[j]:sdispls[j] + scounts[j]]
             for j in range(len(scounts)) if scounts[j]]
    return (np.ascontiguousarray(np.concatenate(parts)) if parts
            else arr[:0].copy())


def _as_local(sendbuf, recvbuf, count: int, in_place_start: int = 0):
    """This rank's contribution as a flat [count] array (device or host)
    and whether it is the caller's own array object. MPI_IN_PLACE reads
    from recvbuf; ``in_place_start`` selects the rank's chunk
    (allgather-style in-place semantics).

    A flat device array asked for whole is deposited as it is (counted:
    dev_deposit_as_is). jax's ``reshape(-1)[0:n]`` hands back that same
    object too, after a hundred microseconds of its indexing machinery
    in every rank's slice of the interpreter lock (PERF.md, PR 35)."""
    buf = sendbuf
    start = 0
    if type(sendbuf).__name__ == "_InPlace":
        buf = recvbuf
        start = in_place_start
    if is_device_array(buf):
        if start == 0 and buf.ndim == 1 and count == buf.shape[0]:
            _DEPOSIT_AS_IS.inc()
            return buf, True
        return buf.reshape(-1)[start:start + count], False
    return np.ascontiguousarray(
        np.asarray(buf).reshape(-1)[start:start + count]), False


def _deliver(out, recvbuf):
    """Write the device result into a host recvbuf (host-staged mode) or
    hand the flat device array back (device-resident mode — the comm
    methods return it to the caller; a flat result, which is every mesh
    and fold program's, is handed back as the object it is)."""
    if _device_resident(recvbuf):
        return out if out.ndim == 1 else out.reshape(-1)
    host = np.asarray(out).reshape(-1)
    dst = np.asarray(recvbuf)
    if dst.size == host.size:
        # copyto writes through views, including non-contiguous ones
        # (a flat reshape of a strided view would silently copy)
        np.copyto(dst, host.reshape(dst.shape))
    else:
        if not dst.flags.c_contiguous:
            raise ValueError(
                "device collective: non-contiguous recvbuf larger than "
                "the result is not supported")
        dst.reshape(-1)[:host.size] = host
    return None


# ---------------------------------------------------------------------------
# per-comm install (the init_MV2_collops moment)
# ---------------------------------------------------------------------------

# wrapper name -> cvar prefix (reduce_scatter_block shares the
# REDUCE_SCATTER override and alltoallv the ALLTOALL one, matching the
# MPI-level collective family)
_CVAR_OF = {"allreduce": "ALLREDUCE", "bcast": "BCAST",
            "allgather": "ALLGATHER", "alltoall": "ALLTOALL",
            "alltoallv": "ALLTOALL",
            "reduce": "REDUCE", "reduce_scatter_block": "REDUCE_SCATTER"}


_warned_no_lower: set = set()


def _select_transport(comm, name: str, nbytes: int, op, buf) -> str:
    """'device' or 'host' for this call — step 2 of the tuning order
    (coll/tuning.py docstring). Note: the decision must be identical on
    every rank of the call; all inputs (msg size, op, dtype, env) are
    required-uniform by MPI except buffer residency, which therefore must
    also be uniform across ranks (device arrays everywhere or nowhere).
    The dtype is asked last: a call every other gate sends to the device
    and the dtype alone keeps off it is counted on its way to the host
    arm (``_note_turned_away``)."""
    cfg = get_config()
    forced = cfg.get(f"{_CVAR_OF[name]}_ALGO", "")
    op_ok = op is None or _op_name(op) is not None
    if forced == "device":
        if name not in _warned_no_lower and \
                not (op_ok and _dtype_ok(buf)):
            _warned_no_lower.add(name)  # once per collective, not per call
            log.warn("%s forced to device but op/dtype does not "
                     "lower; using host path", name)
    elif forced or not cfg["USE_DEVICE_COLL"]:
        return "host"          # a named host algorithm wins
    elif not is_device_array(buf) and name != "alltoallv":
        # host buffer: crossover (autotuner-overridable). Resident
        # buffers never stage through the host, and alltoallv has the
        # one size input that is NOT required-uniform: each rank keys
        # on its own sum(scounts), and a zero-count row is legal — a
        # size-gated decision could diverge (one rank host, peers
        # device) and deadlock the rendezvous, so the v-variant always
        # takes the device path once the uniform gates pass
        from .tuning import device_crossover
        if nbytes < device_crossover(name, comm):
            return "host"
    if not op_ok:
        return "host"
    if not _dtype_ok(buf):
        if hasattr(buf, "dtype"):
            _note_turned_away(comm, name, nbytes, buf)
        return "host"
    return "device"


def _note_turned_away(comm, name: str, nbytes: int, buf) -> None:
    """A call the device path would have carried but for its dtype goes
    to the host arm: count it (dev_coll_fallback_host_dtype) and, traced,
    drop the instant ``_note_tier`` drops for an XLA take. Once per
    call: ``_select_transport`` is asked once per call."""
    mpit.pvar("dev_coll_fallback_host_dtype").inc()
    tr = getattr(comm.u.engine, "tracer", None)
    if tr is not None:
        tr.record("channel", "dev_coll_fallback", "i", coll=name,
                  nbytes=int(nbytes), reason="host_dtype",
                  dtype=str(buf.dtype))


def _dtype_ok(buf) -> bool:
    if not hasattr(buf, "dtype"):
        return False
    return _dtype_lowers(np.dtype(buf.dtype))


def install_device_coll(comm, channel: DeviceCollChannel) -> None:
    """Overwrite the device-capable entries of ``comm.coll_fns`` with
    transport-selecting wrappers — the channel's init_MV2_collops moment
    (ch3i_comm.c:27-100). The host entries installed by install_coll_ops
    remain the fallback."""
    from .tuning import install_coll_ops
    if not comm.coll_fns:
        install_coll_ops(comm)
    host = dict(comm.coll_fns)
    comm.device_channel = channel
    channel.ctx = comm.ctx_coll
    sz = comm.size

    # per-coll (bytes-on-the-wire, op-position, recv-count) metadata; the
    # args tuple `a` excludes the leading comm (core/comm.py signatures)
    meta = {
        "allreduce": (lambda a: a[2] * a[3].size, 4, lambda a: a[2]),
        "reduce": (lambda a: a[2] * a[3].size, 4, lambda a: a[2]),
        "bcast": (lambda a: a[1] * a[2].size, None, lambda a: a[1]),
        "allgather": (lambda a: a[2] * a[3].size * sz, None,
                      lambda a: a[2] * sz),
        "alltoall": (lambda a: a[2] * a[3].size * sz, None,
                     lambda a: a[2] * sz),
        "reduce_scatter_block": (lambda a: a[2] * a[3].size * sz, 4,
                                 lambda a: a[2]),
    }

    def wrap(name):
        hostfn = host[name]
        devfn = getattr(channel, name)
        nbytes_of, op_pos, out_count_of = meta[name]

        def entry(comm_, *a):
            buf = a[0]
            if type(buf).__name__ == "_InPlace" and len(a) > 1:
                buf = a[1]   # selection looks at the effective buffer
            op = a[op_pos] if op_pos is not None else None
            if _select_transport(comm_, name, nbytes_of(a), op,
                                 buf) == "device":
                return devfn(comm_, *a)
            # host path selected (forced algo / op or dtype doesn't lower):
            # device-array buffers are staged through the host and the
            # result pushed back to this rank's device
            channel._draft = None   # nothing here for a call plan
            if name == "bcast":
                if not is_device_array(a[0]):
                    return hostfn(comm_, *a)
                import jax
                h = np.asarray(a[0])
                hostfn(comm_, h, *a[1:])
                return jax.device_put(h, channel.device)
            send, recv = a[0], a[1]
            if not (is_device_array(send) or is_device_array(recv)):
                return hostfn(comm_, *a)
            if type(send).__name__ == "_InPlace" and is_device_array(recv):
                raise ValueError("MPI_IN_PLACE with a device recvbuf is "
                                 "not supported on the host transport")
            import jax
            send_h = np.asarray(send) if is_device_array(send) else send
            recv_h = recv
            if recv_h is None or is_device_array(recv_h):
                if name == "reduce" and comm_.rank != a[5]:
                    recv_h = None
                else:
                    recv_h = np.empty((out_count_of(a),),
                                      dtype=np.asarray(send_h).dtype)
            hostfn(comm_, send_h, recv_h, *a[2:])
            if recv_h is None:
                return None
            return jax.device_put(recv_h, channel.device)
        return entry

    for name in meta:
        if name not in channel.SUPPORTED:
            continue    # e.g. alltoall on the fold channel: host path
        comm.coll_fns[name] = wrap(name)

    # alltoallv: its own wrapper — the signature puts recvbuf at a[3]
    # (not a[1]) and the transport decision keys on this rank's send
    # total. Device tier needs the mesh channel (the slot channel keeps
    # its host path: per-peer variable counts have no slot-transpose).
    host_a2av = host.get("alltoallv")
    if host_a2av is not None and channel.mesh is not None \
            and "alltoallv" in channel.SUPPORTED:
        def a2av_entry(comm_, sendbuf, scounts, sdispls, recvbuf,
                       rcounts, rdispls, datatype):
            buf = sendbuf
            if type(buf).__name__ == "_InPlace":
                buf = recvbuf
            nbytes = int(sum(scounts)) * datatype.size
            if type(sendbuf).__name__ != "_InPlace" and \
                    _select_transport(comm_, "alltoallv", nbytes, None,
                                      buf) == "device":
                return channel.alltoallv(
                    comm_, sendbuf, list(scounts),
                    list(sdispls) if sdispls is not None
                    else _dense_displs(scounts),
                    recvbuf, list(rcounts),
                    list(rdispls) if rdispls is not None
                    else _dense_displs(rcounts), datatype)
            if is_device_array(sendbuf) or is_device_array(recvbuf):
                raise ValueError(
                    "alltoallv: device-array buffers need the device "
                    "transport (host algorithm was forced)")
            return host_a2av(comm_, sendbuf, scounts, sdispls, recvbuf,
                             rcounts, rdispls, datatype)
        comm.coll_fns["alltoallv"] = a2av_entry


def bind_derived(parent, new) -> None:
    """``new`` was just derived from ``parent`` (MPI_Comm_dup, _create,
    _create_group, _split: ``core/comm.py``, once the context id is
    agreed), this rank is a member and ``parent`` is device-bound: ask
    its channel for a channel of the new group (``derive``) and, if it
    has one, bind it.
    The ``dev_comm_derive`` span lies around the asking; its E says
    which class was bound (``none``: the communicator keeps the host
    arm, and a device array handed to it is counted) and whether it
    runs on the parent's mesh with the parent's programs."""
    parent_ch = parent.device_channel
    tr = getattr(parent.u.engine, "tracer", None)
    if tr is not None:
        args = {"parent_ctx": parent.ctx_coll, "ctx": new.ctx_coll,
                "size": new.size}
        tr.record("device", "dev_comm_derive", "B", args)
    try:
        ch = parent_ch.derive(
            new.group.translate_ranks(range(new.size), parent.group),
            new.context_id)
        if ch is not None:
            install_device_coll(new, ch)
    finally:
        if tr is not None:
            bound = new.device_channel
            tr.record("device", "dev_comm_derive", "E", {
                **args, "channel": type(bound).__name__ if bound else "none",
                "same_mesh": bound is not None
                and bound._chan_desc() == parent_ch._chan_desc()})


def note_host_comm(comm) -> None:
    """A device array was handed to a collective of a communicator with
    no device channel, in a universe whose world has one: it takes the
    host arm and comes back as numpy. Counted
    (dev_coll_fallback_host_comm) and, traced, noted as
    ``_note_turned_away`` notes a dtype."""
    mpit.pvar("dev_coll_fallback_host_comm").inc()
    tr = getattr(comm.u.engine, "tracer", None)
    if tr is not None:
        tr.record("channel", "dev_coll_fallback", "i", reason="host_comm",
                  ctx=comm.ctx_coll, size=comm.size)


def build_nonblocking_request(comm, name: str, *a):
    """Satellite routing hook for coll/nonblocking.py: i-collectives on
    a device-capable comm ride the device NBC tier; calls the channel
    cannot route (op/dtype/residency/size, or the slot channel) count
    dev_coll_fallback_nbc and take the host schedule. Returns the
    schedule Request or None."""
    channel = getattr(comm, "device_channel", None)
    if channel is None or getattr(comm, "is_inter", False):
        return None
    try:
        req = channel.nonblocking(comm, name, *a)
    except Exception as e:   # noqa: BLE001 — routing must not kill the call
        log.warn("device nonblocking %s routing failed (%r); host "
                 "schedule", name, e)
        req = None
    if req is None:
        mpit.pvar("dev_coll_fallback_nbc").inc()
    return req


def prewarm_persistent(comm, name: str, *a) -> bool:
    """MPI_*_init hook (core/comm.py _coll_init): when a start() of this
    persistent collective would route to the device tier, build its
    program signatures NOW through the exec-cache seam
    (runtime/daemon.py) — a warm daemon cache turns the init into a
    deserialize and every start() into rendezvous + dispatch only."""
    channel = getattr(comm, "device_channel", None)
    if channel is None:
        return False
    try:
        return bool(channel.nonblocking(comm, name, *a, plan=True))
    except Exception as e:   # noqa: BLE001 — warm-up must never fail init
        log.warn("persistent %s pre-warm failed (%r)", name, e)
        return False


# ---------------------------------------------------------------------------
# binding helpers (harness / launcher entry points)
# ---------------------------------------------------------------------------

def bind_universes(universes, mesh=None, axis=None) -> bool:
    """Bind each thread-rank universe's COMM_WORLD to the device mesh —
    called by the in-process harness (run_ranks(device_mesh=...)) and the
    --vpod launcher before rank threads start. Returns False (no-op) when
    the mesh cannot cover the ranks.

    ``axis`` defaults to the mesh's axis names (ALL of them — a
    multi-axis mesh binds the multi-axis torus channel with ranks
    row-major over the flattened device order); pass one name or an
    ordered tuple to span a subset. Geometry selects the channel:

      * ``#devices == n``  -> DeviceCollChannel (1:1, single- or
        multi-axis mesh programs)
      * ``1 < #devices < n`` with ``n % #devices == 0``
                           -> DeviceFoldChannel (leaders-per-chip
        HBM fold, then the mesh program over chips)
      * one device         -> HBMSlotChannel (slot segment)
    """
    import jax

    from ..utils.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    n = len(universes)
    slot_device = None
    fold = False
    if mesh is None:
        from ..parallel.mesh import make_mesh
        devs = jax.devices()
        if len(devs) >= n:
            if isinstance(axis, (tuple, list)) and len(axis) > 1:
                # multi-axis request: near-square factorization of the
                # n ranks over the named axes (mesh_shape_for)
                mesh = make_mesh(None, tuple(axis), devs[:n])
            else:
                one = axis[0] if isinstance(axis, (tuple, list)) else axis
                mesh = make_mesh((n,), (one or "x",), devs[:n])
        elif len(devs) > 1 and n % len(devs) == 0:
            # more ranks than devices, evenly: the two-level fold —
            # ranks co-resident on a chip fold in HBM, chips ride ICI
            fold = True
            mesh = make_mesh((len(devs),), ("x",), devs)
            log.info("%d ranks over %d devices; binding the "
                     "leaders-per-chip fold channel (%d ranks/chip)",
                     n, len(devs), n // len(devs))
        else:
            # indivisible co-residence: the HBM slot-segment channel on
            # the first device (mpirun on one chip; the shm analog)
            slot_device = devs[0]
            log.info("%d ranks > %d devices; binding the HBM "
                     "slot-segment channel on %s", n, len(devs),
                     slot_device)
    if mesh is not None and slot_device is None:
        if axis is None:
            names = tuple(mesh.axis_names)
            axis = names[0] if len(names) == 1 else names
        msize = int(np.prod(list(mesh.shape.values())))
        if msize == 1 and n > 1:
            slot_device = list(np.asarray(mesh.devices).reshape(-1))[0]
        elif not fold and msize != n:
            if 1 < msize < n and n % msize == 0:
                fold = True
            else:
                log.warn("mesh shape %s does not match %d ranks; host "
                         "path only", dict(mesh.shape), n)
                return False
    if slot_device is None:
        # rank r lives on the r-th device of the mesh: make_mesh lays a
        # 1-D mesh of TPU chips in ICI-neighbour order, which need not
        # be the order of jax.devices()
        log.info("mesh %s binds ranks, in order, to devices %s",
                 dict(mesh.shape),
                 [(d.id, getattr(d, "coords", None))
                  for d in mesh.devices.flat])
    rv = _Rendezvous(n, last_first=slot_device is not None)
    for r, u in enumerate(universes):
        if slot_device is not None:
            ch = HBMSlotChannel(slot_device, rv, r, n)
        elif fold:
            ch = DeviceFoldChannel(mesh, axis, rv, r, n)
        else:
            ch = DeviceCollChannel(mesh, axis, rv, r)
        install_device_coll(u.comm_world, ch)
    # arch is known here (jax initialized): pull in the measured tuning
    # profile for this mesh, if one is committed/pointed-to
    from ..autotune import load_default_profile
    load_default_profile()
    return True
