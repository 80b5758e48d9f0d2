"""Per-rank runtime state and in-process multi-rank harness.

The Universe is the analog of the reference's process-group + VC table state
built in MPID_Init (SURVEY §3.1, /root/reference/src/mpid/ch3/src/
mpid_init.c): world rank/size, the channel set, node topology (which ranks
share a node — src/util/procmap/local_proc.c), and context-id allocation.

Two instantiation modes:
  * ``local_universe(n)`` / ``run_ranks`` — every rank is a thread in this
    process wired through a LocalFabric. This is the unit-test harness and
    the analog of running the MPICH suite with all ranks on one node.
  * process mode (mvapich2_tpu.runtime.bootstrap) — one rank per OS process,
    bootstrapped through the KVS (PMI analog) with tcp/shm channels.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Sequence

from ..core.errors import MPIException, MPI_ERR_INTERN
from ..pt2pt.protocol import Pt2ptProtocol
from ..transport.base import Channel
from ..transport.local import LocalChannel, LocalFabric
from ..transport.progress import ProgressEngine
from ..utils.config import get_config
from ..utils.mlog import get_logger

# mask-allocated context ids live HIGH so they can never collide with
# the monotonic _next_ctx ids the specialized paths (intercomm merge,
# spawn bootstrap, ULFM shrink, idup reservations) still mint
CTX_MASK_BASE = 1 << 20


def _lowest_bit(mask) -> int:
    """Index of the lowest set bit across the uint64 word array, -1 if
    none (the MPIR_Find_local_and_external lowest-free-bit scan)."""
    for w in range(len(mask)):
        v = int(mask[w])
        if v:
            return w * 64 + (v & -v).bit_length() - 1
    return -1

log = get_logger("runtime")


class Universe:
    def __init__(self, world_rank: int, world_size: int,
                 node_ids: Optional[Sequence[int]] = None,
                 world_ranks: Optional[Sequence[int]] = None):
        """``world_rank`` is this proc's universe-wide proc id.
        ``world_ranks`` is the proc-id set of MPI_COMM_WORLD — for a
        spawned child world it is range(base, base+n) rather than
        range(world_size) (dynamic processes, runtime/spawn.py).
        ``node_ids`` is indexed by proc id and must cover every proc this
        rank can address (len >= max proc id + 1)."""
        self.world_rank = world_rank
        self.world_size = world_size
        self.world_ranks: List[int] = list(world_ranks) \
            if world_ranks is not None else list(range(world_size))
        self.node_ids: List[int] = list(node_ids) if node_ids is not None \
            else [0] * (max(self.world_ranks, default=0) + 1)
        self.node_name_to_id: Dict[str, int] = {}
        self.parent_intercomm = None      # set on spawned ranks
        self.ports: Dict[int, str] = {}   # open ports (tag -> port name)
        self.engine = ProgressEngine(world_rank)
        self.engine.universe = self   # watchdog/debugger back-reference
        self.protocol: Optional[Pt2ptProtocol] = None
        self._channels: Dict[int, Channel] = {}   # world rank -> channel
        self._default_channel: Optional[Channel] = None
        self.plane_channel = None  # ShmChannel with native data plane
        self.shm_channel = None    # ShmChannel (plane or python ring)
        self.comm_world = None
        self.comm_self = None
        self._next_ctx = 8  # 0/1: world pt2pt/coll, 2/3: self, 4+: spare
        self._ctx_mask = None   # lazily sized (ctx_mask())
        from ..analysis.lockorder import tracked
        self._ctx_lock = tracked(threading.Lock(), "universe._ctx_lock")
        self._ctx_holder = None   # key of the agreement holding the mask
        self._ctx_waiting = set()  # keys of locally-pending agreements
        self.finalized = False
        self.initialized = False
        self.windows: Dict[int, object] = {}      # win_id -> Win (RMA)
        self.failed_ranks: set = set()            # ULFM state (ft/ulfm.py)
        self.comms_by_ctx: Dict[int, object] = {} # even ctx -> Comm (revoke
                                                  # routing + failure unwind)
        self.attrs = {}

    # -- wiring -----------------------------------------------------------
    def set_default_channel(self, ch: Channel) -> None:
        self.engine.add_channel(ch)
        self._default_channel = ch

    def set_channel(self, world_rank: int, ch: Channel) -> None:
        if ch not in self.engine.channels:
            self.engine.add_channel(ch)
        self._channels[world_rank] = ch

    def channel_for(self, dest_world: int) -> Channel:
        ch = self._channels.get(dest_world, self._default_channel)
        if ch is None:
            raise MPIException(MPI_ERR_INTERN,
                               f"no channel for rank {dest_world}")
        return ch

    @property
    def device(self):
        """The device this rank is bound to (its COMM_WORLD's device
        channel: run_ranks(..., device_mesh=...), --vpod), or None. The
        channel of a communicator derived from COMM_WORLD lives on the
        same device."""
        ch = getattr(self.comm_world, "device_channel", None)
        return ch.device if ch is not None else None

    def is_local(self, dest_world: int) -> bool:
        """Same node? Feeds the SMP-path routing decision
        (mpid_send.c:267 analog) and 2-level collective splits."""
        return self.node_ids[dest_world] == self.node_ids[self.world_rank]

    @property
    def my_node(self) -> int:
        return self.node_ids[self.world_rank]

    def local_world_ranks(self) -> List[int]:
        me = self.my_node
        return [r for r in self.world_ranks if self.node_ids[r] == me]

    def extend_procs(self, base: int, node_names: Sequence[str]) -> None:
        """Grow the proc table for dynamically-spawned processes with ids
        ``base..base+len(node_names)-1`` (the analog of connecting a new
        MPIDI_PG and extending the VC table, mpidi_pg.c). Node names map
        through node_name_to_id — populated at bootstrap with the *same*
        name->id table on every rank, so all ranks extend identically and
        node-aware (2-level) collectives stay consistent. Unknown names
        get fresh ids deterministically (same inputs everywhere)."""
        if base > 0:
            self._grow_proc_table(base - 1)
        for i, name in enumerate(node_names):
            pid = base + i
            nid = self._intern_node(name)
            if pid < len(self.node_ids):
                self.node_ids[pid] = nid
            else:
                self.node_ids.append(nid)

    def node_name_of(self, pid: int) -> str:
        """Canonical node name for a proc id — for shipping process
        topology across an intercomm bridge (intercomm_create between
        groups that have never met, e.g. spawn/spaiccreate.c: the
        non-spawning ranks must learn where the spawned procs live).
        Falls back to a deterministic synthetic name for nodes that
        were never named (the bootstrap name table is identical on
        every rank, so the fallback is too)."""
        nid = self.node_ids[pid] if 0 <= pid < len(self.node_ids) else None
        if nid is not None:
            for name, i in self.node_name_to_id.items():
                if i == nid:
                    return name
            return f"__node_{nid}"   # the local_universe/spawn convention
        return f"__proc_{pid}"

    def _grow_proc_table(self, pid: int) -> None:
        """Gap-fill to cover ``pid`` (unique negatives so is_local is
        never wrongly true) — shared by extend_procs and learn_procs so
        the cross-rank identical-tables invariant has ONE formula."""
        while len(self.node_ids) <= pid:
            self.node_ids.append(-1000 - len(self.node_ids))

    def _intern_node(self, name: str) -> int:
        m = self.node_name_to_id
        if name not in m:
            m[name] = max(max(self.node_ids, default=0),
                          max(m.values(), default=0)) + 1
        return m[name]

    def learn_procs(self, pairs) -> None:
        """Extend the proc table with (proc_id, node_name) pairs learned
        from a peer group (the intercomm-create analog of
        extend_procs). Idempotent; same inputs give the same table on
        every rank."""
        for pid, name in pairs:
            self._grow_proc_table(pid)
            if name not in self.node_name_to_id \
                    and name.startswith("__node_") \
                    and name[7:].lstrip("-").isdigit():
                # synthetic id-carrying name (node_name_of fallback;
                # ids agree across ranks). A user-chosen name that
                # merely LOOKS like one but has a non-numeric suffix
                # falls through to normal interning.
                self.node_ids[pid] = int(name[7:])
                continue
            self.node_ids[pid] = self._intern_node(name)

    def num_nodes(self) -> int:
        return len(set(self.node_ids))

    # -- init / finalize --------------------------------------------------
    def initialize(self) -> None:
        from ..core.comm import Comm
        from ..core.group import Group
        from ..utils import timestamps as ts
        with ts.phase("MPID_Init"):
            with ts.phase("config reload"):
                get_config().reload()
            with ts.phase("trace attach"):
                # after the reload so MV2T_TRACE*/MV2T_STALL_* set in the
                # launcher env are honored; both are no-ops when off
                from .. import trace
                trace.maybe_attach(self.engine)
                trace.watchdog.configure(self.engine)
                from ..analysis import lockorder
                lockorder.configure(self.engine)
                # arm the continuous-telemetry gate (MV2T_METRICS,
                # default on): latency histograms record from here on;
                # the shm sampler attaches with the channel
                from .. import metrics as metrics_mod
                metrics_mod.ensure_live()
            with ts.phase("failure containment"):
                # fault-injection engine (MV2T_FAULTS; no-op when unset)
                # and the liveness probe: blocking waits check co-located
                # peers' heartbeat leases so a dead peer unwinds the wait
                # with MPIX_ERR_PROC_FAILED instead of hanging it
                from .. import faults as faults_mod
                faults_mod.configure(self.world_rank)
                sch = self.shm_channel
                if sch is not None \
                        and getattr(sch, "_peer_timeout", 0) > 0:
                    self.engine.register_liveness(sch.check_peer_leases)
            with ts.phase("protocol + matcher"):
                self.protocol = Pt2ptProtocol(self)
                from ..ft import ulfm
                ulfm.install(self)
            with ts.phase("comm_world/self"):
                self.comm_world = Comm(self, Group(self.world_ranks),
                                       context_id=0, name="MPI_COMM_WORLD")
                self.comm_self = Comm(self, Group([self.world_rank]),
                                      context_id=2, name="MPI_COMM_SELF")
        self.initialized = True

    def ctx_mask(self):
        """Per-rank context-id availability bitmask — the reference's
        MPIR_Get_contextid scheme (mpir_context_id.h: 2048-wide mask,
        collectively ANDed so the chosen id is free at EVERY member).
        Freed ids return to the mask (Comm.free), so dup/free loops
        never exhaust. The default budget is 2048 simultaneous comms:
        the top eighth is reserved for single-member allocations
        (alloc_context_local) and the rest feeds the collective
        agreement. Floor of 128 bits so both regions always exist.

        Double-checked locking under _ctx_lock: two threads racing the
        lazy init could otherwise both build all-ones masks, and the
        later assignment would resurrect a context-id bit the earlier
        winner had already claimed (a duplicated live context id)."""
        if self._ctx_mask is None:
            import numpy as np
            from ..utils.config import get_config
            nbits = max(128, int(get_config()["MAX_CONTEXTS"]))
            fresh = np.full((nbits + 63) // 64,
                            np.uint64(0xFFFFFFFFFFFFFFFF),
                            dtype=np.uint64)
            with self._ctx_lock:
                if self._ctx_mask is None:
                    self._ctx_mask = fresh
        return self._ctx_mask

    def release_context_id(self, ctx: int) -> None:
        if ctx < CTX_MASK_BASE or self._ctx_mask is None:
            return   # predefined / legacy monotonic id: not pooled
        import numpy as np
        bit = (ctx - CTX_MASK_BASE) // 2
        w, b = divmod(bit, 64)
        if w < len(self._ctx_mask):
            # under the lock: an unlocked OR would race ctx_resolve's
            # AND in the same word and lose one of the two updates
            with self._ctx_lock:
                self._ctx_mask[w] |= np.uint64(1 << b)

    def _ctx_local_words(self) -> int:
        """Words at the TOP of the mask reserved for single-member
        allocations (alloc_context_local). Collective agreements
        advertise these bits as unavailable (ctx_payload zeroes them),
        so a self-comm allocated mid-agreement can never collide with
        the id the in-flight agreement settles on — the snapshot the
        holder sent is stale the moment another thread claims. Always
        at least one word on each side (ctx_mask floors at 128 bits)."""
        return min(max(1, len(self.ctx_mask()) // 8),
                   len(self.ctx_mask()) - 1)

    def ctx_payload(self, key):
        """One agreement attempt's contribution: mask words + a guard
        word, under the MPIR_Get_contextid thread protocol
        (mpir_context_id.c): at most one thread per process owns the
        live mask during an agreement; a contending thread contributes
        an EMPTY mask and a ZERO guard. BAND semantics then make every
        member see an empty agreed mask with guard 0 — the collective
        "retry together" verdict — while guard all-ones with an empty
        mask is genuine exhaustion.

        ``key`` = (parent context id, tag) orders contenders: the mask
        goes to the LOWEST locally-pending key. Keys are globally
        consistent (the same comm has the same context id everywhere),
        so every process eventually grants the mask to the same
        agreement and that one completes — the deadlock-avoidance rule
        of the reference's protocol (threads/comm/comm_dup_deadlock.c
        livelocks without it). Returns (payload, owns_mask)."""
        import numpy as np
        mask = self.ctx_mask()
        pay = np.empty(len(mask) + 1, dtype=np.uint64)
        with self._ctx_lock:
            self._ctx_waiting.add(key)
            if self._ctx_holder is not None \
                    or key != min(self._ctx_waiting):
                pay[:] = 0
                return pay, False
            self._ctx_holder = key
            # snapshot under the lock; the reserved local-only words
            # are advertised unavailable (see _ctx_local_words)
            pay[:len(mask)] = mask
            pay[len(mask) - self._ctx_local_words():len(mask)] = 0
        pay[len(mask)] = np.uint64(0xFFFFFFFFFFFFFFFF)
        return pay, True

    def ctx_release(self, own: bool, key, done: bool = False) -> None:
        """Drop the mask-holder flag after a FAILED agreement attempt;
        ``done`` additionally retires the key (success or exception —
        a retry keeps its place in the priority queue). Without the
        release, an exception between ctx_payload and ctx_resolve
        would leave the holder stuck and wedge every later agreement
        in this process."""
        with self._ctx_lock:
            if own:
                self._ctx_holder = None
            if done:
                self._ctx_waiting.discard(key)

    def ctx_resolve(self, agreed, own: bool, key,
                    claim: bool = True) -> int:
        """Resolve an AGREED [mask..., guard] payload to a context id.
        Returns -1 when some process's mask was thread-held (the whole
        collective retries together — the verdict is a pure function of
        the agreed payload, so every member reaches it identically);
        raises on true exhaustion (errors/comm/too_many_comms.c expects
        the error on all ranks); ``claim`` clears the bit in this
        rank's own mask (non-members of a split skip the claim)."""
        import numpy as np
        bit = _lowest_bit(agreed[:-1])
        with self._ctx_lock:
            if own:
                self._ctx_holder = None
            if bit >= 0:
                self._ctx_waiting.discard(key)
                if claim:
                    w, b = divmod(bit, 64)
                    self._ctx_mask[w] &= np.uint64(~np.uint64(1 << b))
                return CTX_MASK_BASE + 2 * bit
        if int(agreed[-1]) == 0:
            return -1
        self.ctx_release(False, key, done=True)
        from ..core.errors import MPIException, MPI_ERR_OTHER
        nw = len(agreed) - 1
        raise MPIException(
            MPI_ERR_OTHER,
            "out of collective context ids "
            f"({(nw - self._ctx_local_words()) * 64} of "
            f"MV2T_MAX_CONTEXTS={nw * 64}; the rest are reserved "
            "single-member)")

    def alloc_context_local(self) -> int:
        """Single-member agreement (COMM_SELF dups, size-1 splits and
        groups): no collective and no mask-holder — claim the lowest
        local free bit under the lock. Bypassing the shared-mask hold
        is load-bearing: threads/comm/comm_dup_deadlock.c's self-dups
        must complete while another thread's world-scoped agreement is
        blocked mid-collective, or the two ranks' threads deadlock
        through each other's holders."""
        import numpy as np
        import time
        mask = self.ctx_mask()
        lw = self._ctx_local_words()
        base = len(mask) - lw
        # bounded wait-out: an agreement that never resolves (a wedged
        # peer, a lost mask-holder) must surface as a diagnostic error,
        # not a silent livelock on the 0.2 ms poll
        deadline = time.monotonic() + 60.0
        while True:
            with self._ctx_lock:
                # the reserved top words first: collective agreements
                # never advertise these bits, so claiming here cannot
                # collide with an in-flight agreement's stale snapshot
                bit = _lowest_bit(mask[base:])
                if bit >= 0:
                    bit += base * 64
                elif self._ctx_holder is None:
                    # reserved region exhausted: the shared region is
                    # safe too while NO agreement is in flight — any
                    # future snapshot is taken after this claim lands
                    bit = _lowest_bit(mask[:base])
                    if bit < 0:
                        from ..core.errors import (MPIException,
                                                   MPI_ERR_OTHER)
                        raise MPIException(
                            MPI_ERR_OTHER,
                            "out of context ids (MV2T_MAX_CONTEXTS="
                            f"{len(mask) * 64}, {lw * 64} reserved "
                            "single-member)")
                else:
                    bit = -1    # wait out the in-flight agreement
                if bit >= 0:
                    w, b = divmod(bit, 64)
                    self._ctx_mask[w] &= np.uint64(~np.uint64(1 << b))
                    return CTX_MASK_BASE + 2 * bit
            if time.monotonic() > deadline:
                raise MPIException(
                    MPI_ERR_INTERN,
                    "alloc_context_local stalled 60s waiting out an "
                    "in-flight context-id agreement (reserved region "
                    "exhausted and the shared mask never came free) — "
                    "a peer is likely wedged mid-agreement")
            time.sleep(0.0002)

    def allocate_context_id(self, parent_comm) -> int:
        """Collective over parent_comm: agree on a fresh context id —
        allreduce-BAND of the members' availability masks, lowest common
        free bit wins (the reference's MPIR_Get_contextid protocol).
        Plane-owned comms run the agreement as ONE C-engine gather
        (cp_coll_gather) and AND the columns locally."""
        import numpy as np
        import time
        from ..coll import algorithms as alg
        from ..core import op as opmod
        if getattr(parent_comm, "size", 0) == 1 \
                and not getattr(parent_comm, "is_inter", False):
            return self.alloc_context_local()
        key = (parent_comm.context_id, 0)
        while True:
            pay, own = self.ctx_payload(key)
            try:
                gather = getattr(parent_comm, "_plane_gather", None)
                table = gather(pay) if gather is not None else None
                if table is not None:
                    agreed = np.bitwise_and.reduce(
                        table.view(np.uint64)
                        .reshape(parent_comm.size, -1), axis=0)
                else:
                    # fixed base algorithm, NOT the tunable dispatch: a
                    # forced two-level algorithm would re-enter
                    # build_2level -> split -> allocate_context_id here
                    # (the reference likewise runs the context-id
                    # protocol on its own reserved path,
                    # MPIR_Get_contextid)
                    agreed = alg.allreduce_recursive_doubling(
                        parent_comm, pay, opmod.BAND,
                        parent_comm.next_coll_tag())
            except BaseException:
                self.ctx_release(own, key, done=True)
                raise
            ctx = self.ctx_resolve(agreed, own, key)
            if ctx >= 0:
                return ctx
            time.sleep(0.0002)   # let the mask-holding thread finish

    def mark_failed(self, world_rank: int) -> None:
        """Record a process failure (detection sink — SURVEY §5.3)."""
        from ..ft import ulfm
        ulfm.mark_failed(self, world_rank)

    def finalize(self) -> None:
        if self.finalized:
            return
        leftover = self.engine.drain_all()
        if leftover:
            log.info("finalize retired %d leftover packets/hook advances "
                     "(rank %d)", leftover, self.world_rank)
        from .. import trace
        trace.dump_rank(self.engine)
        trace.detach(self.engine)
        self.engine.close()
        self.finalized = True


# ---------------------------------------------------------------------------
# current-universe plumbing (thread-local first, then process-global)
# ---------------------------------------------------------------------------

_tls = threading.local()
_process_universe: Optional[Universe] = None


def set_universe(u: Optional[Universe], process_wide: bool = False) -> None:
    global _process_universe
    if process_wide:
        _process_universe = u
    else:
        _tls.universe = u


def current_universe() -> Optional[Universe]:
    u = getattr(_tls, "universe", None)
    return u if u is not None else _process_universe


# ---------------------------------------------------------------------------
# in-process harness
# ---------------------------------------------------------------------------

def local_universe(nranks: int, nodes: Optional[Sequence[int]] = None,
                   device_mesh=None) -> List[Universe]:
    """Build ``nranks`` thread-rank universes over one LocalFabric.

    ``nodes`` optionally assigns a fake node id per rank so node-aware
    (2-level) paths can be exercised without multiple hosts.
    ``device_mesh``: True binds each rank's COMM_WORLD to a device of a
    1-D jax mesh over the visible devices (the ICI collective channel,
    coll/device.py); pass a Mesh to bind to it explicitly."""
    fabric = LocalFabric(nranks)
    universes = []
    for r in range(nranks):
        u = Universe(r, nranks, nodes)
        # synthetic node-name table (spawn extends proc tables through it;
        # every rank must hold the same map — see extend_procs)
        u.node_name_to_id = {f"__node_{v}": v for v in sorted(set(u.node_ids))}
        u.set_default_channel(LocalChannel(fabric, r))
        fabric.register(r, u.engine)
        universes.append(u)
    for u in universes:
        u.initialize()
    if device_mesh is not None and device_mesh is not False:
        from ..coll.device import bind_universes
        mesh = None if device_mesh is True else device_mesh
        bind_universes(universes, mesh)
    return universes


def run_ranks(nranks: int, fn: Callable, *args,
              nodes: Optional[Sequence[int]] = None,
              timeout: float = 120.0, device_mesh=None) -> List:
    """Run ``fn(comm_world, *args)`` on every rank (threads); return the
    per-rank results. Any rank's exception is re-raised with its rank noted.
    This is the in-process testing harness for the MPICH-style corpus."""
    universes = local_universe(nranks, nodes, device_mesh=device_mesh)
    results: List = [None] * nranks
    errors: List = [None] * nranks

    def body(r: int):
        set_universe(universes[r])
        try:
            results[r] = fn(universes[r].comm_world, *args)
        except BaseException as e:  # noqa: BLE001
            errors[r] = e
            # wake peers stuck waiting on us
            ch = getattr(universes[r].comm_world, "device_channel", None)
            if ch is not None:
                # break the device-collective rendezvous, and those of
                # the derived communicators this rank is a member of
                ch.abort()
            for u in universes:
                u.engine.wakeup()
        finally:
            set_universe(None)

    threads = [threading.Thread(target=body, args=(r,), daemon=True,
                                name=f"rank-{r}")
               for r in range(nranks)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
        if t.is_alive():
            raise TimeoutError(
                f"rank thread {t.name} did not finish within {timeout}s "
                f"(errors so far: {[e for e in errors if e]})")
    for u in universes:
        u.finalize()
    for r, e in enumerate(errors):
        if e is not None:
            raise RuntimeError(f"rank {r} failed: {e!r}") from e
    return results
