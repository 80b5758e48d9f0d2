"""MPI_T tools-information interface: cvars, pvars, categories.

Analog of the reference's src/mpi_t/ (SURVEY §5.5 — cvar_read.c,
pvar_session_create.c; 14.7k LoC) plus the MV2 channel counters in
src/mpi_t/mv2_mpit.c:17-39 and the per-algorithm collective timers
(allreduce_osu.c:35-50).

Redesign: the cvar surface is a thin indexed view over utils.config's
declarative registry (one declaration serves env parsing, enumeration and
MPI_T, collapsing the reference's three cooperating layers). Pvars live in
a process-global registry; counters are either owned (incremented by
instrumented code) or sourced (a callable sampled at read time, e.g. a
progress engine's poll count). Sessions follow MPI_T semantics: a handle
bound in a session accumulates from its start value, so concurrent tools
don't perturb each other.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from .utils.config import CVar, cvar, get_config

# MPI_T verbosity / scope / binding constants (subset)
VERBOSITY_USER_BASIC = 221
VERBOSITY_TUNER_BASIC = 333
SCOPE_LOCAL = 0
SCOPE_ALL = 1
PVAR_CLASS_COUNTER = 0
PVAR_CLASS_TIMER = 1
PVAR_CLASS_LEVEL = 2
PVAR_CLASS_HIGHWATERMARK = 3
PVAR_CLASS_HISTOGRAM = 4


# ---------------------------------------------------------------------------
# cvar surface (indexed view of the config registry)
# ---------------------------------------------------------------------------

def _cvar_list() -> List[CVar]:
    return [get_config().cvars()[k] for k in sorted(get_config().cvars())]


def cvar_get_num() -> int:
    return len(_cvar_list())


def cvar_get_index(name: str) -> int:
    for i, cv in enumerate(_cvar_list()):
        if cv.name == name:
            return i
    raise KeyError(name)


def cvar_get_info(index: int) -> Dict[str, Any]:
    cv = _cvar_list()[index]
    return {"name": cv.name, "type": cv.typ.__name__, "default": cv.default,
            "category": cv.group, "desc": cv.desc,
            "env": cv.env_name, "scope": SCOPE_LOCAL,
            "verbosity": VERBOSITY_USER_BASIC}


def cvar_read(index: int) -> Any:
    return _cvar_list()[index].value


def cvar_write(index: int, value: Any) -> None:
    _cvar_list()[index].set_value(value)


# ---------------------------------------------------------------------------
# pvars
# ---------------------------------------------------------------------------

class PVar:
    """One performance variable. Owned pvars are incremented by the
    instrumented code path; sourced pvars sample ``source()`` at read."""

    def __init__(self, name: str, klass: int, group: str, desc: str,
                 source: Optional[Callable[[], float]] = None):
        self.name = name
        self.klass = klass
        self.group = group
        self.desc = desc
        self.source = source
        self._value = 0.0
        self._lock = threading.Lock()

    # -- instrumentation API ---------------------------------------------
    def inc(self, n: float = 1.0) -> None:
        # acquire/release spelled out: the hot call of every counted
        # site, and ``with`` on a Lock costs about as much again as the
        # rest of the call (tests/progs/trace_overhead_prog.py)
        lock = self._lock
        lock.acquire()
        try:
            self._value += n
        finally:
            lock.release()

    def mark(self, v: float) -> None:
        """High-watermark update."""
        with self._lock:
            if v > self._value:
                self._value = v

    def add_time(self, dt: float) -> None:
        self.inc(dt)

    class _Timer:
        def __init__(self, pv: "PVar"):
            self.pv = pv

        def __enter__(self):
            self.t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            self.pv.add_time(time.perf_counter() - self.t0)
            return False

    def timing(self) -> "PVar._Timer":
        return PVar._Timer(self)

    # -- read ------------------------------------------------------------
    def read(self) -> float:
        if self.source is not None:
            return float(self.source())
        with self._lock:
            return self._value

    def reset(self) -> None:
        if self.source is None:
            with self._lock:
                self._value = 0.0


HIST_BUCKETS = 32    # == MV2T_MET_HIST_BUCKETS (metrics shm mirror)


def hist_bucket_index(v: int) -> int:
    """Log2 bucket of a non-negative integer value: bucket 0 holds 0,
    bucket i >= 1 holds [2**(i-1), 2**i - 1] — every power of two is
    exactly a bucket's inclusive LOWER edge, so bucket boundaries are
    value-exact (tested). Values past the last edge saturate into the
    final bucket."""
    i = v.bit_length() if v > 0 else 0
    return i if i < HIST_BUCKETS else HIST_BUCKETS - 1


def hist_bucket_lo(i: int) -> int:
    """Inclusive lower edge of bucket ``i`` (0 for the zero bucket)."""
    return 0 if i <= 0 else 1 << (i - 1)


class HistPVar(PVar):
    """PVAR_CLASS_HISTOGRAM: a log2-bucketed value distribution —
    latency in integer microseconds by convention. ``rec`` is the
    hot-path entry point: no lock, no allocation — one bit_length and
    three integer bumps into preallocated storage. Concurrent
    recorders may lose an increment in the GIL's read-modify-write
    window; this is a stat surface with the same tolerance as the
    fpctr shm mirror. Quantiles/merges over the bucket lists live in
    metrics/hist.py (this module stays on the stdlib light-boot
    path)."""

    def __init__(self, name: str, klass: int, group: str, desc: str,
                 source: Optional[Callable[[], float]] = None):
        super().__init__(name, klass, group, desc, source)
        self.buckets = [0] * HIST_BUCKETS
        self.count = 0
        self.sum = 0

    def rec(self, v: int) -> None:
        if v > 0:
            i = v.bit_length()
            self.buckets[i if i < HIST_BUCKETS else HIST_BUCKETS - 1] += 1
            self.sum += v
        else:
            self.buckets[0] += 1
        self.count += 1

    def snapshot(self) -> tuple:
        """(count, sum, buckets-copy) — consistent enough for the stat
        surface (single GIL-held list copy)."""
        return self.count, self.sum, list(self.buckets)

    def read(self) -> float:
        if self.source is not None:
            return float(self.source())
        return float(self.count)

    def reset(self) -> None:
        b = self.buckets
        for i in range(HIST_BUCKETS):
            b[i] = 0
        self.count = 0
        self.sum = 0


class _PvarRegistry:
    def __init__(self):
        self._vars: Dict[str, PVar] = {}
        self._lock = threading.Lock()

    def declare(self, name: str, klass: int, group: str, desc: str,
                source: Optional[Callable[[], float]] = None) -> PVar:
        with self._lock:
            pv = self._vars.get(name)
            if pv is None:
                cls = HistPVar if klass == PVAR_CLASS_HISTOGRAM else PVar
                pv = cls(name, klass, group, desc, source)
                self._vars[name] = pv
            elif source is not None:
                pv.source = source   # rebind live source (fresh universe)
            return pv

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._vars)

    def get(self, name: str) -> PVar:
        return self._vars[name]


_pvars = _PvarRegistry()


def pvar(name: str, klass: int = PVAR_CLASS_COUNTER, group: str = "general",
         desc: str = "", source: Optional[Callable[[], float]] = None) -> PVar:
    """Declare (or fetch) a pvar — instrumentation-side entry point."""
    return _pvars.declare(name, klass, group, desc, source)


def pvar_get_num() -> int:
    return len(_pvars.names())


def pvar_get_info(index: int) -> Dict[str, Any]:
    pv = _pvars.get(_pvars.names()[index])
    return {"name": pv.name, "class": pv.klass, "category": pv.group,
            "desc": pv.desc, "continuous": pv.source is not None}


def pvar_get_index(name: str) -> int:
    return _pvars.names().index(name)


class PvarSession:
    """MPI_T pvar session: handles accumulate relative to their start."""

    def __init__(self):
        self._handles: Dict[int, tuple] = {}   # handle -> (pvar, base)
        self._next = 1

    def handle_alloc(self, name_or_index) -> int:
        name = name_or_index if isinstance(name_or_index, str) \
            else _pvars.names()[name_or_index]
        pv = _pvars.get(name)
        h = self._next
        self._next += 1
        self._handles[h] = (pv, 0.0)
        return h

    def start(self, handle: int) -> None:
        pv, _ = self._handles[handle]
        self._handles[handle] = (pv, pv.read())

    def read(self, handle: int) -> float:
        """Counters/timers read relative to session start; watermark and
        level pvars are instantaneous — a delta would be meaningless."""
        pv, base = self._handles[handle]
        if pv.klass in (PVAR_CLASS_HIGHWATERMARK, PVAR_CLASS_LEVEL):
            return pv.read()
        return pv.read() - base

    def reset(self, handle: int) -> None:
        self.start(handle)

    def handle_free(self, handle: int) -> None:
        self._handles.pop(handle, None)


def pvar_session_create() -> PvarSession:
    return PvarSession()


# ---------------------------------------------------------------------------
# categories
# ---------------------------------------------------------------------------

def category_get_num() -> int:
    return len(category_names())


def category_names() -> List[str]:
    groups = {cv.group for cv in _cvar_list()}
    groups.update(pv_group for pv_group in
                  (_pvars.get(n).group for n in _pvars.names()))
    return sorted(groups)


def category_get_info(index: int) -> Dict[str, Any]:
    name = category_names()[index]
    cvars = [cv.name for cv in _cvar_list() if cv.group == name]
    pvars = [n for n in _pvars.names() if _pvars.get(n).group == name]
    return {"name": name, "num_cvars": len(cvars), "num_pvars": len(pvars),
            "cvars": cvars, "pvars": pvars}


def dump() -> str:
    """Tool-style dump of every pvar's current value."""
    lines = []
    for n in _pvars.names():
        pv = _pvars.get(n)
        lines.append(f"{pv.name:<44} = {pv.read():<14g} [{pv.group}]")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# analysis knobs (mv2t-analyze). Declared HERE — not next to their code —
# so the MPI_T surface carries the checker's observability even before
# mvapich2_tpu.analysis is imported (the lockorder module fetches the
# already-declared pvars on first use).
# ---------------------------------------------------------------------------

cvar("LOCKCHECK", False, bool, "analysis",
     "Enable the runtime lock-order detector (analysis/lockorder.py): "
     "instrumented locks record a per-process acquisition-order graph; "
     "cycles (potential deadlocks) and locks held across progress_wait "
     "are reported through the stall-watchdog dump path. Zero overhead "
     "when off (lock creation sites return the raw lock).")


def _lint_baseline_count() -> float:
    """Committed mv2tlint suppression count — the ratchet position."""
    try:
        from .analysis.core import load_baseline
        return float(len(load_baseline().entries))
    except Exception:   # tools must never break the registry
        return -1.0


pvar("lint_findings_baseline", PVAR_CLASS_LEVEL, "analysis",
     "mv2tlint findings suppressed by the committed baseline "
     "(analysis/baseline.json); --strict only lets this shrink",
     source=_lint_baseline_count)
pvar("lockcheck_edges", PVAR_CLASS_COUNTER, "analysis",
     "distinct lock-acquisition-order edges observed by the "
     "MV2T_LOCKCHECK monitor")
pvar("lockcheck_cycles", PVAR_CLASS_COUNTER, "analysis",
     "distinct lock-order cycles (potential deadlocks) reported by the "
     "MV2T_LOCKCHECK monitor")

# ---------------------------------------------------------------------------
# failure-containment observability (mvapich2_tpu/faults + ft/ulfm).
# Predeclared so tools enumerate them before the datapath imports; the
# owning modules fetch the same instances by name.
# ---------------------------------------------------------------------------
pvar("faults_injected", PVAR_CLASS_COUNTER, "ft",
     "faults fired by the MV2T_FAULTS deterministic injection engine "
     "(python-side sites; the native flat_fold site counts via "
     "fp_dead_peer-adjacent plane counters)")
pvar("dead_peer_detections", PVAR_CLASS_COUNTER, "ft",
     "peers declared dead by liveness-lease expiry (python probe + "
     "reconciled C-plane scans)")
pvar("wait_deadline_trips", PVAR_CLASS_COUNTER, "ft",
     "blocking waits unwound by a lease deadline instead of completing")
pvar("revokes_propagated", PVAR_CLASS_COUNTER, "ft",
     "REVOKE floods sent by this rank (initiations + re-floods on "
     "first receipt, ft/ulfm.py)")
pvar("arena_reclaimed_dead", PVAR_CLASS_COUNTER, "shm",
     "arena blocks/segments reclaimed from dead ranks (failure sweep, "
     "Finalize leak-check tolerance, stale-segment sweep)")

# ---------------------------------------------------------------------------
# device-collective engine knobs + fallback observability (ops/pallas_ici,
# ops/pallas_ring, coll/device). Declared HERE so the MPI_T surface
# enumerates the device lane before any jax/ops import happens — the same
# early-declaration contract as the analysis knobs above; the kernel
# modules fetch the already-declared entries by name.
# ---------------------------------------------------------------------------

cvar("ICI_CHUNK_BYTES", 256 * 1024, int, "device",
     "VMEM chunk size (bytes) of the HBM-streaming ICI ring kernels: "
     "each chunk is double-buffered through VMEM scratch while the "
     "remote DMA of the next chunk is in flight. A measured tuning "
     "profile (kernel_params.ici_chunk_bytes) overrides this default; "
     "bin/measure_crossover --device re-derives it.")
cvar("ICI_PIPELINE_DEPTH", 2, int, "device",
     "VMEM slots per ring direction in the HBM-streaming kernels "
     "(2 = classic double buffering). Each slot is one in-flight chunk; "
     "the credit handshake bounds a sender to this many chunks ahead.")
cvar("ICI_BIDIR", True, bool, "device",
     "Drive both ring directions of the mesh axis at once (half of "
     "every block clockwise, half counter-clockwise) when the axis has "
     "more than 2 shards — full bisection bandwidth on a physical ring.")
cvar("ICI_INTERPRET", False, bool, "device",
     "Force the pallas ICI kernels through the Mosaic interpreter so "
     "the device tiers run on a CPU mesh (correctness sweeps, CI). "
     "Off-TPU with this unset, device collectives take the XLA "
     "lowering and count dev_coll_fallback_platform.")
cvar("QUANT_COLL", "", str, "device",
     "Accuracy budget opening the block-scaled quantized device-"
     "allreduce tier (ops/pallas_quant): '' = off (exact kernels "
     "only); '<budget>' = int8 wire with that max relative-error "
     "budget (e.g. '1e-2'); '<wire>:<budget>' selects the wire format "
     "(q8 | fp8). Integer dtypes, non-sum ops, budget 0 and budgets "
     "below the declared per-ring bound all keep the exact hbm tier — "
     "the quantized path never runs outside its error contract.")
cvar("QUANT_BLOCK", 512, int, "device",
     "Quantization block size (bytes of the unquantized dtype) of the "
     "quantized wire format: each block travels as one f32 absmax "
     "scale word plus packed int8/fp8 codes, so larger blocks shrink "
     "the wire further but share one scale across more elements. A "
     "measured profile (kernel_params.quant_block_bytes) overrides "
     "this default.")

pvar("dev_coll_fallback_size", PVAR_CLASS_COUNTER, "device",
     "device collectives routed to the XLA lowering because the shard "
     "was past the measured XLA crossover (DEV_TIER_XLA_MIN) — the "
     "once-silent VMEM-cap cliff, now counted")
pvar("dev_coll_fallback_dtype", PVAR_CLASS_COUNTER, "device",
     "device collectives routed to the XLA lowering because the "
     "op/dtype does not lower to the ring kernels")
pvar("dev_coll_fallback_host_dtype", PVAR_CLASS_COUNTER, "device",
     "collective calls on a device-bound comm that every other gate "
     "sent to the device path and the buffer's dtype alone kept off it "
     "(64-bit without jax x64, complex, bool): they took the "
     "host arm, device buffers staged through the host "
     "(coll/device.py _select_transport). The transport-level sibling "
     "of dev_coll_fallback_dtype, which counts XLA takes at the kernel "
     "tier")
pvar("dev_coll_fallback_host_comm", PVAR_CLASS_COUNTER, "device",
     "collective calls, per rank, that handed a device array to a "
     "communicator with no device channel in a device-bound universe "
     "and took the host arm, the array read back and a numpy result "
     "returned (core/comm.py _stage_if_unbound): a proper sub-group "
     "of a 1:1 mesh or fold communicator, MPIX_Comm_shrink's, and a "
     "communicator split by build_2level. A communicator derived "
     "from a device-bound one is otherwise bound (coll/device.py "
     "bind_derived) and counts dev_coll_derived")
pvar("dev_coll_fallback_shape", PVAR_CLASS_COUNTER, "device",
     "device collectives routed to the XLA lowering because of a "
     "degenerate buffer extent")
pvar("dev_coll_fallback_platform", PVAR_CLASS_COUNTER, "device",
     "device collectives routed to the XLA lowering because the pallas "
     "kernels cannot run here (no pallas, or off-TPU without "
     "MV2T_ICI_INTERPRET)")
pvar("dev_coll_tier_vmem", PVAR_CLASS_COUNTER, "device",
     "device collective calls whose program holds the VMEM-resident "
     "flat ring (ops/pallas_ring): a sum or a gather on a 1-D mesh at "
     "or under DEV_TIER_VMEM_MAX. Counted by the one tier rule "
     "(ops/pallas_ici.planned_tier), which the program's lowering "
     "asks too: the tier counted is the kernel that runs")
pvar("dev_coll_tier_hbm", PVAR_CLASS_COUNTER, "device",
     "device collective calls whose program holds the HBM-streaming "
     "chunked engine (ops/pallas_ici, ops/pallas_alltoall): every "
     "size between the VMEM edge and the XLA crossover and, by the "
     "same rule (ops/pallas_ici.planned_tier), what the flat ring "
     "cannot carry under the edge: max / min / prod, a reduce-scatter, "
     "an alltoall, a ring along one axis of a multi-axis mesh")
pvar("dev_coll_tier_quant", PVAR_CLASS_COUNTER, "device",
     "device collective calls served by the block-scaled quantized "
     "wire tier (ops/pallas_quant, gated by MV2T_QUANT_COLL)")
pvar("dev_coll_quant_bytes_saved", PVAR_CLASS_COUNTER, "device",
     "bytes kept off the ICI wire by the quantized tier: exact-wire "
     "minus quantized-wire accounting (ops/pallas_quant.wire_stats) "
     "summed per dispatched call at the collective wrapper")
pvar("dev_a2a_wire_bytes", PVAR_CLASS_COUNTER, "device",
     "bytes the pairwise alltoall kernel (ops/pallas_alltoall) sends "
     "over ICI, per rank, summed over the calls it served: tile padding "
     "included, the local block excluded, as the kernel module reckons "
     "them (alltoall_wire_bytes; counted per call in coll/device.py "
     "_note_tier, the same number as wire_bytes on the call's "
     "dev_a2a_wire trace instant)")
pvar("dev_ag_wire_bytes", PVAR_CLASS_COUNTER, "device",
     "bytes the HBM-streaming ring all-gather kernel (ops/pallas_ici) "
     "sends over ICI, per rank, summed over the calls it served on the "
     "1:1 mesh channel: p - 1 blocks a call, tile padding included, the "
     "rank's own block excluded, as the kernel module reckons them "
     "(all_gather_wire_bytes; counted per call in coll/device.py "
     "_note_tier, the same number as wire_bytes on the call's "
     "dev_ag_wire trace instant)")
pvar("dev_rs_wire_bytes", PVAR_CLASS_COUNTER, "device",
     "bytes the HBM-streaming ring reduce-scatter kernel (ops/pallas_ici) "
     "sends over ICI, per rank, summed over the calls it served on the "
     "1:1 mesh channel: one block in each of the p - 1 fold rounds, tile "
     "padding included, as the kernel module reckons them "
     "(reduce_scatter_wire_bytes; counted per call in coll/device.py "
     "_note_tier, the same number as wire_bytes on the call's "
     "dev_rs_wire trace instant)")
pvar("dev_bc_wire_bytes", PVAR_CLASS_COUNTER, "device",
     "bytes the HBM-streaming ring broadcast kernel (ops/pallas_ici) "
     "sends over ICI from its root, summed per rank over the calls it "
     "served on the 1:1 mesh channel: the payload once, tile padding "
     "included, both lanes together; a forwarding chip sends less and "
     "the root's own copy never reaches the wire, as the kernel module "
     "reckons them (bcast_wire_bytes; counted per call in "
     "coll/device.py _note_tier, the same number as wire_bytes on the "
     "call's dev_bc_wire trace instant)")
pvar("dev_coll_fallback_nbc", PVAR_CLASS_COUNTER, "device",
     "nonblocking collectives on a device-capable comm that could not "
     "route through the device tier (op/dtype/residency/size or the "
     "slot channel) and took the host schedule instead — the NBC "
     "analog of the dev_coll_fallback_* family (coll/device.py "
     "build_nonblocking_request)")
pvar("dev_deposit_as_is", PVAR_CLASS_COUNTER, "device",
     "device collective calls, per rank, whose deposit is the caller's "
     "own array object: a flat device array asked for whole, handed on "
     "without a call into jax's reshape or indexing (coll/device.py "
     "_as_local; the blocking entries and the nonblocking build); a "
     "shaped buffer, a count below the size, MPI_IN_PLACE at an offset "
     "and host buffers do not count")
pvar("dev_call_plan_hit", PVAR_CLASS_COUNTER, "device",
     "blocking device collective calls, per rank, that ran on a filed "
     "call plan: the signature (collective, buffer type, shape and "
     "dtype, count, datatype, op, root) was decided by an earlier call "
     "of this rank under the cvars as they stand, so comm.<coll>'s "
     "lines, _select_transport, _as_local, _op_name and _decide_tier "
     "were not walked again (coll/device.py plan_of, run_plan)")
pvar("dev_call_plan_filed", PVAR_CLASS_COUNTER, "device",
     "blocking device collective calls, per rank, that decided their "
     "signature and filed a call plan on their way out of _run: a "
     "signature's first call, and its first after a cvar was written "
     "or a tuning profile loaded (Config.writes moved: the old plan "
     "is stale). Host buffers, shaped or partial buffers, MPI_IN_PLACE, "
     "64-bit types and alltoallv decide every call and file nothing")
pvar("dev_slot_operands", PVAR_CLASS_COUNTER, "device",
     "slot-channel leader calls that handed the program the deposited "
     "device arrays as they lay — R operands, no stack, no staging "
     "copy (coll/device.py HBMSlotChannel._stage); host deposits, "
     "staged as one stacked array, do not count")
pvar("dev_mesh_operands", PVAR_CLASS_COUNTER, "device",
     "mesh-channel leader calls (blocking, alltoallv, nonblocking) in "
     "which every rank's deposit entered the mesh-sharded global array "
     "as it lay: a flat device array on the rank's own device, no "
     "reshape, no eager op, no copy (coll/device.py DeviceCollChannel."
     "_shards); a call with a host deposit or a padded alltoallv "
     "payload among its ranks does not count")
pvar("dev_fold_stacked", PVAR_CLASS_COUNTER, "device",
     "planar (k, n) copies the fold channel's leader made of a chip's k "
     "deposits (coll/device.py DeviceFoldChannel._chip_stack: jnp.stack "
     "of device-resident deposits, np.stack + device_put of host ones): "
     "+1 per chip copied per leader call: every chip of an allgather at "
     "k > 1; of allreduce, reduce and reduce_scatter_block the chips "
     "whose deposits do not lie flat on them (every chip on host "
     "deposits); 0 for bcast, and 0 where the deposits are the fold "
     "program's operands as they lie (dev_fold_operands), as the slot "
     "channel's are (dev_slot_operands)")
pvar("dev_fold_operands", PVAR_CLASS_COUNTER, "device",
     "fold-channel leader calls of allreduce, reduce and "
     "reduce_scatter_block in which every chip's k deposits were its "
     "fold program's operands as they lay: flat device arrays on "
     "their chip's device, no stack, no reshape, no eager op "
     "(coll/device.py DeviceFoldChannel._fold_chip); a call with a "
     "host deposit, a shaped array or one committed to another chip "
     "among its ranks stages that chip (dev_fold_stacked) and does "
     "not count")
pvar("dev_fold_fused", PVAR_CLASS_COUNTER, "device",
     "fold-channel leader calls of allreduce, reduce and "
     "reduce_scatter_block whose level 1 ran inside the level-2 mesh "
     "program: one launch a call, k mesh-sharded operands, shard j of "
     "operand i rank j*k+i's deposit as it lies (coll/device.py "
     "DeviceFoldChannel._stage, _build); rises with "
     "dev_fold_operands on a 1-D mesh at k > 1; a call with a chip "
     "that had to be staged folds every chip by its own launch and "
     "does not count. Where in the program level 1 runs: inside the "
     "ring kernel's fold rounds (dev_fold_in_ring) or as the slot "
     "reduction in front of the level-2 collective")
pvar("dev_fold_in_ring", PVAR_CLASS_COUNTER, "device",
     "fold-channel leader calls counted by dev_fold_fused whose level 1 "
     "ran inside the ring kernel: the streaming ring's fold rounds read "
     "the chip's k deposits chunk by chunk and fold them in VMEM as "
     "they use them, so no slot-reduce kernel runs and no fold result "
     "is written (ops/pallas_ici.py ring_folds, _rs_rounds): the hbm "
     "tier of a 1-D mesh, deposits of p whole-tile blocks. A ragged "
     "length, a message under the hbm tier's edge (the flat VMEM ring "
     "of a sum), the quantized wire and the XLA lowering fold first "
     "and do not count")
pvar("dev_mesh_reordered", PVAR_CLASS_COUNTER, "device",
     "1-D meshes parallel/mesh.make_mesh returned with their devices in "
     "another order than they were given: TPU chips laid along a snake "
     "over their coords, so that consecutive ring positions are ICI "
     "neighbours (a 2x2 given as ids 0, 1, 2, 3 is walked 0, 1, 3, 2); "
     "devices without coords, and meshes given in ring order, do not "
     "count")
pvar("dev_coll_derived", PVAR_CLASS_COUNTER, "device",
     "blocking device collective calls, per rank, that ran on the "
     "channel of a derived communicator (MPI_Comm_dup, _split, "
     "_create, _create_group and the topology constructors over "
     "them, of a device-bound communicator: coll/device.py "
     "bind_derived), counted in _run beside the level pvars, which "
     "rise as on the world's channel")
pvar("coll_level_chip", PVAR_CLASS_COUNTER, "device",
     "collective calls that exercised the chip level of the three-"
     "level hierarchy: an HBM slot fold among co-resident ranks (the "
     "slot channel, or the fold stage of the leaders-per-chip channel "
     "— coll/device.py _run LEVELS accounting)")
pvar("coll_level_ici", PVAR_CLASS_COUNTER, "device",
     "collective calls that exercised the ICI level: a mesh program "
     "over the device ring/torus phases (the 1:1 mesh channel, or the "
     "inter-chip stage of the fold channel)")
pvar("coll_level_net", PVAR_CLASS_COUNTER, "device",
     "collective calls that exercised the network level: the net2 "
     "node-leader bridge over the KVS/TCP lanes past np=64 "
     "(coll/netcoll.py)")
pvar("dev_persistent_starts", PVAR_CLASS_COUNTER, "device",
     "persistent-collective start() dispatches that rode the device "
     "nonblocking tier (MPI_*_init handles whose cached program was "
     "pre-warmed through the exec-cache seam at init time)")
pvar("dev_nbc_segments", PVAR_CLASS_COUNTER, "device",
     "device nonblocking-collective program segments launched by the "
     "NBC DAG's poll vertices (coll/device.py _nb_poll — each launch "
     "is one async jitted dispatch the engine then pumps to "
     "completion)")

# the device point-to-point lane (pt2pt/protocol.py: a jax.Array given
# whole to send/recv/isend/irecv/sendrecv between thread-ranks)
pvar("dev_pt2pt_send", PVAR_CLASS_COUNTER, "device",
     "messages sent on the device point-to-point lane, at the sender: a "
     "jax.Array given whole to a peer thread-rank, matched by the "
     "library's own Matcher and handed over as a device array the "
     "receiver owns (pt2pt/protocol.py _dev_isend)")
pvar("dev_pt2pt_recv", PVAR_CLASS_COUNTER, "device",
     "messages of the device point-to-point lane delivered as a device "
     "array, at the receiver (pt2pt/protocol.py _dev_deliver); a lane "
     "message read back into a host receive buffer does not count")
pvar("dev_pt2pt_bytes", PVAR_CLASS_COUNTER, "device",
     "payload bytes received on the device point-to-point lane "
     "(the messages dev_pt2pt_recv counts)")
pvar("dev_pt2pt_unexpected", PVAR_CLASS_COUNTER, "device",
     "device point-to-point messages that came before their receive "
     "was posted and waited in the matcher's unexpected queue")
pvar("dev_pt2pt_d2d", PVAR_CLASS_COUNTER, "device",
     "device point-to-point messages whose receiver lives on another "
     "device of the process: the receiver-owned copy was the runtime's "
     "device-to-device copy (jax.device_put), made by the sender")
pvar("dev_pt2pt_fallback_host", PVAR_CLASS_COUNTER, "device",
     "device buffers handed to a point-to-point call that took the "
     "host path: a partial count or derived datatype, an array sharded "
     "over devices, a peer in another process, a plane-owned comm "
     "(read back once at the sender); a lane message matched by a "
     "host receive buffer (read back once at the receiver); a host "
     "message matched by a device receive (staged, then device_put)")

# device-lane timing observability (ISSUE 10): the optional hardware-
# profiler bracket (the spans are coll/device.py's own).
cvar("JAX_PROFILE", "", str, "device",
     "Directory for a jax.profiler trace bracketing the device-"
     "collective region (started at the first device collective, "
     "stopped at process exit). Empty = off. The hardware-tuning "
     "workflow for ici_chunk_bytes/ICI_PIPELINE_DEPTH on a real TPU "
     "(ROADMAP item 1) reads this trace in TensorBoard/XProf.")

# device one-sided RMA engine knobs + tier observability (ISSUE 16:
# ops/pallas_rma, rma/device). Same early-declaration contract; the
# dev_rma_rdma_min / dev_rma_quant_min tier-edge cvars live with the
# other DEV_* edges in coll/tuning.py.
cvar("RMA_CHUNK_BYTES", 0, int, "device",
     "VMEM chunk size (bytes) of the one-sided remote-DMA kernels "
     "(ops/pallas_rma): each put/get/accumulate chunk is one remote "
     "DMA through a depth-slotted landing buffer. 0 (default) inherits "
     "the ICI chunk edge (kernel_params.ici_chunk_bytes / "
     "MV2T_ICI_CHUNK_BYTES) so both device lanes tune together.")
pvar("dev_rma_tier_rdma", PVAR_CLASS_COUNTER, "device",
     "one-sided window ops served by the chunked remote-DMA tier "
     "(ops/pallas_rma put/get/accumulate kernels)")
pvar("dev_rma_tier_quant", PVAR_CLASS_COUNTER, "device",
     "one-sided accumulates served by the block-scaled quantized "
     "remote-DMA wire (ops/pallas_rma + the pallas_quant codec, gated "
     "by MV2T_QUANT_COLL and the dev_rma_quant_min edge)")
pvar("dev_rma_tier_epoch", PVAR_CLASS_COUNTER, "device",
     "one-sided window ops served by the ppermute epoch compiler "
     "(rma/device.py _build_epoch — the scheduled fallback tier)")
pvar("dev_rma_fallback_noncontig", PVAR_CLASS_COUNTER, "device",
     "one-sided ops routed to the epoch compiler because the element "
     "pattern is strided/derived (the epoch compiler's home turf; the "
     "remote-DMA tier carries contiguous runs only)")
pvar("dev_rma_fallback_platform", PVAR_CLASS_COUNTER, "device",
     "one-sided ops routed to the epoch compiler because the pallas "
     "kernels cannot run here (no pallas, or off-TPU without "
     "MV2T_ICI_INTERPRET)")
pvar("dev_rma_fallback_size", PVAR_CLASS_COUNTER, "device",
     "one-sided ops routed to the epoch compiler because the payload "
     "is below the dev_rma_rdma_min edge (or degenerate)")
pvar("dev_rma_fallback_dtype", PVAR_CLASS_COUNTER, "device",
     "one-sided ops routed to the epoch compiler because the window "
     "dtype does not lower to the remote-DMA kernels")
pvar("dev_rma_flush", PVAR_CLASS_COUNTER, "device",
     "passive-target completion waves (flush/flush_local/unlock) "
     "closed on a DeviceWin (rma/device.py)")
pvar("dev_rma_wire_bytes", PVAR_CLASS_COUNTER, "device",
     "payload bytes the remote-DMA one-sided tier put on the wire "
     "(quantized accumulates count their shrunken wire run)")


# ---------------------------------------------------------------------------
# multi-tenant node-service knobs + observability (runtime/daemon.py,
# coll/device.py executable cache). Declared HERE — daemon.claim runs
# inside MPI_Init's stdlib-only light boot and this module is already
# on that path (faults -> mpit), so the MPI_T surface enumerates the
# serving-fabric knobs before any heavy import; the owning modules
# fetch the already-declared entries by name.
# ---------------------------------------------------------------------------

cvar("DAEMON_NSETS", 4, int, "runtime",
     "Warm-attach daemon: maximum segment-set instances per geometry "
     "key. Overlapping jobs of ONE geometry claim distinct instances "
     "(<geokey>-i<k>) up to this bound; further claims queue under the "
     "admission quota.")
cvar("DAEMON_QUOTA", 8, int, "runtime",
     "Warm-attach daemon: node-wide admission quota — maximum busy "
     "segment sets across all geometries. Claims past the quota queue "
     "(bounded) instead of being refused; a timed-out waiter falls "
     "back to private per-job segments.")
cvar("DAEMON_EXEC_CACHE", 1, int, "runtime",
     "Device-executable cache in the daemon dir: coll/device.py "
     "program builds serialize the traced+compiled executable "
     "(jax.export) keyed on (kernel, shape, mesh, jax/profile "
     "fingerprint) so the first device collective of a new process "
     "deserializes instead of re-tracing. 0 = build per process as "
     "before. Requires MV2T_DAEMON=1; no-op on jax without the export "
     "API.")

pvar("daemon_claims_active", PVAR_CLASS_LEVEL, "runtime",
     "warm-attach segment-set claims this process currently holds "
     "(claim grants minus epoch-guarded releases)")
pvar("daemon_queue_waits", PVAR_CLASS_COUNTER, "runtime",
     "claims that entered the daemon's bounded admission queue "
     "(all instances busy or quota reached) before being granted or "
     "timing out")
pvar("exec_cache_hits", PVAR_CLASS_COUNTER, "runtime",
     "device-executable cache hits: program builds served by "
     "deserializing a cached executable instead of trace+compile")
pvar("exec_cache_misses", PVAR_CLASS_COUNTER, "runtime",
     "device-executable cache misses (no entry for the key at the "
     "current cache epoch, or a stale-epoch entry rejected)")
pvar("exec_cache_bytes", PVAR_CLASS_COUNTER, "runtime",
     "bytes of serialized executables written into the daemon's "
     "exec-cache by this process")


# ---------------------------------------------------------------------------
# continuous serving telemetry (mvapich2_tpu/metrics). Declared HERE —
# the daemon claim path records attach/queue histograms inside MPI_Init's
# stdlib-only light boot, and this module is already on that path; the
# owning modules (metrics/, coll/, rma/, transport/) fetch the
# already-declared entries by name.
# ---------------------------------------------------------------------------

cvar("METRICS", 1, int, "metrics",
     "Continuous serving telemetry: per-rank latency histograms "
     "(PVAR_CLASS_HISTOGRAM) at the collective/rendezvous/RMA/daemon "
     "sites plus the heartbeat-thread sampler that snapshots the fp_* "
     "shm mirror and selected pvars into the <ring>.metrics "
     "time-series segment for bin/mpistat --watch / bin/mpimetrics / "
     "the daemon's `metrics` verb. 1 (default) = on; 0 = off — sites "
     "then pay one attribute check, nothing else (the trace-off "
     "discipline, guarded by tests/progs/trace_overhead_prog.py).")
cvar("METRICS_INTERVAL_MS", 250, int, "metrics",
     "Sampling period (milliseconds) of the metrics ring sampler. The "
     "sampler rides the shm heartbeat thread (no thread of its own), "
     "so the effective period is max(interval, heartbeat wait) and "
     "never busier than ~20 ms.")

for _h, _d in (
    ("lat_coll_flat", "host flat-tier collective wave latency "
     "(coll/flatcoll.py try_* around the cp_flat_* call)"),
    ("lat_coll_flat2", "host hierarchical flat2-tier collective wave "
     "latency (coll/flatcoll.py try_* around the cp_flat2_* call)"),
    ("lat_coll_sched", "host scheduled-algorithm collective latency "
     "(coll/api.py dispatch around the pt2pt schedule)"),
    ("lat_coll_net2", "net2 node-leader-tier collective latency "
     "(coll/netcoll.py: group fold + leader bridge + fan-out, "
     "end-to-end)"),
    ("lat_dev_vmem", "the rank's time in rendezvous + leader of a "
     "device collective on the VMEM flat ring tier (coll/device.py "
     "_run; ends at the enqueue on the mesh channel, not at the "
     "result)"),
    ("lat_dev_hbm", "the rank's time in rendezvous + leader of a "
     "device collective on the HBM-streaming chunked ring tier "
     "(coll/device.py _run; ends at the enqueue on the mesh channel)"),
    ("lat_dev_quant", "the rank's time in rendezvous + leader of a "
     "device collective on the block-scaled quantized wire tier "
     "(coll/device.py _run; ends at the enqueue on the mesh channel)"),
    ("lat_dev_xla", "the rank's time in rendezvous + leader of a "
     "device collective on the XLA lowering (coll/device.py _run; "
     "ends at the enqueue on the mesh channel)"),
    ("lat_dev_slot", "the rank's time in rendezvous + leader of a "
     "device collective on the slot tier (coll/device.py _run; the "
     "slot leader waits for the device, so this includes the result)"),
    ("lat_dev_nbc", "device nonblocking-collective segment latency "
     "(coll/device.py _nb_poll: async launch to observed completion "
     "on the NBC DAG)"),
    ("lat_rndv_chunk", "rendezvous pipeline chunk-batch service time "
     "(transport/base.py account_rndv_chunk: one publish/drain batch "
     "from first copy to hand-off)"),
    ("lat_rma_flush", "one-sided completion-wave latency (rma/device.py "
     "fence/flush/unlock around the queued-op drain)"),
    ("lat_daemon_attach", "daemon claim attach latency (runtime/"
     "daemon.py claim entry to grant, queue wait included)"),
    ("lat_daemon_queue", "daemon admission-queue wait (queue entry to "
     "grant; only queued claims record)"),
):
    pvar(_h, PVAR_CLASS_HISTOGRAM, "metrics",
         f"log2-bucketed latency histogram (us): {_d}")


# ---------------------------------------------------------------------------
# the autotuner lives beside MPI_T (tools space): mpit.autotune —
# re-exported lazily (PEP 562): it imports numpy, and this module sits
# on the C-ABI light boot path (faults -> mpit), which must stay
# stdlib-only until the deferred world build
# ---------------------------------------------------------------------------
def __getattr__(name: str):
    if name == "autotune":
        from . import autotune
        return autotune
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
