"""Collective algorithm selection — the tuning-table machinery.

Analog of the MV2 tuning layer (SURVEY §2.3): the reference ships 1,377
generated per-(arch × HCA × ppn) headers (src/mpi/coll/tuning/, 284,869 LoC)
whose rows map {comm-size, msg-size bin} -> algorithm function pointer, with
env overrides (MV2_INTER_ALLREDUCE_TUNING etc., allreduce_tuning.h:28-37)
and per-comm installation in init_MV2_collops (ch3i_comm.c:27-100).

TPU-first redesign: tables are data (this module + optional JSON profiles
emitted by the autotuner in mvapich2_tpu.mpit.autotune), keyed by the arch
key from utils.detect (tpu generation × topology). Selection order:
  1. MV2T_<COLL>_ALGO env override ("device" forces the ICI path),
  2. device (XLA/ICI) path when the comm is mesh-bound and the op lowers
     — decided by coll/device.py's _select_transport wrappers installed
     over these entries (install_device_coll), using device_crossover(),
  3. two-level hierarchy when the comm spans multiple nodes,
  4. msg-size binned host algorithm (select_algorithm below).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

from ..utils.config import cvar, get_config, note_write
from ..utils.mlog import get_logger
from . import algorithms as alg

log = get_logger("tuning")

for _c in ("ALLREDUCE", "BCAST", "ALLGATHER", "ALLTOALL", "REDUCE",
           "BARRIER", "REDUCE_SCATTER"):
    cvar(f"{_c}_ALGO", "", str, "coll",
         f"Force the {_c.lower()} algorithm (empty = tuned selection). "
         f"Analog of MV2_INTER_{_c}_TUNING.")
cvar("USE_TWO_LEVEL", True, bool, "coll",
     "Enable hierarchical (node-aware) collectives "
     "(analog of MV2_USE_SHMEM_COLL / two-level paths).")
cvar("FLAT2", 1, int, "coll",
     "Hierarchical flat tier + multicast bcast kill switch (cp_flat2_*; "
     "0 disables the tier at segment attach). Read natively by "
     "cp_flat2_attach, so it must be launcher-uniform (env), like "
     "MV2T_FLAT2_GROUP.")
cvar("FLAT2_GROUP", 8, int, "coll",
     "Leaders-of-k group width of the hierarchical flat tier (clamped "
     "to [2, 8]; the np ceiling is k x 8 groups). Read natively by "
     "cp_flat2_group() from the env so BOTH ABIs derive one geometry — "
     "set it uniformly at launch, never per-rank.")
cvar("DEV_TIER_VMEM_MAX", 4 * 1024 * 1024, int, "device",
     "Device-collective tier edge: shards at or below this many bytes "
     "run the VMEM-resident flat ring kernels (ops/pallas_ring); above "
     "it the HBM-streaming chunked ring (ops/pallas_ici). Measured "
     "profiles (device_crossovers.dev_tier_vmem_max) override; "
     "bin/measure_crossover --device re-derives it.")
cvar("DEV_TIER_XLA_MIN", -1, int, "device",
     "Device-collective tier edge: shards at or above this many bytes "
     "leave the hand-written kernels for the stock XLA lowering "
     "(-1 = never — the HBM-streaming tier has no size ceiling). "
     "Measured profiles (device_crossovers.dev_tier_xla_min) override. "
     "Every XLA take is counted by the dev_coll_fallback_* pvars.")
cvar("DEV_TIER_QUANT_MIN", 1024 * 1024, int, "device",
     "Device-collective tier edge: with an MV2T_QUANT_COLL accuracy "
     "budget set, float sum-reduce shards at or above this many bytes "
     "take the block-scaled quantized wire tier (ops/pallas_quant) "
     "above the exact hbm tier (-1 = never). Measured profiles "
     "(device_crossovers.dev_tier_quant_min) override.")
cvar("DEV_RMA_RDMA_MIN", 0, int, "device",
     "One-sided tier edge: contiguous DeviceWin put/get/accumulate "
     "payloads at or above this many bytes run the chunked remote-DMA "
     "kernels (ops/pallas_rma) instead of the ppermute epoch compiler "
     "(-1 = never — everything keeps the epoch tier). Measured "
     "profiles (device_crossovers.dev_rma_rdma_min) override; every "
     "epoch take is counted by the dev_rma_fallback_* pvars.")
cvar("DEV_TIER_AXES_MIN", 4096, int, "device",
     "Device-collective mesh edge: on a multi-axis torus mesh, shards "
     "at or above this many bytes decompose allreduce into per-axis "
     "reduce-scatter/all-gather ring phases (each element crosses each "
     "axis' ICI links once); below it each axis runs a full allreduce "
     "in sequence (half the kernel launches — the latency shape). "
     "-1 = always decompose. Measured profiles "
     "(device_crossovers.dev_tier_axes_min) override.")
cvar("NET2", 1, int, "coll",
     "Three-level network tier kill switch: comms past the np=64 flat2 "
     "ceiling compose node-local waves under round-robin leader groups "
     "with an inter-leader exchange (0 disables; the sched table rows "
     "of the net2 comm-size class take over). Must be launcher-uniform "
     "— every member must reach the same dispatch verdict.")
cvar("NET2_MAX_RANKS", 256, int, "coll",
     "np ceiling of the net2 leader-bridge tier (and of the net2 "
     "comm-size class): above it comms fall to the generic large-class "
     "sched rows. Clamped to [65, 4096].")
cvar("DEV_RMA_QUANT_MIN", 1024 * 1024, int, "device",
     "One-sided tier edge: with an MV2T_QUANT_COLL accuracy budget "
     "set, f32 sum accumulates at or above this many bytes carry the "
     "block-scaled quantized wire over the remote-DMA tier (-1 = "
     "never). Measured profiles (device_crossovers.dev_rma_quant_min) "
     "override; ineligible calls keep the exact rdma tier, bit-exact.")

# ---------------------------------------------------------------------------
# algorithm registries (name -> fn), per collective
# ---------------------------------------------------------------------------

ALGOS: Dict[str, Dict[str, Callable]] = {
    "barrier": {
        "dissemination": alg.barrier_dissemination,
    },
    "bcast": {
        "binomial": alg.bcast_binomial,
        "scatter_ring_allgather": alg.bcast_scatter_ring_allgather,
    },
    "reduce": {
        "binomial": alg.reduce_binomial,
        "gather_local": alg.reduce_gather_local,
    },
    "allreduce": {
        "rd": alg.allreduce_recursive_doubling,
        "rsa": alg.allreduce_reduce_scatter_allgather,
        "ring": alg.allreduce_ring,
        "two_level": alg.allreduce_two_level,
        "gather_bcast": alg.allreduce_gather_bcast,
    },
    "allgather": {
        "rd": alg.allgather_recursive_doubling,
        "bruck": alg.allgather_bruck,
        "ring": alg.allgather_ring,
    },
    "alltoall": {
        "bruck": alg.alltoall_bruck,
        "scattered": alg.alltoall_scattered,
        "pairwise": alg.alltoall_pairwise,
    },
}

from .shmcoll import (allreduce_rsa_arena,  # noqa: E402
                      allreduce_two_level_slotted, bcast_arena)

ALGOS["allreduce"]["two_level_slotted"] = allreduce_two_level_slotted
ALGOS["allreduce"]["rsa_arena"] = allreduce_rsa_arena
ALGOS["bcast"]["arena"] = bcast_arena

from .netcoll import (allreduce_net2, barrier_net2,  # noqa: E402
                      bcast_net2)

ALGOS["allreduce"]["net2"] = allreduce_net2
ALGOS["bcast"]["net2"] = bcast_net2
ALGOS["barrier"]["net2"] = barrier_net2

# ---------------------------------------------------------------------------
# default tables: rows of (msg-size upper bound, algo name); the last row's
# bound is None (infinity). Mirrors the shape of e.g. allreduce_tuning.h:38-90
# with {comm size ranges} x {msg bins}.
# ---------------------------------------------------------------------------

Table = List[Tuple[Optional[int], str]]

DEFAULT_TABLES: Dict[str, Dict[str, Table]] = {
    # comm-size class: "small" (<= 8), "large" (> 8). The top bin is the
    # large-message tier: the arena/CMA sectioned exchange (zero packet
    # handshakes on a single node; reduce-scatter+allgather shape), with
    # graceful internal fallback to two-level/ring when it cannot run.
    # symbolic bin edges ("eager" = SMP_EAGERSIZE, "coll_max" =
    # FP_COLL_MAX) resolve against the live cvars at selection time, so
    # the table's tier switches stay aligned with the protocol
    # thresholds the plane tier gates on — a drifting constant here is
    # exactly how the r5 64 KiB allreduce cliff happened
    # "flat2" is the hierarchical-tier comm-size band (8 < np <= 64,
    # the cp_flat2_* window): these rows are the SCHEDULED fallback for
    # calls the flat2 tier does not carry (payload > MV2T_FLAT2_MAX,
    # tier disabled, lane exhausted). Edges measured at np=16 on the
    # r8 bench host (oversubscribed 1-core): rd's log-depth chain wins
    # the sub-8 KiB band, the reduce-scatter shapes win the middle,
    # the arena tier everything above the eager size.
    # "net2" is the leader-bridge comm-size band (64 < np <=
    # MV2T_NET2_MAX_RANKS): the net2 algorithm composes node-local
    # flat2 waves under round-robin leader groups with an inter-leader
    # exchange (coll/netcoll.py); its small-message band is where the
    # leaders-of-k fold wins. The remaining rows are the explicit SCHED
    # FALLBACK for calls the tier does not carry (tier disabled, comm
    # not plane-owned, payload past the eager band) — before this class
    # existed, np>64 comms fell through to the generic large rows
    # silently. The net2 algorithms degrade to these sched shapes
    # internally when their gates fail, so the verdict stays uniform.
    "allreduce": {
        "small": [(16 * 1024, "rd"), ("eager", "ring"),
                  (None, "rsa_arena")],
        "flat2": [(8 * 1024, "rd"), ("eager", "rsa"),
                  (None, "rsa_arena")],
        "net2": [(8 * 1024, "net2"), ("eager", "rsa"),
                 (None, "rsa_arena")],
        "large": [(8 * 1024, "rd"), ("eager", "rsa"),
                  (None, "rsa_arena")],
    },
    "bcast": {
        "small": [(64 * 1024, "binomial"), (None, "arena")],
        "flat2": [(16 * 1024, "binomial"), (None, "arena")],
        "net2": [(16 * 1024, "net2"), (None, "arena")],
        "large": [(16 * 1024, "binomial"), (None, "arena")],
    },
    "allgather": {
        "small": [(32 * 1024, "bruck"), (None, "ring")],
        "flat2": [(8 * 1024, "bruck"), (None, "ring")],
        "net2": [(8 * 1024, "bruck"), (None, "ring")],
        "large": [(8 * 1024, "bruck"), (None, "ring")],
    },
    "alltoall": {
        "small": [(4 * 1024, "bruck"), (None, "scattered")],
        "flat2": [(1024, "bruck"), (64 * 1024, "scattered"),
                  (None, "pairwise")],
        "net2": [(1024, "bruck"), (64 * 1024, "scattered"),
                 (None, "pairwise")],
        "large": [(1024, "bruck"), (64 * 1024, "scattered"),
                  (None, "pairwise")],
    },
    "reduce": {
        "small": [(None, "binomial")],
        "flat2": [(None, "binomial")],
        "net2": [(None, "binomial")],
        "large": [(None, "binomial")],
    },
    "barrier": {
        "small": [(None, "dissemination")],
        "flat2": [(None, "dissemination")],
        "net2": [(None, "net2")],
        "large": [(None, "dissemination")],
    },
}

# runtime-measured overrides loaded from a profile (autotuner output)
_PROFILE_TABLES: Dict[str, Dict[str, Table]] = {}
# measured host->device transport crossovers (bytes) per collective
_DEVICE_CROSSOVERS: Dict[str, int] = {}
# measured kernel parameters (e.g. pallas block sizes: hbm_slot_block_m,
# hbm_fused_block_m — consumed by ops/pallas_hbm.py)
_KERNEL_PARAMS: Dict[str, int] = {}


def load_profile(tables: Optional[Dict[str, Dict[str, Table]]] = None,
                 device_crossovers: Optional[Dict[str, int]] = None,
                 kernel_params: Optional[Dict[str, int]] = None) -> None:
    """Install autotuned tables (analog of regenerating tuning headers).
    Produced by mvapich2_tpu.mpit.autotune; see autotune.load_profile_file
    for the JSON artifact form."""
    if tables:
        _PROFILE_TABLES.update(tables)
    if device_crossovers:
        _DEVICE_CROSSOVERS.update(device_crossovers)
    if kernel_params:
        _KERNEL_PARAMS.update(kernel_params)
    note_write()    # tier edges and crossovers are read from these


def kernel_param(key: str, default: int) -> int:
    """A measured kernel parameter from the loaded profile, or the
    compiled-in default when no profile covers it."""
    return _KERNEL_PARAMS.get(key, default)


def kernel_param_cv(key: str, cvar_name: str) -> int:
    """A cvar-backed kernel parameter with the device-edge precedence
    (_dev_tier_edge): explicitly-set cvar (the user said so) >
    measured profile entry > cvar default. Before this, a committed
    profile's ici_chunk_bytes silently outranked an explicit
    MV2T_ICI_CHUNK_BYTES — the one device knob the user could never
    win back from a measurement."""
    cv = get_config()._vars[cvar_name]
    val = int(cv.value)
    if not cv._explicit:
        val = int(_KERNEL_PARAMS.get(key, val))
    return val


def describe_profile() -> Dict:
    """The loaded measured-profile state, for display tools (mpiname
    -a): {} values when no profile is loaded."""
    return {"tables": dict(_PROFILE_TABLES),
            "kernel_params": dict(_KERNEL_PARAMS),
            "device_crossovers": dict(_DEVICE_CROSSOVERS)}


def device_crossover(name: str, comm) -> int:
    """Bytes at which a host-buffer collective on a mesh-bound comm moves
    to the device (XLA/ICI) transport. Precedence: explicitly-set cvar
    (env or config.set — the user said so) > measured profile > cvar
    default."""
    cfg = get_config()
    cv = cfg._vars["DEVICE_COLL_MIN_BYTES"]
    val = cv.value          # forces the lazy env load
    if cv._explicit:
        return val
    got = _DEVICE_CROSSOVERS.get(name)
    if got is not None:
        return got
    return val


def quant_params() -> Tuple[str, float]:
    """(wire_format, rel_error_budget) parsed from MV2T_QUANT_COLL.
    Grammar: '' = off (budget 0); '<budget>' = q8 wire with that max
    relative-error budget (e.g. '1e-2'); '<wire>:<budget>' selects the
    wire format explicitly (q8 | fp8). A malformed value logs once and
    reads as off — a typo must never silently quantize."""
    raw = str(get_config().get("QUANT_COLL", "") or "").strip()
    if not raw:
        return "q8", 0.0
    wire = "q8"
    if ":" in raw:
        wire, _, raw = raw.partition(":")
        wire = wire.strip().lower()
    try:
        budget = float(raw)
    except ValueError:
        log.warn("MV2T_QUANT_COLL %r is not '<budget>' or "
                 "'<wire>:<budget>'; quant tier off", raw)
        return "q8", 0.0
    if wire not in ("q8", "fp8"):
        log.warn("MV2T_QUANT_COLL wire %r is not q8|fp8; quant tier "
                 "off", wire)
        return "q8", 0.0
    return wire, max(0.0, budget)


def device_tier(name: str, shard_nbytes: int) -> str:
    """'vmem' | 'hbm' | 'quant' | 'xla' for a device-resident
    collective shard of ``shard_nbytes`` — the device-side msg-size
    bin. Edge precedence mirrors device_crossover(): explicitly-set
    cvar (the user said so) > measured profile entry > cvar default.
    The quant bin sits at the top (above hbm AND the xla re-entry: its
    whole point is shrinking the wire where messages are largest) and
    only opens when MV2T_QUANT_COLL carries a nonzero accuracy budget;
    per-call eligibility (op/dtype/bound) is the kernel dispatcher's
    check (ops/pallas_ici.planned_tier). ``name`` is accepted for
    future per-collective edges; today the edges are shared."""
    vmax = _dev_tier_edge("DEV_TIER_VMEM_MAX", "dev_tier_vmem_max")
    xmin = _dev_tier_edge("DEV_TIER_XLA_MIN", "dev_tier_xla_min")
    if shard_nbytes <= vmax:
        return "vmem"
    _wire, budget = quant_params()
    if budget > 0:
        qmin = _dev_tier_edge("DEV_TIER_QUANT_MIN",
                              "dev_tier_quant_min")
        if qmin >= 0 and shard_nbytes >= qmin:
            return "quant"
    if xmin is not None and xmin >= 0 and shard_nbytes >= xmin:
        return "xla"
    return "hbm"


def net2_max_ranks() -> int:
    """np ceiling of the net2 class/tier (cvar, clamped): the leader-
    bridge geometry caps at ngroups x 64-rank flat2 windows."""
    return max(65, min(4096, int(get_config()["NET2_MAX_RANKS"])))


def _size_class(comm) -> str:
    """small (flat-tier window) / flat2 (hierarchical-tier window) /
    net2 (leader-bridge window past the single-node ceiling) / large.
    The 8 and 64 edges mirror MV2T_FLAT_NSLOTS and
    MV2T_FLAT2_MAX_RANKS — the np bands the two shm tiers serve; the
    net2 edge is MV2T_NET2_MAX_RANKS. Before the net2 class, np>64
    comms silently fell through to the generic large-class rows."""
    if comm.size <= 8:
        return "small"
    if comm.size <= 64:
        return "flat2"
    return "net2" if comm.size <= net2_max_ranks() else "large"


def _resolve_edge(bound):
    """A table bin edge: an int, None (infinity), or a symbolic name
    tracking its single source of truth ("eager" = SMP_EAGERSIZE,
    "coll_max" = FP_COLL_MAX, "dev_tier_vmem_max"/"dev_tier_xla_min" =
    the device tier edges, profile-overridable) so tier switches cannot
    drift from the thresholds the protocol layers gate on. The
    mv2tlint ``profile`` doctor harvests the known symbols from THIS
    function — adding one here is the whole registration."""
    if bound == "eager":
        return int(get_config()["SMP_EAGERSIZE"])
    if bound == "coll_max":
        return int(get_config()["FP_COLL_MAX"])
    if bound == "dev_tier_vmem_max":
        return _dev_tier_edge("DEV_TIER_VMEM_MAX", "dev_tier_vmem_max")
    if bound == "dev_tier_xla_min":
        return _dev_tier_edge("DEV_TIER_XLA_MIN", "dev_tier_xla_min")
    if bound == "dev_tier_quant_min":
        return _dev_tier_edge("DEV_TIER_QUANT_MIN", "dev_tier_quant_min")
    if bound == "dev_tier_axes_min":
        return _dev_tier_edge("DEV_TIER_AXES_MIN", "dev_tier_axes_min")
    if bound == "dev_rma_rdma_min":
        return _dev_tier_edge("DEV_RMA_RDMA_MIN", "dev_rma_rdma_min")
    if bound == "dev_rma_quant_min":
        return _dev_tier_edge("DEV_RMA_QUANT_MIN", "dev_rma_quant_min")
    return bound


def _dev_tier_edge(cvar_name: str, profile_key: str) -> int:
    """One device tier edge with the device_tier() precedence:
    explicitly-set cvar > measured profile entry > cvar default."""
    cv = get_config()._vars[cvar_name]
    val = cv.value
    if not cv._explicit:
        val = _DEVICE_CROSSOVERS.get(profile_key, val)
    return int(val)


def _lookup(name: str, comm, nbytes: int) -> str:
    cls = _size_class(comm)
    tables = _PROFILE_TABLES.get(name) or DEFAULT_TABLES.get(name)
    if not tables:
        raise KeyError(name)
    if cls not in tables:
        # a measured profile only covers the comm-size class it ran at;
        # other classes keep the defaults
        tables = DEFAULT_TABLES[name]
    rows = tables[cls]
    for bound, algo in rows:
        bound = _resolve_edge(bound)
        if bound is None or nbytes <= bound:
            return algo
    return rows[-1][1]


def select_algorithm(comm, name: str, nbytes: int, op=None) -> Callable:
    cfg = get_config()
    # 1. env override
    forced = cfg.get(f"{name.upper()}_ALGO", "")
    if forced == "device":
        # names the transport, not a host algorithm: coll/device.py
        # handed this call back (op/dtype does not lower) and said so
        forced = ""
    if forced:
        fn = ALGOS[name].get(forced)
        if fn is None:
            log.warn("unknown %s algorithm %r; using tuned selection",
                     name, forced)
        else:
            return fn
    # 2. op constraints: non-commutative ops need order-preserving algos
    if op is not None and not op.commutative:
        if name == "allreduce":
            return alg.allreduce_gather_bcast
        if name == "reduce":
            return alg.reduce_gather_local
    # 3. two-level hierarchy when the comm spans nodes (node-aware path)
    if (name == "allreduce" and cfg["USE_TWO_LEVEL"]
            and comm.u.num_nodes() > 1 and comm.size > 2
            and _spans_nodes(comm) and nbytes >= 4096):
        return alg.allreduce_two_level
    # 4. tuned table
    algo = _lookup(name, comm, nbytes)
    return ALGOS[name][algo]


def _spans_nodes(comm) -> bool:
    nodes = {comm.u.node_ids[comm.world_of(r)] for r in range(comm.size)}
    return len(nodes) > 1


def install_coll_ops(comm) -> None:
    """Per-comm collective table — init_MV2_collops analog. The comm's
    methods dispatch through these entries, so a channel (e.g. the ICI mesh
    channel) can overwrite individual entries with native implementations."""
    from . import api
    comm.coll_fns = {
        "barrier": api.barrier,
        "bcast": api.bcast,
        "reduce": api.reduce,
        "allreduce": api.allreduce,
        "allgather": api.allgather,
        "allgatherv": api.allgatherv,
        "gather": api.gather,
        "gatherv": api.gatherv,
        "scatter": api.scatter,
        "scatterv": api.scatterv,
        "alltoall": api.alltoall,
        "alltoallv": api.alltoallv,
        "reduce_scatter_block": api.reduce_scatter_block,
        "reduce_scatter": api.reduce_scatter,
        "scan": api.scan,
        "exscan": api.exscan,
        "_select": lambda name, nbytes, op=None:
            select_algorithm(comm, name, nbytes, op),
    }
