"""Central configuration registry (control variables).

TPU-native analog of MVAPICH2's three-layer config system (SURVEY §5.6):
  * ~522 ``MV2_*`` environment variables parsed in
    /root/reference/src/mpid/ch3/channels/mrail/src/gen2/ibv_param.c
  * the central registry table in gen2/ibv_env_params.c:29-70
    ({id, type, group, name, address, visibility, description})
  * MPI_T cvars generated from structured comment blocks
    (maint/extractcvars.in).

Here all three collapse into one declarative registry: each knob is declared
once with ``cvar(...)`` and is then (a) settable via ``MV2T_<NAME>`` env vars,
(b) enumerable for tools (the MPI_T cvar surface in mvapich2_tpu.mpit reads
this registry), and (c) documented.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, Optional

ENV_PREFIX = "MV2T_"

_TRUE = {"1", "true", "yes", "on", "y"}
_FALSE = {"0", "false", "no", "off", "n"}


def _parse(typ: type, raw: str) -> Any:
    if typ is bool:
        low = raw.strip().lower()
        if low in _TRUE:
            return True
        if low in _FALSE:
            return False
        raise ValueError(f"bad boolean: {raw!r}")
    if typ is int:
        # Accept size suffixes like 64K / 2M / 1G (as ibv_param.c does for
        # thresholds such as MV2_IBA_EAGER_THRESHOLD).
        s = raw.strip().upper()
        mult = 1
        if s and s[-1] in "KMG":
            mult = {"K": 1 << 10, "M": 1 << 20, "G": 1 << 30}[s[-1]]
            s = s[:-1]
        return int(s) * mult
    if typ is float:
        return float(raw)
    return raw


class CVar:
    """One control variable: name, type, default, group, description.

    Mirrors the fields of the reference's mv2_env_param_list entries
    (gen2/ibv_env_params.c) and the MPI_T cvar info blocks. A plain
    class, not a dataclass: this module sits on the C-ABI light boot
    path and ``dataclasses`` drags in ``inspect`` (~7 ms of MPI_Init
    on the 1-core bench host)."""

    __slots__ = ("name", "default", "typ", "group", "desc", "choices",
                 "_value", "_explicit")

    def __init__(self, name: str, default: Any, typ: type,
                 group: str = "general", desc: str = "",
                 choices: Optional[tuple] = None):
        self.name = name
        self.default = default
        self.typ = typ
        self.group = group
        self.desc = desc
        self.choices = choices
        self._value = None
        self._explicit = False  # set via env or set_value (not default)

    def __repr__(self):
        return (f"CVar(name={self.name!r}, default={self.default!r}, "
                f"typ={self.typ!r}, group={self.group!r})")

    @property
    def env_name(self) -> str:
        return ENV_PREFIX + self.name

    def load(self) -> None:
        raw = os.environ.get(self.env_name)
        if raw is None:
            self._value = self.default
            self._explicit = False
            return
        val = _parse(self.typ, raw)
        if self.choices is not None and val not in self.choices:
            raise ValueError(
                f"{self.env_name}={raw!r}: must be one of {self.choices}")
        self._value = val
        self._explicit = True

    @property
    def value(self) -> Any:
        if self._value is None and not self._explicit:
            self.load()
        return self._value

    def set_value(self, val: Any) -> None:
        if self.choices is not None and val not in self.choices:
            raise ValueError(f"{self.name}: must be one of {self.choices}")
        self._value = val
        self._explicit = True
        note_write()


class Config:
    """Registry of all cvars. Singleton per process (like the env-param table)."""

    def __init__(self) -> None:
        self._vars: Dict[str, CVar] = {}
        self._lock = threading.Lock()
        # how often a value was written at run time (set, set_value,
        # reload, MPI_T's cvar write, a tuning profile loaded): whoever
        # remembers an answer decided from cvars remembers this count
        # beside it, and decides again once it has moved
        self.writes = 0

    def declare(self, name: str, default: Any, typ: Optional[type] = None,
                group: str = "general", desc: str = "",
                choices: Optional[tuple] = None) -> CVar:
        typ = typ or type(default)
        with self._lock:
            if name in self._vars:
                return self._vars[name]
            cv = CVar(name=name, default=default, typ=typ, group=group,
                      desc=desc, choices=choices)
            self._vars[name] = cv
            return cv

    def __getitem__(self, name: str) -> Any:
        return self._vars[name].value

    def get(self, name: str, default: Any = None) -> Any:
        cv = self._vars.get(name)
        return cv.value if cv is not None else default

    def set(self, name: str, value: Any) -> None:
        self._vars[name].set_value(value)

    def reload(self) -> None:
        """Re-read every cvar from the environment (used at Init time)."""
        for cv in self._vars.values():
            cv.load()
        note_write()

    def cvars(self) -> Dict[str, CVar]:
        return dict(self._vars)

    def dump(self) -> str:
        """Human-readable dump, the analog of ``mpiname -a`` env enumeration."""
        lines = []
        for name in sorted(self._vars):
            cv = self._vars[name]
            mark = "*" if cv._explicit else " "
            lines.append(f"{mark} {cv.env_name:<40} = {cv.value!r:<12} "
                         f"[{cv.group}] {cv.desc}")
        return "\n".join(lines)


_config = Config()


def get_config() -> Config:
    return _config


def note_write() -> None:
    """A cvar, or a measured profile that overrides one, was written
    (``Config.writes``)."""
    _config.writes += 1


def cvar(name: str, default: Any, typ: Optional[type] = None,
         group: str = "general", desc: str = "",
         choices: Optional[tuple] = None) -> CVar:
    """Declare (or fetch) a control variable in the global registry."""
    return _config.declare(name, default, typ, group, desc, choices)


# ---------------------------------------------------------------------------
# Core knobs shared across subsystems. Subsystem-specific knobs are declared
# next to their code; these are the ones the runtime itself needs.
# ---------------------------------------------------------------------------

cvar("DEBUG_LEVEL", 0, int, "debug",
     "Global debug verbosity (0=off). Analog of MV2_DEBUG_* switches.")
cvar("EAGER_THRESHOLD", 64 * 1024, int, "pt2pt",
     "Eager->rendezvous switch point in bytes "
     "(analog of MV2_IBA_EAGER_THRESHOLD, gen2/ibv_param.c:2354).")
cvar("SMP_EAGERSIZE", 32 * 1024, int, "pt2pt",
     "Intra-node eager size (analog of MV2_SMP_EAGERSIZE, ibv_param.c:776). "
     "Default measured on the 1-core bench host (see "
     "profiles/pt2pt_crossover.json): eager wins while a 64-deep window "
     "fits the shm ring; the CMA rendezvous wins beyond.")
cvar("FP_COLL_MAX", 256 * 1024, int, "coll",
     "Largest payload the plane-native collective tier carries (flat "
     "slots below cp_flat_payload_max, pt2pt schedules with eager-or-"
     "rendezvous hops above). Must agree on every rank of a job: the "
     "C fast path (fastpath.c fpc_enter) and the python dispatch "
     "(coll/api.py) both gate on it, and a rank that schedules while "
     "its peer takes the tuning tier deadlocks. Above it the tuning "
     "table (coll/tuning.py) selects the arena/slotted algorithms. "
     "Default = the measured sched/arena crossover on the 1-core "
     "bench host (np4 allreduce: 256 KiB rides the C schedule at "
     "~940 us vs ~1550 through the arena tier; at 512 KiB the arena's "
     "~1.1 ms fixed interpreter cost is amortized and it wins).")
cvar("RNDV_PROTOCOL", "RGET", str, "pt2pt",
     "Rendezvous protocol: RGET (receiver pulls), RPUT (sender pushes), "
     "R3 (packetized through channel). Default mirrors ibv_param.c:116.",
     choices=("RGET", "RPUT", "R3"))
cvar("MAX_CONTEXTS", 2048, int, "runtime",
     "Communicator context-id space (the reference's MPIR context-id "
     "bitmask is 2048 wide, mpir_context_id.h); exhaustion returns "
     "MPI_ERR_OTHER from comm creation (errors/comm/too_many_comms.c).")
cvar("ENABLE_AFFINITY", False, bool, "runtime",
     "Pin rank processes to CPUs (analog of MV2_ENABLE_AFFINITY).")
cvar("SHOW_ENV_INFO", False, bool, "runtime",
     "Print the cvar registry at Init (analog of MV2_SHOW_ENV_INFO).")
