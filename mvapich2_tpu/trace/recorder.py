"""Per-rank bounded ring-buffer event recorder.

The distributed-tracing analog of the reference's debug_utils.c subsystem
switches + mv2_mpit.c channel counters, redesigned as an event stream: each
rank owns one bounded ring buffer (a deque with maxlen — old events fall
off, memory is bounded by MV2T_TRACE_BUF) into which the five instrumented
layers append (timestamp, layer, name, phase, args) tuples:

    mpi       MPI entry/exit (profile.py interposition, trace/__init__.py)
    protocol  eager vs RTS/CTS/FIN rendezvous transitions (pt2pt/protocol.py)
    channel   per-channel send/recv with byte counts (transport/*.py)
    progress  progress_wait / idle / wake cycles (transport/progress.py)
    nbc       NBC DAG vertex issue/complete (coll/nbc/engine.py)

Cost discipline: when tracing is off every instrumented site pays exactly
ONE attribute check (``engine.tracer is None``) — the recorder attaches to
the ProgressEngine only when the MV2T_TRACE cvar is set, so the hot paths
never consult the config registry. Timestamps are CLOCK_MONOTONIC, which
is system-wide on Linux, so per-process rank dumps merge on one time axis
(trace/perfetto.py).
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from .. import faults as _faults
from ..utils.config import cvar, get_config

_monotonic = time.monotonic

cvar("TRACE", False, bool, "trace",
     "Enable the per-rank ring-buffer event recorder (near-zero cost when "
     "off: one attribute check per instrumented site).")
cvar("TRACE_BUF", 65536, int, "trace",
     "Ring-buffer capacity in events per rank; the oldest events are "
     "dropped first (bounded memory under any workload).")
cvar("TRACE_DIR", "", str, "trace",
     "Directory for per-rank trace dumps written at Finalize "
     "(trace-r<rank>.json); empty keeps events in memory only. "
     "bin/mpitrace sets this and merges the dumps into one Perfetto "
     "JSON after the job exits.")

# the instrumented layers, in lane order for the Perfetto export. Two
# lanes beyond the python recorder's five: "device" (coll/device.py
# dispatch spans + ops/pallas_ici.py entry instants) and "cplane" (the
# native trace ring of cplane.cpp, merged into the rank dump at
# Finalize — see trace/native.py).
LAYERS = ("mpi", "protocol", "channel", "progress", "nbc", "device",
          "cplane")


class Recorder:
    """One rank's bounded event ring. ``record`` is the only hot call."""

    __slots__ = ("rank", "events", "dropped_floor", "_append")

    def __init__(self, rank: int, capacity: int):
        self.rank = rank
        self.events: collections.deque = collections.deque(maxlen=capacity)
        self._append = self.events.append
        # number of events ever recorded minus len(events) = dropped count
        self.dropped_floor = 0

    def record(self, layer: str, name: str, ph: str = "i",
               args: Optional[dict] = None, **kw) -> None:
        """Append one event. ``ph`` follows the Chrome trace-event phases:
        'B'egin / 'E'nd for spans, 'i' for instants. deque.append with a
        maxlen is atomic under the GIL, so no lock on the hot path.

        The event's args are keywords, or on a hot site one dict handed
        over as the fourth argument and put into the ring as the object
        it is: whoever hands it over does not change it afterwards.

        The ``trace_stamp`` fault site lives here (``_record_faulted``);
        while no fault is armed it costs this one attribute test of the
        fault table, the test ``faults.fire`` itself starts with."""
        if kw:
            args = {**args, **kw} if args else kw
        if _faults._active is not None:
            return self._record_faulted(layer, name, ph, args)
        self._append((_monotonic(), layer, name, ph, args or None))

    def _record_faulted(self, layer: str, name: str, ph: str,
                        args: Optional[dict]) -> None:
        """``record`` while MV2T_FAULTS arms something: ``skip_stamp``
        drops the stamp, ``reorder`` swaps it behind its predecessor —
        seeded trace corruption that the conformance checker
        (bin/mv2tconform) must catch by a named invariant, never by
        silence. It corrupts only the trace, never the datapath."""
        kind = _faults.fire("trace_stamp")
        if kind == "skip_stamp":
            return
        self.events.append((_monotonic(), layer, name, ph, args or None))
        if kind == "reorder" and len(self.events) >= 2:
            # swap ring position AND timestamp with the predecessor, so
            # the corruption survives both ring-order and ts-order
            # readers (a stamp that landed with the wrong clock)
            last = self.events.pop()
            prev = self.events.pop()
            self.events.append((prev[0],) + last[1:])
            self.events.append((last[0],) + prev[1:])

    def tail(self, n: int) -> List[tuple]:
        """The most recent ``n`` events (stall-watchdog post-mortem)."""
        evs = list(self.events)
        return evs[-n:]

    def snapshot(self) -> Dict[str, Any]:
        """The per-rank dump payload (schema consumed by trace/perfetto)."""
        return {
            "rank": self.rank,
            "clock": "monotonic",
            "capacity": self.events.maxlen,
            "events": [[t, layer, name, ph, args]
                       for (t, layer, name, ph, args) in self.events],
        }


# ---------------------------------------------------------------------------
# attach / detach (the only code that consults the config registry)
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_active: List[Recorder] = []


def maybe_attach(engine) -> Optional[Recorder]:
    """Attach a recorder to ``engine`` iff the MV2T_TRACE cvar is set
    (called once per rank from Universe.initialize, after the config
    reload). Also installs the MPI entry/exit interposition tool while
    any recorder is live."""
    cfg = get_config()
    if not cfg.get("TRACE", False):
        engine.tracer = None
        return None
    rec = Recorder(engine.rank, max(256, int(cfg["TRACE_BUF"])))
    engine.tracer = rec
    with _lock:
        _active.append(rec)
    from . import _install_mpi_tracer
    _install_mpi_tracer()
    return rec


def detach(engine) -> None:
    """Drop ``engine``'s recorder; uninstalls the MPI interposition tool
    when the last recorder leaves (so an untraced run that follows a
    traced one in the same process pays nothing)."""
    rec = getattr(engine, "tracer", None)
    if rec is None:
        return
    engine.tracer = None
    last = False
    with _lock:
        if rec in _active:
            _active.remove(rec)
        last = not _active
    if last:
        from . import _uninstall_mpi_tracer
        _uninstall_mpi_tracer()


def dump_rank(engine) -> Optional[str]:
    """Write ``engine``'s ring buffer to MV2T_TRACE_DIR/trace-r<rank>.json
    (called at Finalize, before the recorder detaches). Returns the path,
    or None when no recorder / no dump dir."""
    rec = getattr(engine, "tracer", None)
    if rec is None:
        return None
    out_dir = get_config().get("TRACE_DIR", "")
    if not out_dir:
        return None
    os.makedirs(out_dir, exist_ok=True)
    snap = rec.snapshot()
    # merge the native C-plane ring (MV2T_NTRACE) into this rank's dump:
    # both clocks are CLOCK_MONOTONIC, so C events and python spans
    # share the Perfetto time axis with no translation. Diagnostics
    # must never kill Finalize — any ring-parse trouble drops the lane.
    try:
        from . import native as _native
        u = getattr(engine, "universe", None)
        pch = getattr(u, "plane_channel", None) if u is not None else None
        snap["events"].extend(_native.drain_channel(pch))
    except Exception:
        pass
    # embed this rank's metrics sampler series (MV2T_METRICS): the
    # merge renders them as Perfetto counter tracks beside the span
    # lanes — one timeline for spans AND time-series, same monotonic
    # clock as the ntrace events above. Same never-kill-Finalize rule.
    try:
        from ..metrics import ring as _mring
        u = getattr(engine, "universe", None)
        sch = getattr(u, "shm_channel", None) if u is not None else None
        if sch is not None:
            samples = _mring.channel_rows(sch)
            if samples:
                snap["metrics"] = samples
    except Exception:
        pass
    path = os.path.join(out_dir, f"trace-r{rec.rank}.json")
    with open(path, "w") as f:
        json.dump(snap, f)
    return path
