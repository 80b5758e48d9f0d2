"""What a traced run hands to the per-layer metric readers.

A reader (``layer_metrics/<metric>.py``) gets one ``RunContext`` and
returns a number, or ``None`` where it finds nothing to read; run.py
then leaves that metric out of the line.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from . import xplane

Event = Tuple[float, str, str, str, Optional[dict]]   # recorder.py's tuple


@dataclass
class DeviceTrace:
    """One device's reduction of the traced sub-window (trace axis, s)."""
    ordinal: int
    lo: float
    hi: float
    busy: List[xplane.Interval]
    ops: List[Tuple[str, float, float]]

    @property
    def busy_s(self) -> float:
        return xplane.length(self.busy)

    @property
    def window_s(self) -> float:
        return self.hi - self.lo


@dataclass
class RunContext:
    collective: object                  # collectives/<name>.py
    config: dict
    traffic: dict
    ranks: int
    bytes_per_rank: int
    device_kind: str
    peaks: dict                         # peaks.json's row for device_kind
    window_mono: Tuple[float, float]    # the measured window, time.monotonic
    spans: Dict[int, List[Event]] = field(default_factory=dict)
    counters: Dict[str, int] = field(default_factory=dict)
    # rank 0, traced iterations: from comm.<coll> returning to the result
    # being ready (time.monotonic)
    caller_waits: List[Tuple[float, float]] = field(default_factory=list)
    devices: Dict[int, DeviceTrace] = field(default_factory=dict)
    rank0_ordinal: int = 0
    traced_calls: int = 0               # collectives completed in the sub-window
    clock_offset_s: Optional[float] = None   # monotonic -> trace axis

    def rank0_device(self) -> Optional[DeviceTrace]:
        return self.devices.get(self.rank0_ordinal)


def paired_spans(events: List[Event], layer: str, name: str
                 ) -> List[Tuple[float, float]]:
    """``(begin, end)`` of every closed B/E pair of ``layer``/``name``
    in one rank's recorder events (a begin whose end fell off the ring,
    or the reverse, is dropped)."""
    out, open_at = [], None
    for t, lay, nam, ph, _args in events:
        if lay != layer or nam != name:
            continue
        if ph == "B":
            open_at = t
        elif ph == "E" and open_at is not None:
            out.append((open_at, t))
            open_at = None
    return out
