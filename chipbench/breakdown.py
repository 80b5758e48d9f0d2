"""Where the traced sub-window's time went: the device operations that
took most of it, and its idle time by what rank 0's host thread was
inside (the program's own spans, put on the trace's axis through the
harness's per-iteration TraceAnnotation)."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from . import xplane
from .context import Event, RunContext

TOP = 10
NAME_CHARS = 120         # an XLA op's name is its whole HLO line
LANES = ("device", "mpi")          # innermost first


def _closed_spans(events: List[Event]) -> List[Tuple[float, float, str]]:
    out, open_at = [], {}
    for t, layer, name, ph, _args in events:
        if layer not in LANES:
            continue
        if ph == "B":
            open_at[(layer, name)] = t
        elif ph == "E" and (layer, name) in open_at:
            out.append((open_at.pop((layer, name)), t, f"{layer}:{name}"))
    return out


def _label(t: float, spans: List[Tuple[float, float, str]],
           waits: List[Tuple[float, float]]) -> str:
    """What rank 0's host thread was in at host time ``t``: the
    innermost program span, else the harness's own two states."""
    best: Optional[Tuple[float, str]] = None
    for b, e, name in spans:
        if b <= t <= e and (best is None or b > best[0]):
            best = (b, name)
    if best:
        return best[1]
    if any(b <= t <= e for b, e in waits):
        return "caller waiting in block_until_ready (call returned)"
    return "harness loop between calls"


def device_ops(ctx: RunContext) -> List[List]:
    dev = ctx.rank0_device()
    if dev is None:
        return []
    return [[n[:NAME_CHARS], s] for n, s in
            xplane.time_by_name(dev.ops, dev.lo, dev.hi)[:TOP]]


def idle_gaps(ctx: RunContext) -> List[List]:
    """Idle seconds of rank 0's device by the program span rank 0 was
    in at the middle of each gap, largest first. Empty where the two
    clocks could not be put on one axis."""
    dev = ctx.rank0_device()
    if dev is None or ctx.clock_offset_s is None:
        return []
    spans = _closed_spans(ctx.spans.get(0, []))
    total: Dict[str, float] = {}
    for s, e in xplane.gaps(dev.busy, dev.lo, dev.hi):
        name = _label((s + e) / 2 - ctx.clock_offset_s, spans,
                      ctx.caller_waits)
        total[name] = total.get(name, 0.0) + (e - s)
    return [[n, s] for n, s in
            sorted(total.items(), key=lambda kv: -kv[1])[:TOP]]
