"""Merge per-rank trace dumps into one Chrome trace-event / Perfetto JSON.

Lane model: rank -> pid, layer -> tid, so `chrome://tracing` (or
ui.perfetto.dev) shows one process row per rank with the five layer lanes
stacked inside it. Timestamps are CLOCK_MONOTONIC seconds in the dumps
(system-wide on Linux, so rank processes on one host share the axis);
the export rebases to the earliest event and converts to microseconds —
the unit the trace-event format specifies.

Also renders the text per-layer summary (span time per layer, event and
byte counts) that bin/mpitrace prints after the merge.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
from typing import Any, Dict, List, Optional

from .recorder import LAYERS

_LAYER_TID = {layer: i + 1 for i, layer in enumerate(LAYERS)}


def read_dumps(trace_dir: str) -> List[Dict[str, Any]]:
    """Load every trace-r*.json under ``trace_dir`` (rank order)."""
    dumps = []
    for path in sorted(glob.glob(os.path.join(trace_dir, "trace-r*.json"))):
        with open(path) as f:
            dumps.append(json.load(f))
    dumps.sort(key=lambda d: d.get("rank", 0))
    return dumps


def merge(dumps: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Per-rank dumps -> one trace-event JSON object."""
    t0 = min((ev[0] for d in dumps for ev in d["events"]), default=0.0)
    t0 = min([t0] + [s[0] for d in dumps
                     for s in (d.get("metrics") or [])])
    out: List[Dict[str, Any]] = []
    for d in dumps:
        rank = d["rank"]
        out.append({"name": "process_name", "ph": "M", "pid": rank,
                    "args": {"name": f"rank {rank}"}})
        out.append({"name": "process_sort_index", "ph": "M", "pid": rank,
                    "args": {"sort_index": rank}})
        for layer, tid in _LAYER_TID.items():
            out.append({"name": "thread_name", "ph": "M", "pid": rank,
                        "tid": tid, "args": {"name": layer}})
        for ts, layer, name, ph, args in d["events"]:
            ev = {"name": name, "cat": layer, "ph": ph,
                  "ts": (ts - t0) * 1e6, "pid": rank,
                  "tid": _LAYER_TID.get(layer, 0)}
            if args:
                ev["args"] = args
            out.append(ev)
        # MV2T_METRICS sampler series as counter tracks: one counter
        # lane per rank beside the span lanes (ph "C" groups by pid +
        # name), so a trace and its metrics share one timeline. Flat
        # series are skipped — an all-constant counter is dead pixels.
        samples = d.get("metrics") or []
        if samples:
            active = {k for _, vals in samples for k in vals}
            flat = {k for k in active
                    if len(samples) > 1
                    and len({vals.get(k, 0)
                             for _, vals in samples}) <= 1}
            for ts, vals in samples:
                live = {k: v for k, v in vals.items() if k not in flat}
                for k, v in live.items():
                    out.append({"name": f"metrics:{k}", "ph": "C",
                                "pid": rank, "ts": (ts - t0) * 1e6,
                                "args": {"value": v}})
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def merge_dir(trace_dir: str,
              out_path: Optional[str] = None) -> Dict[str, Any]:
    """Merge every rank dump under ``trace_dir``; optionally write the
    merged JSON to ``out_path`` (the bin/mpitrace flow)."""
    merged = merge(read_dumps(trace_dir))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(merged, f)
    return merged


# ---------------------------------------------------------------------------
# per-layer text summary
# ---------------------------------------------------------------------------

def summarize(dumps: List[Dict[str, Any]]) -> str:
    """Text report: per (rank, layer) span time, event count, and bytes;
    under the ``device`` lane one row per span name (count, total,
    median): the phases of a device collective, the operator's view of
    what chipbench's phase metrics read.

    Span time pairs each 'E' with the most recent unmatched same-name 'B'
    in its (rank, layer) lane; a truncated ring (oldest events dropped)
    can orphan an 'E' — those are skipped, not an error. A lane's time
    counts its outermost spans only: a span opened inside another of its
    lane (``dev_stage`` inside ``dev_allreduce``) is that span's time
    once more, not more time."""
    lines = ["# trace summary (per rank, per layer)",
             f"# {'rank':>4} {'layer':<9} {'events':>8} {'span(s)':>10} "
             f"{'bytes':>12}"]
    for d in dumps:
        per: Dict[str, Dict[str, float]] = {}
        stacks: Dict[tuple, list] = {}
        depth: Dict[str, int] = {}
        by_name: Dict[str, List[float]] = {}     # device lane only
        for ts, layer, name, ph, args in d["events"]:
            st = per.setdefault(layer, {"n": 0, "t": 0.0, "b": 0})
            st["n"] += 1
            if args and "bytes" in args:
                st["b"] += args["bytes"]
            key = (layer, name)
            if ph == "B":
                stacks.setdefault(key, []).append(ts)
                depth[layer] = depth.get(layer, 0) + 1
            elif ph == "E":
                opens = stacks.get(key)
                if opens:
                    took = ts - opens.pop()
                    depth[layer] -= 1
                    if depth[layer] == 0:
                        st["t"] += took
                    if layer == "device":
                        by_name.setdefault(name, []).append(took)
        for layer in LAYERS:
            if layer not in per:
                continue
            st = per[layer]
            lines.append(f"  {d['rank']:>4} {layer:<9} {int(st['n']):>8} "
                         f"{st['t']:>10.6f} {int(st['b']):>12}")
            if layer == "device":
                for name, took in sorted(by_name.items()):
                    lines.append(
                        f"       . {name:<18} x{len(took):<6} "
                        f"{sum(took):>10.6f} s  median "
                        f"{statistics.median(took) * 1e6:>10.1f} us")
    return "\n".join(lines)
