"""Merge per-rank trace dumps into one Chrome trace-event / Perfetto JSON.

Lane model: rank -> pid, layer -> tid, so `chrome://tracing` (or
ui.perfetto.dev) shows one process row per rank with the five layer lanes
stacked inside it. Timestamps are CLOCK_MONOTONIC seconds in the dumps
(system-wide on Linux, so rank processes on one host share the axis);
the export rebases to the earliest event and converts to microseconds —
the unit the trace-event format specifies.

Given a jax profile of the same run (``MV2T_JAX_PROFILE``) the merge
puts it on the same axis (trace/xprof.py's tie): beside each rank's
lanes a ``runtime`` lane with what the runtime wrote on that rank's
thread (the jitted call, the executable's steps, the thread's waits for
a buffer), and one row per device with the ops it ran.

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
_RUNTIME_TID = len(LAYERS) + 1      # the lane below a rank's layers
_DEVICE_PID = 1 << 20               # device rows: this + the ordinal


def read_dumps(trace_dir: str) -> List[Dict[str, Any]]:
    """Load every trace-r*.json under ``trace_dir`` (rank order)."""
    dumps = []
    for path in sorted(glob.glob(os.path.join(trace_dir, "trace-r*.json"))):
        with open(path) as f:
            dumps.append(json.load(f))
    dumps.sort(key=lambda d: d.get("rank", 0))
    return dumps


def merge(dumps: List[Dict[str, Any]], profile=None) -> Dict[str, Any]:
    """Per-rank dumps -> one trace-event JSON object; with ``profile``
    (a jax profile of the run, ``xprof.load``) the runtime's lanes and
    the devices' rows beside them (``_profile_rows``)."""
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
    merged = {"traceEvents": out, "displayTimeUnit": "ms"}
    if profile is not None:
        merged["metadata"] = _profile_rows(out, dumps, profile, t0)
    return merged


def _profile_rows(out: List[Dict[str, Any]], dumps: List[Dict[str, Any]],
                  profile, t0: float) -> Dict[str, Any]:
    """Append the profile's part of the merge to ``out`` and return what
    the file says about the fit. Per rank whose thread's line the
    profile holds: a ``runtime`` lane of complete events, the
    annotation, the launch, execute, wait and completion events of
    every call, moved onto the recorder's clock by ``xprof.tie``. Per
    device: a row of its ``XLA Ops``, moved by the tie and by the middle
    of ``xprof.plane_shift``, fitted on the lowest device between the
    enqueue and the completion of each program rank 0 launched there. Where the
    profile ties to no recorder stamp nothing is appended."""
    from . import xprof
    lines = xprof.rank_lines(profile)
    tied = xprof.tie(profile, {d["rank"]: d["events"] for d in dumps},
                     lines)
    if tied is None:
        return {"tie": None}
    says: Dict[str, Any] = {"tie": {"offset_s": tied.offset_s,
                                    "spread_us": tied.spread_s * 1e6,
                                    "pairs": tied.pairs}}

    def us(t: float, shift: float = 0.0) -> float:
        return (t + shift - tied.offset_s - t0) * 1e6

    def row(name, cat, pid, tid, s, t, shift=0.0, **args):
        ev = {"name": name, "cat": cat, "ph": "X", "pid": pid, "tid": tid,
              "ts": us(s, shift), "dur": (t - s) * 1e6}
        if args:
            ev["args"] = args
        out.append(ev)

    calls = {r: xprof.runtime_events(profile, r, lines) for r in lines}
    for rank, mine in sorted(calls.items()):
        out.append({"name": "thread_name", "ph": "M", "pid": rank,
                    "tid": _RUNTIME_TID, "args": {"name": "runtime"}})
        for call in mine.values():
            said = {"seq": call.seq}    # and whose call it is, if said
            if call.ctx is not None:
                said["ctx"] = call.ctx
            row(call.name, "runtime", rank, _RUNTIME_TID, call.begin,
                call.end, **said)
            for s, t in call.launch:
                row("launch", "runtime", rank, _RUNTIME_TID, s, t, **said)
            for name, s, t in call.execute:
                row(name, "runtime", rank, _RUNTIME_TID, s, t, **said)
            for s, t in call.wait:
                row("wait", "runtime", rank, _RUNTIME_TID, s, t, **said)
            for dev, _flow, _enqueued, s, t in call.done:
                row("done", "runtime", rank, _RUNTIME_TID, s, t,
                    device=dev, **said)

    ops_of = {o: xprof.device_ops(profile, o)
              for o in xprof.device_ordinals(profile)}
    ordinals = sorted(ops_of)
    shift = 0.0
    if ordinals:
        fit = xprof.plane_shift(
            calls.get(0, {}), xprof.device_programs(profile, ordinals[0]),
            ordinals[0])
        if fit is None:
            says["plane_shift"] = None      # the device rows: as traced
        else:
            shift = (fit[0] + fit[1]) / 2
            says["plane_shift"] = {"low_us": fit[0] * 1e6,
                                   "high_us": fit[1] * 1e6,
                                   "width_us": (fit[1] - fit[0]) * 1e6,
                                   "applied_us": shift * 1e6,
                                   "fitted_on_device": ordinals[0]}
    for ordinal in ordinals:
        pid = _DEVICE_PID + ordinal
        out.append({"name": "process_name", "ph": "M", "pid": pid,
                    "args": {"name": f"{xprof.DEVICE_PLANE}{ordinal}"}})
        out.append({"name": "process_sort_index", "ph": "M", "pid": pid,
                    "args": {"sort_index": pid}})
        out.append({"name": "thread_name", "ph": "M", "pid": pid, "tid": 1,
                    "args": {"name": xprof.OPS_LINE}})
        for name, s, t in ops_of[ordinal]:
            row(name, "device_ops", pid, 1, s, t, shift)
    return says


def merge_dir(trace_dir: str, out_path: Optional[str] = None,
              profile=None) -> Dict[str, Any]:
    """Merge every rank dump under ``trace_dir``; optionally write the
    merged JSON to ``out_path`` (the bin/mpitrace flow)."""
    merged = merge(read_dumps(trace_dir), profile)
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
