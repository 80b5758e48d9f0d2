"""The join between a jax profile (``.xplane.pb``) and the recorder.

While a recorder is attached every rank's every device collective lies
on the profiler's host plane as a ``TraceAnnotation`` ``dev_<coll>``
that says ``seq``, ``ctx`` and ``rank`` (``coll/device.py:_run``), so
each rank thread's line of ``/host:CPU`` is keyed like the recorder's
spans. ``seq`` is a channel's own count and every communicator's
channel counts from 1: a call is ``(ctx, seq)``, ``ctx`` the
communicator's ``ctx_coll`` (``None`` in a trace of a tree before the
stat), and a job that uses two communicators ties each call to its own
spans. On
those same lines the runtime writes its own events, stamped in C++
outside the interpreter lock and on the annotation's clock: the jitted
call from entry to return, the executable's steps inside it, and the
thread's wait for a buffer. This module is the only code that knows
their names (``RUNTIME_EVENTS``); it reads a profile through
``jax.profiler.ProfileData``'s shape alone (``planes`` / ``lines`` /
``events``; ``name``, ``start_ns``, ``duration_ns``, ``stats``), so a
test hands it plain objects.

Times are seconds: on the trace's axis where they come from the
profile, on ``time.monotonic`` where from the recorder; ``tie`` says
what to add to the second to land on the first.
"""

from __future__ import annotations

import bisect
import glob
import os
import statistics
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

HOST_PLANE = "/host:CPU"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"    # one event a program run: all its ops
ANNOTATION = "dev_"         # ``_run``'s annotation: dev_<coll>, seq, ctx, rank

# The runtime's events read, by kind: a name that holds one of the
# kind's entries is of that kind. Filled from what
# ``chipbench/inspect_trace.py`` prints on the chip (TPU v5e, libtpu
# 0.0.34) and on the CPU (jax 0.9.0); a name that matches nothing is
# simply not read, and a kind whose names a later jax drops reads as
# empty, never as an error.
#   launch   the jitted call, entry to return: ``PjitFunction(<name>)``,
#            jaxlib's, on the calling thread's line whatever the backend
#   wait     the thread's wait for a buffer, begun and ended with the
#            interpreter lock released: the PJRT buffer's ``Await``. The
#            CPU client writes it (``CommonPjRtBuffer::Await``); the TPU
#            client writes nothing on a waiting thread's line
#   done     TPU: the runtime's own thread has read the chip's sync flag
#            (``ReadSyncFlag`` ends where this starts) and runs a
#            program's completion callbacks, which make its buffers
#            ready and wake whoever waits; says ``device_ordinal`` and
#            consumes the flow id that the program's enqueue produced
#            (``DoEnqueueProgram``, inside the launch or, for a program
#            with several outputs, on a pool thread behind it)
# Whatever else the runtime wrote inside a launch event is one of its
# ``execute`` steps (argument parsing, output buffers, the executable's
# run), by position and whatever its name.
RUNTIME_EVENTS: Dict[str, Tuple[str, ...]] = {
    "launch": ("PjitFunction(",),
    "wait": ("Buffer::Await",),
    "done": ("CompleteCallbacks",),
}
# Two events the profiler ties to each other carry these stats: the
# first a producer id, the second the same id as a consumer. A wait of
# the CPU client is two such marks (its start, the thread woken); on the
# TPU a chain of such pairs leads from a launch event to the completion
# of what it enqueued, whatever thread each step ran on (``_Flows``).
_FLOW_OUT, _FLOW_IN = "_p", "_c"
_FLOW_OUT_TYPE, _FLOW_IN_TYPE = "_pt", "_ct"    # ids repeat across types
_DEVICE = "device_ordinal"


Span = Tuple[float, float]
Key = Tuple[Optional[int], int]     # a call: (ctx, seq)


def _produced(stats: dict):
    """The flow an event's stats say it produces, or ``None``."""
    return (stats.get(_FLOW_OUT_TYPE), stats[_FLOW_OUT]) \
        if _FLOW_OUT in stats else None


def _consumed(stats: dict):
    return (stats.get(_FLOW_IN_TYPE), stats[_FLOW_IN]) \
        if _FLOW_IN in stats else None


class RankLine(NamedTuple):
    """One rank thread's line of the host plane."""
    name: str                               # the thread's
    # by start: (kind of RUNTIME_EVENTS or None, name, begin, end, stats)
    events: List[Tuple[Optional[str], str, float, float, dict]]
    calls: List[Tuple[Key, float, float]]   # ((ctx, seq), begin, end), by begin
    names: Dict[Key, str]                   # (ctx, seq) -> its annotation's name
    # an outer launch event's start -> the programs it enqueued that
    # the trace saw done (``Call.done``'s tuples)
    programs: Dict[float, List[Tuple[int, object, float, float, float]]]


class Call(NamedTuple):
    """What the runtime wrote on a rank's line from the start of
    annotation ``seq`` to the start of the next."""
    seq: int
    name: str                               # the annotation's: dev_<coll>
    begin: float                            # the annotation's
    end: float
    launch: List[Span]                      # outermost launch events
    execute: List[Tuple[str, float, float]]     # inside a launch
    wait: List[Span]                        # each wait, start to woken
    # a program the call's launches enqueued that the trace saw done:
    # (device ordinal, flow id, enqueue's start, completion's start and
    # end); the flow id is also its run's on the device plane
    # (``device_programs``)
    done: List[Tuple[int, object, float, float, float]]
    ctx: Optional[int] = None               # the communicator's ctx_coll


class Tie(NamedTuple):
    """``offset_s`` + a recorder stamp = the trace's axis. ``spread_s``:
    how far the middle pair lies above the least; ``pairs``: how many
    ``(rank, ctx, seq)`` both sides held."""
    offset_s: float
    spread_s: float
    pairs: int


def newest(profile_dir: str) -> str:
    """The ``.xplane.pb`` written last under ``profile_dir`` (what
    ``jax.profiler.start_trace`` was given)."""
    found = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return found[-1]


def load(path: str):
    """The profile in ``path``: an ``.xplane.pb``, or a directory a
    trace was started on."""
    import jax
    if not os.path.isfile(path):
        path = newest(path)
    return jax.profiler.ProfileData.from_file(path)


def kind_of(name: str) -> Optional[str]:
    """Which kind of ``RUNTIME_EVENTS`` an event's name is of."""
    for kind, parts in RUNTIME_EVENTS.items():
        if any(part in name for part in parts):
            return kind
    return None


def _seconds(e) -> Span:
    s = e.start_ns * 1e-9
    return s, s + e.duration_ns * 1e-9


class _Flows:
    """The host plane's events that produce or consume a flow id: who
    consumes an id, and per line the producers by start."""

    def __init__(self):
        self.consumers: Dict[object, list] = {}
        self.producers: Dict[int, list] = {}    # line -> (begin, end, id)
        self.starts: Dict[int, list] = {}       # line -> their begins

    def sort(self) -> None:
        """Once every event is in: the producers of each line by start."""
        for line, made in self.producers.items():
            made.sort(key=lambda p: p[0])
            self.starts[line] = [p[0] for p in made]

    def add(self, line: int, kind, span: Span, stats: dict) -> None:
        out, into = _produced(stats), _consumed(stats)
        if out is not None:
            self.producers.setdefault(line, []).append(span + (out,))
        if into is not None:
            self.consumers.setdefault(into, []).append(
                (line, kind, int(stats.get(_DEVICE, 0))) + span)

    def programs(self, line: int, begin: float, end: float,
                 hops: int = 4) -> Dict[object, tuple]:
        """``flow id -> (device, flow id, enqueue's start, completion's
        start and end)`` of every completion event that a chain of at
        most ``hops`` flows leads to from the producers of ``line``
        inside ``[begin, end]``: the last producer of the chain is the
        program's enqueue."""
        out = {}
        made = self.producers.get(line, ())
        first = bisect.bisect_left(self.starts.get(line, ()), begin)
        for s, t, flow in made[first:]:
            if s > end:
                break
            if t > end:
                continue
            for at, kind, dev, cs, ct in self.consumers.get(flow, ()):
                if cs < s:          # an id of an earlier trace epoch
                    continue
                if kind == "done":
                    out[flow] = (dev, flow, s, cs, ct)
                elif hops > 1 and (at, cs, ct) != (line, begin, end):
                    out.update(self.programs(at, cs, ct, hops - 1))
        return out


def rank_lines(profile) -> Dict[int, RankLine]:
    """The lines of the host plane that hold ``dev_<coll>`` annotations,
    by the ``rank`` the annotations say. A line whose annotations say no
    rank (a trace of a tree before the stat) is left out. Of the other
    lines only what carries a flow id is looked at, to follow each
    launch event of a rank line to the completion of what it enqueued
    (``RankLine.programs``)."""
    out: Dict[int, RankLine] = {}
    flows = _Flows()
    at = {}                     # rank -> its line's number
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for number, line in enumerate(plane.lines):
            events, calls, names, rank = [], [], {}, None
            for e in line.events:
                name, span, stats = e.name, _seconds(e), dict(e.stats)
                kind = kind_of(name)
                if kind is None and name.startswith(ANNOTATION) \
                        and "seq" in stats and "rank" in stats:
                    rank = int(stats["rank"])
                    key = (_ctx_of(stats), int(stats["seq"]))
                    calls.append((key,) + span)
                    names[key] = name
                    continue
                if _FLOW_OUT in stats or _FLOW_IN in stats:
                    flows.add(number, kind, span, stats)
                events.append((kind, name) + span + (stats,))
            if rank is not None:
                events.sort(key=lambda ev: ev[2])
                calls.sort(key=lambda c: c[1])
                out[rank] = RankLine(line.name, events, calls, names, {})
                at[rank] = number
    flows.sort()
    for rank, line in out.items():
        end = float("-inf")
        for kind, _name, s, t, _stats in line.events:
            if kind == "launch" and s >= end:       # an outer one
                end = t
                found = flows.programs(at[rank], s, t)
                if found:
                    line.programs[s] = sorted(found.values(),
                                              key=lambda p: p[3])
    return out


def _ctx_of(said: dict) -> Optional[int]:
    """The ``ctx`` an annotation's stats or a span's args say."""
    ctx = said.get("ctx")
    return None if ctx is None else int(ctx)


def _stamps_of(events) -> Dict[Key, float]:
    """``(ctx, seq) -> stamp`` of one rank's recorder ``dev_<coll>`` B
    events (tuples of the ring, or lists of a dump)."""
    out = {}
    for t, layer, name, ph, args in events:
        if layer == "device" and ph == "B" and args and "seq" in args \
                and name == ANNOTATION + str(args.get("coll")):
            out[_ctx_of(args), args["seq"]] = t
    return out


def tie(profile, events_by_rank: Dict[int, Sequence],
        lines: Optional[Dict[int, RankLine]] = None) -> Optional[Tie]:
    """Seconds to add to a recorder stamp to land on the trace's axis.
    Per ``(rank, ctx, seq)`` both sides hold: the annotation's start less the
    recorder's ``dev_<coll>`` B stamp. The annotation is entered a few
    lines after the stamp in the same thread, so every difference is the
    offset plus what those lines took: the least is the offset. ``None``
    where no pair is held."""
    lines = rank_lines(profile) if lines is None else lines
    diffs = []
    for rank, line in lines.items():
        stamps = _stamps_of(events_by_rank.get(rank, ()))
        diffs += [begin - stamps[key] for key, begin, _end in line.calls
                  if key in stamps]
    if not diffs:
        return None
    least = min(diffs)
    return Tie(least, statistics.median(diffs) - least, len(diffs))


def runtime_events(profile, rank: int,
                   lines: Optional[Dict[int, RankLine]] = None
                   ) -> Dict[object, Call]:
    """One rank's calls: ``seq -> Call`` where its line holds one
    communicator's calls (every cell of the benchmark, whose readers
    ask by ``seq``), ``(ctx, seq) -> Call`` where it holds those of
    more than one. On its line, from the start of
    an annotation to the start of the next (the last
    call's stretch runs to the line's end), the launch events that lie
    in no other launch event, whatever else lies inside them (the
    execute steps), the completion of each program they enqueued, and
    the waits. A wait written as two marks
    runs from the first's start to the end of the mark that consumes
    its id; one written as a span is that span. A rank with no line, or
    a kind with no event, reads as empty."""
    lines = rank_lines(profile) if lines is None else lines
    line = lines.get(rank)
    if line is None:
        return {}
    out: Dict[object, Call] = {}
    events, at = line.events, 0
    by_seq = len({ctx for (ctx, _seq), _b, _e in line.calls}) <= 1
    for k, (key, begin, end) in enumerate(line.calls):
        until = line.calls[k + 1][1] if k + 1 < len(line.calls) \
            else float("inf")
        ctx, seq = key
        call = Call(seq, line.names[key], begin, end, [], [], [], [], ctx)
        pending: Dict[object, float] = {}   # flow id -> the wait's start
        while at < len(events) and events[at][2] < begin:
            at += 1
        while at < len(events) and events[at][2] < until:
            kind, name, s, t, stats = events[at]
            at += 1
            if kind == "launch":
                if not call.launch or s >= call.launch[-1][1]:
                    call.launch.append((s, t))
                    call.done.extend(line.programs.get(s, ()))
            elif kind == "wait":
                if _FLOW_OUT in stats:
                    pending[_produced(stats)] = s
                elif _FLOW_IN in stats:
                    began = pending.pop(_consumed(stats), None)
                    if began is not None:
                        call.wait.append((began, t))
                else:
                    call.wait.append((s, t))
            elif call.launch and t <= call.launch[-1][1]:
                call.execute.append((name, s, t))
        out[seq if by_seq else key] = call
    return out


def _device_planes(profile) -> Dict[int, object]:
    out = {}
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            tail = plane.name[len(DEVICE_PLANE):].split()[0]
            if tail.isdigit():
                out[int(tail)] = plane
    return out


def device_ordinals(profile) -> List[int]:
    """The ordinals of the profile's device planes."""
    return sorted(_device_planes(profile))


def device_ops(profile, ordinal: int) -> List[Tuple[str, float, float]]:
    """``(name, begin, end)`` of the ops device ``ordinal`` ran: the
    events of its plane's ``XLA Ops`` line, by start (the plane's other
    lines cover the same time again)."""
    out = []
    plane = _device_planes(profile).get(ordinal)
    for line in plane.lines if plane is not None else ():
        if line.name == OPS_LINE:
            for e in line.events:
                s = e.start_ns * 1e-9
                out.append((e.name, s, s + e.duration_ns * 1e-9))
    return sorted(out, key=lambda op: op[1])


def result_seen(calls: Dict[int, Dict[object, Call]], seq
                ) -> Optional[float]:
    """When call ``seq``'s result was first seen (``calls`` is ``{rank:
    runtime_events(...)}``, ``seq`` a key of theirs): the earliest end,
    over every rank's line,
    of the call's wait (its last, where a call waits for several
    arrays); on a client that writes no wait on a waiting thread's line
    (the TPU's), the earliest start of the completion events of the
    call's programs: the runtime has seen a chip done and begins to wake
    whoever waits. ``None`` where neither is held: no thread had to
    wait."""
    mine = [call for by_seq in calls.values()
            if (call := by_seq.get(seq)) is not None]
    seen = [call.wait[-1][1] for call in mine if call.wait] or \
        [begin for call in mine
         for _dev, _flow, _enq, begin, _end in call.done]
    return min(seen) if seen else None


def device_programs(profile, ordinal: int) -> Dict[object, Span]:
    """``flow id -> (begin, end)`` of the program runs on device
    ``ordinal``'s plane (its ``XLA Modules`` line: one event a run, from
    its first op's start to its last op's end), keyed as the host
    plane's enqueue and completion events of the same run are."""
    out = {}
    plane = _device_planes(profile).get(ordinal)
    for line in plane.lines if plane is not None else ():
        if line.name == MODULES_LINE:
            for e in line.events:
                flow = _consumed(dict(e.stats))
                if flow is not None:
                    out[flow] = _seconds(e)
    return out


def plane_shift(calls: Dict[int, Call], runs: Dict[object, Span],
                device: int) -> Optional[Span]:
    """The shifts of ``device``'s plane, ``(low, high)`` in seconds,
    under which every program run of ``runs`` (``device_programs``)
    that a launch of ``calls`` (one rank's ``runtime_events``) enqueued
    lies between the enqueue's start and the completion's start: the
    chip cannot begin a program before the host hands it over, nor be
    seen done before it ends, and both are the runtime's stamps on the
    host plane. The plane's true shift is in the interval, and its width
    says how exactly a gap on the device can be laid against the host's
    lines. A run is paired with its enqueue by the flow id both carry,
    not by counting. ``None`` where nothing pairs or no shift fits."""
    low, high = float("-inf"), float("inf")
    for call in calls.values():
        for dev, flow, enqueued, seen, _end in call.done:
            if dev == device and flow in runs:
                began, ended = runs[flow]
                low = max(low, enqueued - began)
                high = min(high, seen - ended)
    if not float("-inf") < low <= high < float("inf"):
        return None
    return low, high
