"""What the host-plane readers share: the run's trace opened once more
(the harness reads its own mark and the device planes from it; the file
is still on disk when the readers run) and handed to the program's own
join, ``mvapich2_tpu/trace/xprof.py``: every rank thread's line of
``/host:CPU`` keyed by the ``seq`` and ``rank`` of its ``dev_<coll>``
annotations, the runtime's launch events between them and the
completion of what they enqueued (stamped in C++, outside the
interpreter lock), and the tie that puts a recorder
stamp on the trace's axis. Not a metric: no entry of BENCHMARK.json
names it.

``tables`` is ``None``, and so is every reader of it, where the run
traced no device, where the program has no ``trace/xprof`` or writes no
``rank`` on its annotations (the parent of the PR that added them), and
where the trace holds no annotation the recorder also stamped.
"""

from .. import harness, xplane
from . import phase, rounds

try:
    from mvapich2_tpu.trace import xprof
except ImportError:         # a program before trace/xprof.py
    xprof = None

_last = (None, None)        # the context last asked about, and its tables


class Tables:
    """One run's join. ``calls[rank][seq]`` is xprof's ``Call`` (launch,
    execute and wait events, trace axis); ``spans[rank][seq]`` the
    recorder's stamps of that call (``rounds.calls``), which ``at`` puts
    on the trace's axis."""

    def __init__(self, ctx, profile):
        self.ctx = ctx
        self.profile = profile
        self.lines = xprof.rank_lines(profile)
        self.tie = xprof.tie(profile, ctx.spans, self.lines)
        self.calls = {r: xprof.runtime_events(profile, r, self.lines)
                      for r in self.lines}
        self.spans = {r: {c["seq"]: c for c in got if "seq" in c}
                      for r, got in rounds.rank_calls(ctx).items()}

    def at(self, stamp):
        """A recorder stamp on the trace's axis."""
        return stamp + self.tie.offset_s

    def inside(self, *stamps):
        """Every recorder stamp lies in the measured window."""
        lo, hi = self.ctx.window_mono
        return all(lo <= t <= hi for t in stamps)

    def seen(self, seq):
        """When call ``seq``'s result was first seen (the earliest wait
        end, or on the TPU the runtime's completion event's start), or
        ``None`` where the trace holds neither."""
        return xprof.result_seen(self.calls, seq)

    def runs(self, ordinal):
        """The program runs on device ``ordinal``'s plane, by flow id."""
        return xprof.device_programs(self.profile, ordinal)

    def launches(self):
        """``(seq, dev_dispatch span, launch events)`` of rank 0's calls
        the trace holds whole: the launch events inside the call's
        ``dev_dispatch`` span (its two stamps on the trace's axis), the
        call inside the measured window and not one that built its
        program."""
        out = []
        mine = self.calls.get(0, {})
        for b, e, args in phase.closed(self.ctx.spans.get(0, []),
                                       "dev_dispatch"):
            call = mine.get(args.get("seq"))
            if call is None or args.get("built") or not self.inside(b, e):
                continue
            span = (self.at(b), self.at(e))
            within = [(s, t) for s, t in call.launch
                      if s >= span[0] and t <= span[1]]
            if within:
                out.append((args["seq"], span, within))
        return out


def tables(ctx):
    global _last
    if _last[0] is ctx:
        return _last[1]
    got = None
    if xprof is not None and phase.chip_traced(ctx):
        try:
            profile = xplane.load(xplane.newest_trace(harness.TRACE_DIR))
        except OSError:
            profile = None
        if profile is not None:
            got = Tables(ctx, profile)
            if got.tie is None:
                got = None
    _last = (ctx, got)
    return got
