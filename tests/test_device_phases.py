"""Phase spans inside ``dev_<coll>`` (ISSUE 26): every blocking device
collective carries a ``seq`` equal on every rank, and between its
``dev_<coll>`` B and E the rendezvous and the leader open ``dev_arrive``,
``dev_stage``, ``dev_dispatch``, ``dev_collect`` and ``dev_release``, the
same list on the three channels (no leader waits for the device: ISSUE
50); ``dev_deliver`` follows the E; the fold channel's leader opens
``dev_chip_fold`` inside its ``dev_stage``. No test here asserts a time:
only names, order, nesting, ``seq`` and that every span closes, on the
error paths too.
"""

import os
import threading

import jax
import numpy as np
import pytest

from mvapich2_tpu.analysis import conform, core
from mvapich2_tpu.analysis.events import EventCoveragePass
from mvapich2_tpu.parallel.mesh import make_mesh
from mvapich2_tpu.runtime.universe import run_ranks
from mvapich2_tpu.utils.config import get_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 4096

# channel -> (ranks, devices of the mesh it binds to, class name)
CHANNELS = {"mesh": (4, 4, "DeviceCollChannel"),
            "slot": (4, 1, "HBMSlotChannel"),
            "fold": (8, 4, "DeviceFoldChannel")}
LEADER = ["dev_stage", "dev_dispatch", "dev_collect"]


def _mesh(channel):
    ndev = CHANNELS[channel][1]
    return make_mesh((ndev,), ("x",), jax.devices()[:ndev])


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setenv("MV2T_TRACE", "1")
    monkeypatch.setenv("MV2T_DEVICE_COLL_MIN_BYTES", "1")
    get_config().reload()
    yield
    monkeypatch.undo()
    get_config().reload()


def _device_lane(comm):
    return [e for e in comm.u.engine.tracer.events if e[1] == "device"]


def _run_two_collectives(channel):
    """allreduce then bcast on ``channel``; every rank's device lane."""
    ranks, _ndev, klass = CHANNELS[channel]
    lanes = {}

    def app(comm):
        assert type(comm.device_channel).__name__ == klass
        out = comm.allreduce(np.full(N, float(comm.rank + 1), np.float32))
        assert np.asarray(out)[0] == ranks * (ranks + 1) / 2
        b = np.full(N, float(comm.rank), np.float32)
        comm.bcast(b, root=1)
        assert b[0] == 1.0
        lanes[comm.rank] = _device_lane(comm)

    run_ranks(ranks, app, device_mesh=_mesh(channel))
    return lanes


def _spans(events):
    """[(name, seq, depth, closed children's names)] in B order, from
    one rank's device lane; raises where B and E do not nest."""
    out, stack = [], []
    for _t, _layer, name, ph, args in events:
        if ph == "B":
            rec = [name, args["seq"], len(stack), []]
            if stack:
                stack[-1][3].append(name)
            stack.append(rec)
            out.append(rec)
        elif ph == "E":
            assert stack and stack[-1][0] == name, (name, stack)
            assert args["seq"] == stack.pop()[1]
    assert not stack, stack
    return [tuple(r) for r in out]


def _violations(lanes):
    events = [conform.Event(t, rank, layer, name, ph, args)
              for rank, lane in lanes.items()
              for t, layer, name, ph, args in lane]
    return conform.check_events(events, options={"peer_timeout": 10.0},
                                ranks=frozenset(lanes))


@pytest.mark.parametrize("channel", list(CHANNELS))
def test_phases_in_order_nested_one_seq(traced, channel):
    lanes = _run_two_collectives(channel)
    for rank, lane in lanes.items():
        spans = _spans(lane)
        inside = (["dev_arrive"] + (LEADER if rank == 0 else [])
                  + ["dev_release"])
        tops = [(name, seq, kids) for name, seq, depth, kids in spans
                if depth == 0]
        assert tops == [("dev_allreduce", 1, inside), ("dev_deliver", 1, []),
                        ("dev_bcast", 2, inside), ("dev_deliver", 2, [])], \
            (rank, tops)
        # one level of phases, but for the fold leader's level 1, which
        # lies inside its dev_stage (ISSUE 38)
        deeper = [(name, kids) for name, _s, depth, kids in spans
                  if depth == 1 and kids]
        assert deeper == ([("dev_stage", ["dev_chip_fold"])] * 2
                          if channel == "fold" and rank == 0 else [])
        assert max(depth for _n, _s, depth, _k in spans) == 1 + bool(deeper)
        # a phase shares its collective's seq and names the collective
        colls = {(a["seq"], a["coll"]) for _t, _l, _n, ph, a in lane
                 if ph in "BE"}
        assert colls == {(1, "allreduce"), (2, "bcast")}
    assert _violations(lanes) == []


@pytest.mark.parametrize("channel", list(CHANNELS))
def test_dispatch_says_which_call_built(traced, channel):
    """``built`` on dev_dispatch's E: true on the first call of a
    signature, false when the program was found."""
    ranks = CHANNELS[channel][0]
    built = []

    def app(comm):
        x = np.ones(N, np.float32)
        for _ in range(3):
            comm.allreduce(x)
        if comm.rank == 0:
            built.extend(e[4]["built"] for e in _device_lane(comm)
                         if e[2] == "dev_dispatch" and e[3] == "E")

    run_ranks(ranks, app, device_mesh=_mesh(channel))
    assert built == [True, False, False]


@pytest.mark.parametrize("fault", ["leader_raises", "broken_barrier"])
def test_error_paths_close_every_span(traced, fault):
    """A leader that raises releases its peers and closes its spans; a
    rank that dies instead of arriving breaks the barrier under the
    others, whose dev_arrive and dev_allreduce still close."""
    lanes, errors = {}, {}
    gate = threading.Barrier(4)

    def app(comm):
        ch = comm.device_channel
        if fault == "leader_raises" and comm.rank == 0:
            def boom(*_a, **_k):
                raise ValueError("seeded leader failure")
            ch._program = boom
        gate.wait()
        try:
            if fault == "broken_barrier" and comm.rank == 3:
                ch.abort()          # dies before the rendezvous
            else:
                comm.allreduce(np.ones(N, np.float32))
        except RuntimeError as e:
            errors[comm.rank] = str(e)
        lanes[comm.rank] = _device_lane(comm)

    run_ranks(4, app, device_mesh=_mesh("mesh"))
    if fault == "leader_raises":
        assert sorted(errors) == [0, 1, 2, 3]
        assert all("failed on the leader" in m for m in errors.values())
        kids = _spans(lanes[0])[0][3]
        assert kids == ["dev_arrive", "dev_stage", "dev_dispatch",
                        "dev_release"]
    else:
        assert sorted(errors) == [0, 1, 2]
        assert all("aborted" in m for m in errors.values())
        assert lanes[3] == []
        assert _spans(lanes[0])[0][3] == ["dev_arrive"]
    for lane in lanes.values():
        _spans(lane)                # every B has its E, properly nested
    assert _violations(lanes) == []


# -- no leader waits: a result is a future (ISSUE 50) ----------------------

class _NotYet:
    """What an enqueue returns, as far as the library may look at it:
    flat, and not to be waited for by anyone but its caller."""

    ndim = 1

    def __init__(self):
        self.waited = []        # the threads that asked

    def block_until_ready(self):
        self.waited.append(threading.get_ident())
        raise RuntimeError("the runtime's own, after the enqueue")


@pytest.mark.parametrize("coll,per_rank", [("allreduce", False),
                                           ("alltoall", True)])
def test_the_slot_leader_hands_out_what_the_enqueue_returned(coll,
                                                             per_rank):
    """The slot leader does not touch the program's output: a result
    that raises on ``block_until_ready`` is still handed to every rank,
    the one array shared or a tuple's element a rank, and what the
    runtime reports after the enqueue reaches every rank from its own
    wait on what it was handed, as on the mesh channel."""
    ranks = 8
    outs = ([_NotYet() for _ in range(ranks)] if per_rank
            else [_NotYet()] * ranks)
    got, raised, idents = [None] * ranks, [None] * ranks, [None] * ranks

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == "HBMSlotChannel"
        if comm.rank == 0:      # the leader's program: an enqueue, no more
            out = tuple(outs) if per_rank else outs[0]
            ch._program = lambda *key: lambda *operands: out
        comm.barrier()
        x = jax.device_put(np.ones(ranks * 16, np.float32), ch.device)
        got[comm.rank] = getattr(comm, coll)(x)     # returns: nobody waited
        idents[comm.rank] = threading.get_ident()
        comm.barrier()
        assert not any(o.waited for o in outs)
        comm.barrier()
        try:
            jax.block_until_ready(got[comm.rank])
        except RuntimeError as e:
            raised[comm.rank] = str(e)

    run_ranks(ranks, app, device_mesh=_mesh("slot"))
    assert all(g is o for g, o in zip(got, outs))
    assert raised == ["the runtime's own, after the enqueue"] * ranks
    if per_rank:                # each waited for its own, and once
        assert [o.waited for o in outs] == [[i] for i in idents]
    else:
        assert sorted(outs[0].waited) == sorted(idents)


@pytest.mark.parametrize("ranks", [4, 8])
def test_a_failed_enqueue_raises_on_every_slot_rank_and_the_gate_holds(
        traced, ranks):
    """A failure at the enqueue (trace, compile, argument) is the
    leader's: every rank raises "failed on the leader", every span
    closes, and the next call on the same gate runs and is right."""
    errors, sums, lanes = {}, {}, {}

    def app(comm):
        ch = comm.device_channel
        if comm.rank == 0:
            program = ch._program

            def boom(*_a, **_k):
                ch._program = program       # this call alone
                raise ValueError("seeded enqueue failure")
            ch._program = boom
        comm.barrier()
        x = jax.device_put(np.full(N, float(comm.rank + 1), np.float32),
                           ch.device)
        try:
            comm.allreduce(x)
        except RuntimeError as e:
            errors[comm.rank] = (str(e), type(e.__cause__).__name__)
        sums[comm.rank] = float(np.asarray(comm.allreduce(x))[0])
        lanes[comm.rank] = _device_lane(comm)

    run_ranks(ranks, app, device_mesh=_mesh("slot"))
    assert errors == {r: ("device collective allreduce failed on the leader",
                          "ValueError") for r in range(ranks)}
    assert sums == {r: ranks * (ranks + 1) / 2 for r in range(ranks)}
    kids = [k for name, _s, depth, k in _spans(lanes[0])
            if name == "dev_allreduce"]
    assert kids == [["dev_arrive", "dev_stage", "dev_dispatch",
                     "dev_release"],
                    ["dev_arrive"] + LEADER + ["dev_release"]]
    for lane in lanes.values():
        _spans(lane)
    assert _violations(lanes) == []


@pytest.mark.parametrize("dtype", ["float32", "int32"])
def test_eight_threads_waiting_on_one_shared_result_get_the_same_bits(
        dtype):
    """Eight rank threads wait at once on the one array the slot leader
    shared at the enqueue: every one reads the reference's bits, and
    its own send buffer as it was."""
    ranks, n = 8, 1 << 16
    rng = np.random.default_rng(50)
    data = [rng.integers(-2 ** 20, 2 ** 20, n).astype(dtype)
            for _ in range(ranks)]
    want = np.sum(data, axis=0, dtype=dtype)
    got, ids, kept = [None] * ranks, [None] * ranks, [None] * ranks
    start = threading.Barrier(ranks)

    def app(comm):
        x = jax.device_put(data[comm.rank], comm.device_channel.device)
        for _ in range(3):
            out = comm.allreduce(x)
        ids[comm.rank] = id(out)
        start.wait()
        got[comm.rank] = np.asarray(jax.block_until_ready(out))
        kept[comm.rank] = np.asarray(x)

    run_ranks(ranks, app, device_mesh=_mesh("slot"))
    assert len(set(ids)) == 1               # the zero-copy share
    for r in range(ranks):
        assert got[r].dtype == want.dtype
        assert got[r].tobytes() == want.tobytes()
        assert kept[r].tobytes() == data[r].tobytes()


@pytest.mark.parametrize("trace", [False, True])
def test_trace_annotation_only_while_a_recorder_is_attached(
        monkeypatch, trace):
    """Untraced: no recorder, no phase object, no TraceAnnotation.
    Traced: one annotation per collective, named like the span, that
    says the call's ``seq``, its communicator's ``ctx`` (the world's
    ``ctx_coll`` here, and not ``derived``) and the rank whose thread it
    is (trace/xprof.py ties a line of the profile to its rank by it)."""
    import mvapich2_tpu.coll.device as devmod
    if trace:
        monkeypatch.setenv("MV2T_TRACE", "1")
    else:
        monkeypatch.delenv("MV2T_TRACE", raising=False)
    monkeypatch.setenv("MV2T_DEVICE_COLL_MIN_BYTES", "1")
    get_config().reload()
    made = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **kw):
        made.append((name, kw))
        return real(name, **kw)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    phases = []

    def app(comm):
        comm.allreduce(np.ones(N, np.float32))
        assert (comm.u.engine.tracer is not None) == trace
        with comm.device_channel._phase("dev_arrive") as ph:
            phases.append(ph)

    try:
        run_ranks(4, app, device_mesh=_mesh("slot"))
    finally:
        monkeypatch.undo()
        get_config().reload()
    if trace:
        assert sorted(made, key=str) == [
            ("dev_allreduce", {"seq": 1, "rank": r, "ctx": 1,
                               "derived": False}) for r in range(4)]
        assert all(isinstance(p, devmod._Phase) for p in phases)
    else:
        assert made == []
        assert phases == [None] * 4     # the shared no-op was entered


@pytest.mark.parametrize("channel", ["mesh", "slot"])
def test_release_says_the_turn_the_gate_chose(traced, channel):
    """Every rank but the leader leaves the gate with its place in the
    line the leader let go on its ``dev_release`` E (``turn``; rank 0,
    which opens the gate, says none): 0..size-2 once each a ``seq``, in
    the order the ranks came on the mesh channel's first-in-first-out
    gate and in its reverse on the slot channel's last-first one. The
    ranks come 30 ms apart, the leader first, so the order they came in
    is theirs by rank."""
    import time
    ranks = CHANNELS[channel][0]
    lanes = {}

    def app(comm):
        x = np.ones(N, np.float32)
        comm.allreduce(x)               # builds the program
        comm.barrier()
        time.sleep(0.03 * comm.rank)
        comm.allreduce(x)
        lanes[comm.rank] = _device_lane(comm)

    run_ranks(ranks, app, device_mesh=_mesh(channel))
    turns = {}                          # seq -> {rank: turn}
    for rank, lane in lanes.items():
        for _t, _layer, name, ph, args in lane:
            if name == "dev_release" and ph == "E":
                assert ("turn" in args) == (rank != 0), (rank, args)
                if rank:
                    turns.setdefault(args["seq"], {})[rank] = args["turn"]
            elif name == "dev_release":
                assert "turn" not in args       # the B is as it was
    assert sorted(turns) == [1, 2]
    for got in turns.values():
        assert sorted(got.values()) == list(range(ranks - 1))
    came = list(range(1, ranks))
    if channel == "slot":
        came.reverse()
    assert [r for r, _turn in sorted(turns[2].items(),
                                     key=lambda kv: kv[1])] == came


def test_summarize_counts_nested_spans_once_and_lists_the_phases():
    """trace/perfetto.summarize (bin/mpitrace): the device lane's time is
    its outermost spans' (3 + 1 ms here, not 3 + 2 + 1), and each span
    name of the lane gets a row with count, total and median."""
    from mvapich2_tpu.trace import perfetto
    a = {"seq": 1, "coll": "allreduce"}
    events = [[0.000, "device", "dev_allreduce", "B", dict(a, bytes=64)],
              [0.001, "device", "dev_stage", "B", a],
              [0.003, "device", "dev_stage", "E", a],
              [0.003, "device", "dev_allreduce", "E", a],
              [0.004, "device", "dev_deliver", "B", a],
              [0.005, "device", "dev_deliver", "E", a],
              [0.006, "device", "dev_stage", "E", a]]     # orphan: skipped
    text = perfetto.summarize([{"rank": 0, "events": events}])
    lane = next(ln for ln in text.splitlines() if " device " in ln)
    assert lane.split() == ["0", "device", "7", "0.004000", "64"]
    rows = {ln.split()[1]: ln.split()[2:] for ln in text.splitlines()
            if ln.lstrip().startswith(". ")}
    assert rows == {
        "dev_allreduce": ["x1", "0.003000", "s", "median", "3000.0", "us"],
        "dev_deliver": ["x1", "0.001000", "s", "median", "1000.0", "us"],
        "dev_stage": ["x1", "0.002000", "s", "median", "2000.0", "us"]}


def test_phase_names_pass_the_events_lint():
    """The literal span names reach analysis/events.py through
    ``_phase``'s call sites and DeviceLaneAutomaton's ``dev_*`` covers
    each: the pass is clean on coll/device.py, and it did see them."""
    path = os.path.join(REPO, "mvapich2_tpu", "coll", "device.py")
    mods, errs = core.scan_paths([path])
    assert not errs
    assert EventCoveragePass().run(mods) == []
    with open(path) as f:
        src = f.read()
    for name in ("dev_arrive", "dev_stage", "dev_chip_fold", "dev_dispatch",
                 "dev_collect", "dev_release", "dev_deliver"):
        assert f'self._phase("{name}")' in src
        assert conform.grammar_covers("device", name)
    assert "dev_device_wait" not in src     # went with the wait (ISSUE 50)


# -- what one event holds, and what recording it reads (ISSUE 36) --------

PHASE_ARGS = {"seq", "coll", "ctx"}
# what a site learns after its B, on the E alone
ADDED = {"dev_dispatch": "built", "dev_collect": "parts",
         "dev_deliver": "relaid"}
FOLD_ADDED = {"k", "chips", "stacked", "fused",
              "in_ring"}                           # dev_chip_fold E's own


@pytest.mark.parametrize("channel", list(CHANNELS))
def test_the_ring_holds_the_same_tuple_and_args(traced, channel):
    """Every event is ``(time.monotonic, lane, name, ph, args)``; the
    ``dev_<coll>`` B says tier, op, bytes, seq, coll, as_is and planned,
    its E and every phase event seq and coll, and a phase's E besides them
    what its site added after the B (``dev_release``'s, on every rank
    but the leader, the ``turn`` the gate gave it), which the B in the
    ring never gains. ``us`` (and the E's ``tier``) are gone: the two stamps say
    it."""
    lanes = _run_two_collectives(channel)
    for rank, lane in lanes.items():
        for ev in lane:
            t, layer, name, ph, args = ev           # five, as ever
            assert isinstance(t, float) and layer == "device"
            assert isinstance(args, dict)
            if ph == "i":       # ops/pallas_ici.py's trace-time instants
                assert name.startswith("ici_")
                continue
            assert ph in ("B", "E")
            if name in ("dev_allreduce", "dev_bcast"):
                assert set(args) == (PHASE_ARGS | (
                    {"tier", "op", "bytes", "as_is", "planned", "derived"}
                    if ph == "B"
                    else set())), ev
            elif ph == "E" and name in ADDED:
                assert set(args) == PHASE_ARGS | {ADDED[name]}, ev
            elif ph == "E" and name == "dev_chip_fold":
                assert set(args) == PHASE_ARGS | FOLD_ADDED, ev
            elif ph == "E" and name == "dev_release" and rank:
                assert set(args) == PHASE_ARGS | {"turn"}, ev
            else:
                assert set(args) == PHASE_ARGS, ev
        first = next(e[4] for e in lane if e[2] == "dev_allreduce")
        assert first["op"] == "sum" and first["bytes"] == 4 * N
        assert first["tier"] in ("vmem", "hbm", "xla", "slot")
        assert first["as_is"] is False              # a host buffer
        assert first["planned"] is False            # ... decides each call
        added = {e[2]: e[4][ADDED[e[2]]] for e in lane
                 if e[3] == "E" and e[2] in ADDED and e[4]["seq"] == 2}
        assert added == ({"dev_dispatch": True, "dev_collect": 0,
                          "dev_deliver": 0} if rank == 0
                         else {"dev_deliver": 0})


@pytest.mark.parametrize("resident,stacked", [(True, 0), (False, 4)])
def test_the_chip_fold_span_says_what_level_1_copied(traced, resident,
                                                     stacked):
    """The fold leader's ``dev_chip_fold`` E of an allreduce: two ranks
    a chip on four chips, and no planar copy where the deposits lie flat
    on their chips (ISSUE 41: they are the fold's operands; ISSUE 44:
    inside the mesh program, ``fused``; ISSUE 49: not ``in_ring`` where
    the kernels do not run and the mesh collective is XLA's); a host
    deposit is still staged, one copy a chip, and folded by a launch a
    chip."""
    ranks = CHANNELS["fold"][0]
    lanes = {}

    def app(comm):
        x = np.full(N, float(comm.rank + 1), np.float32)
        if resident:
            x = jax.device_put(x, comm.device_channel.device)
        out = comm.allreduce(x)
        assert np.asarray(out)[0] == ranks * (ranks + 1) / 2
        lanes[comm.rank] = _device_lane(comm)

    run_ranks(ranks, app, device_mesh=_mesh("fold"))
    ends = {rank: [a for _t, _l, name, ph, a in lane
                   if name == "dev_chip_fold" and ph == "E"]
            for rank, lane in lanes.items()}
    assert ends.pop(0) == [{"seq": 1, "coll": "allreduce", "ctx": 1, "k": 2,
                            "chips": 4, "stacked": stacked,
                            "fused": resident, "in_ring": False}]
    assert not any(ends.values())       # the leader's span alone


def test_the_mpi_lane_through_one_tool_and_through_two(traced):
    """The recorder's tool alone is handed the implementation bound to
    the comm; with a second tool installed the chain runs last
    installed first and ends at the same implementation. Either way
    one ``mpi`` B/E pair a call, with no args."""
    from mvapich2_tpu import profile
    order, lanes = [], {}

    def outer(name, call, args, kwargs):
        order.append((name, type(args[0]).__name__))
        return call(*args[1:], **kwargs)

    def app(comm):
        x = np.ones(N, np.float32)
        comm.allreduce(x)
        if comm.rank == 0:
            profile.install(outer)
        comm.barrier()
        try:
            comm.allreduce(x)
        finally:
            comm.barrier()
            if comm.rank == 0:
                profile.uninstall(outer)
        lanes[comm.rank] = [e for e in comm.u.engine.tracer.events
                            if e[1] == "mpi" and e[2] == "allreduce"]

    run_ranks(4, app, device_mesh=_mesh("slot"))
    assert sorted(n for n, _c in order).count("allreduce") == 4
    assert {c for _n, c in order} == {"Comm"}
    for lane in lanes.values():
        assert [(e[3], e[4]) for e in lane] == [("B", None), ("E", None)] * 2
    assert not profile._installed and profile._chain == ()


@pytest.mark.parametrize("metered", [False, True])
def test_an_untraced_unmetered_run_reads_no_clock(monkeypatch, metered):
    """``_run`` times the call for the ``lat_dev_<tier>`` histogram
    alone: with no recorder and ``metrics.LIVE`` None (MV2T_METRICS=0)
    it reads neither clock; metered, as by default, it reads
    ``perf_counter`` twice a call."""
    import sys
    import time

    import mvapich2_tpu.coll.device as devmod
    from mvapich2_tpu import metrics
    monkeypatch.delenv("MV2T_TRACE", raising=False)
    monkeypatch.setenv("MV2T_DEVICE_COLL_MIN_BYTES", "1")
    monkeypatch.setenv("MV2T_METRICS", "1" if metered else "0")
    get_config().reload()
    reads, samples = [], []

    def counting(real):
        def clock():
            if sys._getframe(1).f_code is devmod.DeviceCollChannel._run \
                    .__code__:
                reads.append(real.__name__)
            return real()
        return clock

    class _Live:
        def rec_us(self, name, us):
            samples.append(name)

    monkeypatch.setattr(time, "perf_counter", counting(time.perf_counter))
    monkeypatch.setattr(time, "monotonic", counting(time.monotonic))
    monkeypatch.setattr(metrics, "LIVE", _Live() if metered else None)

    def app(comm):
        assert comm.u.engine.tracer is None
        comm.allreduce(np.ones(N, np.float32))

    try:
        run_ranks(4, app, device_mesh=_mesh("slot"))
    finally:
        monkeypatch.undo()
        get_config().reload()
    if metered:
        assert reads == ["perf_counter"] * 8
        assert samples == ["lat_dev_slot"] * 4
    else:
        assert reads == [] and samples == []
