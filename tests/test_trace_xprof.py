"""trace/xprof.py: the join between a jax profile and the recorder
(ISSUE 53). A synthetic profile (plain objects in ``ProfileData``'s
shape) holds the cases a real one seldom shows together; one real CPU
profile of a two-rank allreduce and one ``bin/mpitrace --jax-profile``
run hold the names the table reads to what this jax writes. No test
here asserts a time of its own: only which event is whose, which stamp
lies before which, and that nothing raises where a name matches nothing.
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

from mvapich2_tpu.runtime.universe import run_ranks
from mvapich2_tpu.trace import perfetto, xprof
from mvapich2_tpu.utils.config import get_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
US = 1000                   # ns


def ev(name, start_us, dur_us, **stats):
    return NS(name=name, start_ns=start_us * US, duration_ns=dur_us * US,
              stats=list(stats.items()))


def wait_marks(start_us, end_us, flow):
    """A wait as the CPU client writes it: a mark at its start that
    produces a flow id, one at its end that consumes it."""
    return [ev("CommonPjRtBuffer::Await", start_us, 1, _pt=0, _p=flow),
            ev("CommonPjRtBuffer::Await", end_us - 1, 1, _ct=0, _c=flow)]


def launch(start_us, dur_us, k):
    """The jitted call as jaxlib writes it: the event twice, one inside
    the other, the runtime's steps inside both, and the mark that ties
    it to libtpu's part of the launch on a line of its own."""
    return [ev("PjitFunction(f)", start_us, dur_us),
            ev("PjitFunction(f)", start_us + 1, dur_us - 2),
            ev("ParseArguments", start_us + 2, 1),
            ev("PjRtCpuExecutable::Execute", start_us + 5, dur_us - 10),
            ev("SomethingInside", start_us + 6, 1),
            ev("PJRT_LoadedExecutable_Execute linkage", start_us + 8, 1,
               _pt=14, _p=1000 + k),
            ev("SomethingNobodyReads", start_us + dur_us + 1, 1)]


# Three calls, 1000 us apart, on two ranks. Rank 0 launches inside each
# annotation; seq 1 is waited for by both (marks), seq 2 by nobody, seq
# 3 by rank 0 twice (a span, then marks) and by rank 1 once (a span).
RANK0 = ([ev("dev_allreduce", 100, 400, seq=1, rank=0)] + launch(200, 100, 1)
         + wait_marks(520, 700, 11)
         + [ev("dev_allreduce", 1100, 400, seq=2, rank=0)]
         + launch(1200, 100, 2)
         + [ev("dev_allreduce", 2100, 400, seq=3, rank=0)]
         + launch(2200, 100, 3)
         + [ev("CommonPjRtBuffer::Await", 2510, 90)]
         + wait_marks(2610, 2800, 12))
RANK1 = ([ev("dev_allreduce", 110, 380, seq=1, rank=1)]
         + wait_marks(500, 690, 21)
         + [ev("dev_allreduce", 1110, 380, seq=2, rank=1),
            ev("dev_allreduce", 2110, 380, seq=3, rank=1),
            ev("CommonPjRtBuffer::Await", 2500, 250)])
OLD_TREE = [ev("dev_allreduce", 100, 400, seq=1)]       # says no rank
# As the TPU's runtime writes a launch's other half: on a nameless line
# the executable's run, tied to the launch by the mark's flow id, and
# inside it the program's enqueue (seq 1 and 2; at 250 and 1250 us); for
# seq 3 a step that hands the enqueue to a pool thread, which does it at
# 2320 us, behind the launch's return. Each enqueue's flow id comes back
# on the runtime's own thread when it has seen the chip done (at 470,
# 1480 and 2500 us) and on the device plane's run of the program.
LIBTPU = ([ev("PJRT_LoadedExecutable_Execute", 210 + 1000 * k, 85,
              _ct=14, _c=1001 + k) for k in range(3)]
          # an id repeats across kinds: this step's is the next call's
          # mark's, under another type
          + [ev("CommonPjRtLoadedExecutable::Execute", 212 + 1000 * k, 80,
                _pt=7, _p=1002 + k) for k in range(3)]
          + [ev("DoEnqueueProgram", 250 + 1000 * k, 20, _pt=12, _p=2001 + k,
                device_ordinal=0) for k in range(2)]
          + [ev("tpu::System::Execute", 2250, 30, _pt=7, _p=3003)])
POOL = [ev("tpu::System::Execute=>IssueSequencedEvent", 2310, 40,
           _ct=7, _c=3003),
        ev("DoEnqueueProgram", 2320, 20, _pt=12, _p=2003, device_ordinal=0)]
RUNTIME = [ev("ReadSyncFlag", 400, 70)] + [
    ev("CompleteCallbacks", at, 30, _ct=12, _c=flow, device_ordinal=0)
    for at, flow in ((470, 2001), (1480, 2002), (2500, 2003), (2600, 999))]
RUNS = [(300, 450), (1300, 1450), (2340, 2490)]     # on the device, us
DEVICE_OPS = [ev("%fusion", s, e - s) for s, e in RUNS]
DEVICE_RUNS = [ev("jit_f(1)", s, e - s, run_id=k, _ct=12, _c=2001 + k)
               for k, (s, e) in enumerate(RUNS)]


def synthetic(shuffled=False):
    r0 = list(reversed(RANK0)) if shuffled else RANK0
    host = NS(name="/host:CPU", lines=[
        NS(name="", events=LIBTPU), NS(name="tfrt-pool", events=POOL),
        NS(name="python", events=r0), NS(name="python", events=RANK1),
        NS(name="old", events=OLD_TREE), NS(name="futex", events=RUNTIME),
        NS(name="tf_pool", events=[ev("Rendezvous", 150, 10)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=DEVICE_OPS),
        NS(name="XLA Modules", events=DEVICE_RUNS)])
    return NS(planes=[NS(name="/host:metadata", lines=[]), host, dev])


def recorder(rank, lag_us, base_s=5000.0):
    """The recorder's side of the three calls on ``time.monotonic``
    (``base_s`` ahead of the trace's axis): each ``dev_allreduce`` B
    ``lag_us[k]`` before its annotation's start, the E 5 us after its
    end."""
    begin = 100 if rank == 0 else 110
    dur = 400 if rank == 0 else 380
    out = []
    for k, lag in enumerate(lag_us):
        a = {"seq": k + 1, "coll": "allreduce"}
        at = base_s + (begin + 1000 * k) * 1e-6
        out.append([at - 1e-5 - lag * 1e-6, "mpi", "allreduce", "B", None])
        out.append([at - lag * 1e-6, "device", "dev_allreduce", "B", a])
        out.append([at + (dur + 5) * 1e-6, "device", "dev_allreduce", "E", a])
        out.append([at + (dur + 9) * 1e-6, "mpi", "allreduce", "E", None])
    return out


@pytest.mark.parametrize("shuffled", [False, True])
def test_rank_lines_by_what_the_annotation_says(shuffled):
    lines = xprof.rank_lines(synthetic(shuffled))
    assert sorted(lines) == [0, 1]      # no rank stat, no annotation: out
    # a call is (ctx, seq); these annotations say no ctx
    assert [c[0] for c in lines[0].calls] == [(None, 1), (None, 2), (None, 3)]
    assert lines[0].calls[0][1:] == pytest.approx((100e-6, 500e-6))
    assert lines[1].calls[2][1:] == pytest.approx((2110e-6, 2490e-6))
    assert lines[0].names == {(None, 1): "dev_allreduce",
                              (None, 2): "dev_allreduce",
                              (None, 3): "dev_allreduce"}


def test_tie_is_the_least_difference_and_says_the_spread():
    prof = synthetic()
    events = {0: recorder(0, [7, 3, 40]), 1: recorder(1, [5, 9, 4])}
    tied = xprof.tie(prof, events)
    assert tied.pairs == 6
    assert tied.offset_s == pytest.approx(-5000.0 + 3e-6, abs=1e-9)
    # the differences lie 0, 1, 2, 4, 6, 37 us above the least
    assert tied.spread_s == pytest.approx(3e-6, abs=1e-9)
    assert xprof.tie(prof, {0: [], 1: []}) is None
    assert xprof.tie(prof, {5: recorder(0, [1, 1, 1])}) is None


def test_runtime_events_per_call():
    prof = synthetic()
    mine = xprof.runtime_events(prof, 0)
    assert sorted(mine) == [1, 2, 3]
    one, two, three = mine[1], mine[2], mine[3]
    # the outer of the two nested launch events, once
    assert one.launch == [pytest.approx((200e-6, 300e-6))]
    # whatever else lies inside the launch, a launch's own double but
    assert [n for n, _s, _e in one.execute] == [
        "ParseArguments", "PjRtCpuExecutable::Execute",
        "SomethingInside", "PJRT_LoadedExecutable_Execute linkage"]
    assert one.wait == [pytest.approx((520e-6, 700e-6))]
    assert two.wait == [] and len(two.launch) == 1      # nobody waited
    # what each launch enqueued and when the runtime saw it done: device,
    # flow id, enqueue's start, completion's start and end
    assert one.done == [(0, (12, 2001), pytest.approx(250e-6),
                         pytest.approx(470e-6), pytest.approx(500e-6))]
    assert [d[1:3] for d in three.done] == [
        ((12, 2003), pytest.approx(2320e-6))]
    assert three.wait == [pytest.approx((2510e-6, 2600e-6)),
                          pytest.approx((2610e-6, 2800e-6))]
    other = xprof.runtime_events(prof, 1)
    assert [c.launch for c in other.values()] == [[], [], []]
    assert [c.done for c in other.values()] == [[], [], []]
    assert other[1].wait == [pytest.approx((500e-6, 690e-6))]
    assert other[3].wait == [pytest.approx((2500e-6, 2750e-6))]
    assert xprof.runtime_events(prof, 7) == {}          # no such line


def test_a_name_that_matches_nothing_reads_as_empty(monkeypatch):
    monkeypatch.setitem(xprof.RUNTIME_EVENTS, "wait", ("NoSuchEvent",))
    monkeypatch.setitem(xprof.RUNTIME_EVENTS, "launch", ())
    mine = xprof.runtime_events(synthetic(), 0)
    assert all(c.wait == [] and c.launch == [] and c.execute == []
               and c.done == [] for c in mine.values())
    assert sorted(mine) == [1, 2, 3]


def test_the_first_to_see_a_result_and_the_plane_shift(monkeypatch):
    prof = synthetic()
    calls = {r: xprof.runtime_events(prof, r) for r in (0, 1)}
    # a thread's wait where one is written, ...
    assert xprof.result_seen(calls, 1) == pytest.approx(690e-6)
    assert xprof.result_seen(calls, 3) == pytest.approx(2750e-6)
    # ... else the runtime's seeing the chip done
    assert xprof.result_seen(calls, 2) == pytest.approx(1480e-6)
    assert xprof.result_seen(calls, 4) is None
    runs = xprof.device_programs(prof, 0)
    assert runs == {(12, 2001 + k): (pytest.approx(s * 1e-6),
                                     pytest.approx(e * 1e-6))
                    for k, (s, e) in enumerate(RUNS)}
    assert xprof.device_ordinals(prof) == [0]
    assert [op[0] for op in xprof.device_ops(prof, 0)] == ["%fusion"] * 3
    # a run may move back to its enqueue's start (250 - 300, 1250 - 1300,
    # 2320 - 2340) and on to where it was seen done (470 - 450,
    # 1480 - 1450, 2500 - 2490)
    assert xprof.plane_shift(calls[0], runs, 0) == (
        pytest.approx(-20e-6), pytest.approx(10e-6))
    assert xprof.plane_shift(calls[0], runs, 1) is None     # another chip
    assert xprof.plane_shift(calls[1], runs, 0) is None     # no launch
    assert xprof.plane_shift({}, runs, 0) is None
    late = {flow: (s + 1e-3, e + 1e-3) for flow, (s, e) in runs.items()}
    assert xprof.plane_shift(calls[0], late, 0) == (
        pytest.approx(-1020e-6), pytest.approx(-990e-6))
    long = {flow: (s, e + 50e-6) for flow, (s, e) in runs.items()}
    assert xprof.plane_shift(calls[0], long, 0) is None     # no shift fits
    # a client that writes no completion event: only the waits are left
    monkeypatch.setitem(xprof.RUNTIME_EVENTS, "done", ())
    bare = {r: xprof.runtime_events(prof, r) for r in (0, 1)}
    assert xprof.result_seen(bare, 2) is None
    assert xprof.plane_shift(bare[0], runs, 0) is None


def _rows(merged, pid, tid=None, ph="X"):
    return [e for e in merged["traceEvents"]
            if e.get("pid") == pid and e.get("ph") == ph
            and (tid is None or e.get("tid") == tid)]


def _bracketed(merged, ranks, spread_us):
    """Every call's recorder stamps bracket its annotation's row, to
    within ``spread_us``; returns how many calls were looked at."""
    looked = 0
    for rank in ranks:
        stamps = {((e["args"].get("ctx"), e["args"]["seq"]), e["ph"]): e["ts"]
                  for e in merged["traceEvents"]
                  if e.get("pid") == rank and e.get("cat") == "device"
                  and e["name"].startswith("dev_") and e.get("args")
                  and e["name"] == "dev_" + str(e["args"].get("coll"))}
        for row in _rows(merged, rank, perfetto._RUNTIME_TID):
            seq = (row["args"].get("ctx"), row["args"]["seq"])
            if not row["name"].startswith("dev_") \
                    or (seq, "B") not in stamps or (seq, "E") not in stamps:
                continue
            assert stamps[(seq, "B")] <= row["ts"] + spread_us + 1e-3
            assert stamps[(seq, "E")] >= row["ts"] + row["dur"] - spread_us \
                - 1e-3
            looked += 1
    return looked


def test_the_merge_holds_runtime_lanes_and_device_rows_on_one_clock():
    dumps = [{"rank": r, "events": recorder(r, lag)}
             for r, lag in ((0, [7, 3, 40]), (1, [5, 9, 4]))]
    merged = perfetto.merge(dumps, synthetic())
    json.dumps(merged)
    says = merged["metadata"]
    assert says["tie"]["pairs"] == 6
    assert says["tie"]["spread_us"] == pytest.approx(3.0, abs=1e-3)
    names = {(e["pid"], e["args"]["name"]) for e in merged["traceEvents"]
             if e.get("name") == "thread_name"}
    assert {(0, "runtime"), (1, "runtime"), (0, "device"), (1, "mpi"),
            (perfetto._DEVICE_PID, "XLA Ops")} <= names
    for rank, n_wait, n_launch in ((0, 3, 3), (1, 2, 0)):
        lane = _rows(merged, rank, perfetto._RUNTIME_TID)
        kinds = [e["name"] for e in lane]
        assert kinds.count("dev_allreduce") == 3
        assert kinds.count("wait") == n_wait
        assert kinds.count("launch") == kinds.count("done") == n_launch
        assert all(e["cat"] == "runtime" for e in lane)
    assert _bracketed(merged, (0, 1), says["tie"]["spread_us"]) == 6
    # the device's row, moved by the tie and by the middle of the fit
    fit = says["plane_shift"]
    assert fit["width_us"] == pytest.approx(30.0, abs=1e-3)
    assert fit["applied_us"] == pytest.approx(-5.0, abs=1e-3)
    ops = _rows(merged, perfetto._DEVICE_PID, 1)
    assert [e["name"] for e in ops] == ["%fusion"] * 3
    first_b = min(e["ts"] for e in merged["traceEvents"] if "ts" in e)
    assert first_b == pytest.approx(0.0, abs=1e-6)
    # the first op began 300 us on the trace's axis; the first recorder
    # stamp 100 - 7 - 10 us on it by the true offset, 3 us less by the
    # tie's
    assert ops[0]["ts"] == pytest.approx(300 - 5 - (100 - 17) - 3, abs=1e-2)
    # without a profile the merge is what it was
    assert "metadata" not in perfetto.merge(dumps)
    assert perfetto.merge(dumps, NS(planes=[]))["metadata"] == {"tie": None}


def test_two_communicators_calls_tie_by_ctx_and_seq():
    """Every channel counts its calls from 1: a rank that used the
    world and a communicator split from it holds two calls ``seq`` 1.
    Each ties to its own recorder spans by ``(ctx, seq)``: joined by
    ``seq`` alone, one of the two would pair with the other's stamp, a
    millisecond off."""
    line = ([ev("dev_allreduce", 100, 400, seq=1, rank=0, ctx=3, derived=0)]
            + launch(200, 100, 1)
            + [ev("dev_alltoall", 1100, 400, seq=1, rank=0, ctx=9,
                  derived=1)] + launch(1200, 100, 2)
            + [ev("dev_allreduce", 2100, 400, seq=2, rank=0, ctx=3,
                  derived=0)] + launch(2200, 100, 3))
    prof = NS(planes=[NS(name="/host:CPU",
                         lines=[NS(name="python", events=line)])])
    base, lags = 5000.0, {(3, 1): 7, (9, 1): 3, (3, 2): 5}
    starts = {(3, 1): 100, (9, 1): 1100, (3, 2): 2100}
    events = []
    for (ctx, seq), lag in lags.items():
        coll = "alltoall" if ctx == 9 else "allreduce"
        a = {"seq": seq, "coll": coll, "ctx": ctx, "derived": ctx == 9}
        at = base + starts[ctx, seq] * 1e-6
        events.append([at - lag * 1e-6, "device", f"dev_{coll}", "B", a])
        events.append([at + 405e-6, "device", f"dev_{coll}", "E", a])
    events.sort(key=lambda e: e[0])
    lines = xprof.rank_lines(prof)
    assert [c[0] for c in lines[0].calls] == [(3, 1), (9, 1), (3, 2)]
    tied = xprof.tie(prof, {0: events}, lines)
    assert tied.pairs == 3
    assert tied.offset_s == pytest.approx(-base + 3e-6, abs=1e-9)
    assert tied.spread_s == pytest.approx(2e-6, abs=1e-9)   # 0, 2, 4 us above
    mine = xprof.runtime_events(prof, 0, lines)
    assert sorted(mine) == [(3, 1), (3, 2), (9, 1)]
    assert mine[9, 1].name == "dev_alltoall" and mine[9, 1].ctx == 9
    assert mine[9, 1].seq == 1 and mine[3, 2].seq == 2
    assert mine[9, 1].launch == [pytest.approx((1200e-6, 1300e-6))]
    assert mine[3, 1].launch == [pytest.approx((200e-6, 300e-6))]
    # the merged timeline says whose call a row is, and every call's
    # recorder stamps lie round its own annotation
    merged = perfetto.merge([{"rank": 0, "events": events}], prof)
    rows = [e for e in _rows(merged, 0, perfetto._RUNTIME_TID)
            if e["name"].startswith("dev_")]
    assert [(e["args"]["ctx"], e["args"]["seq"]) for e in rows] \
        == [(3, 1), (9, 1), (3, 2)]
    assert _bracketed(merged, (0,), merged["metadata"]["tie"]["spread_us"]) \
        == 3


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setenv("MV2T_TRACE", "1")
    monkeypatch.setenv("MV2T_DEVICE_COLL_MIN_BYTES", "1")
    get_config().reload()
    yield
    monkeypatch.undo()
    get_config().reload()


def test_a_real_cpu_profile_of_a_two_rank_allreduce(traced, tmp_path):
    """What this jax writes: every rank's annotations with ``rank`` and
    ``seq``, a launch event inside rank 0's ``dev_dispatch``, and a wait
    event behind at least one rank's annotation (a result that is there
    before it is asked for leaves none, so only one is asked of the
    calls together)."""
    calls = 6
    spans = {}
    world_ctx = []

    def app(comm):
        if comm.rank == 0:
            world_ctx.append(comm.ctx_coll)
        x = jax.device_put(np.ones(1 << 22, np.float32),
                           comm.device_channel.device)
        jax.block_until_ready(comm.allreduce(x))     # builds the program
        comm.barrier()
        if comm.rank == 0:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        comm.barrier()
        try:
            for _ in range(calls):
                jax.block_until_ready(comm.allreduce(x))
            comm.barrier()
        finally:
            if comm.rank == 0:
                jax.profiler.stop_trace()
        spans[comm.rank] = list(comm.u.engine.tracer.events)

    run_ranks(2, app, device_mesh=True)
    prof = xprof.load(str(tmp_path))
    lines = xprof.rank_lines(prof)
    assert sorted(lines) == [0, 1]
    for rank in (0, 1):     # every call on the world's channel, by its ctx
        assert {c[0][0] for c in lines[rank].calls} == {world_ctx[0]}
        assert [c[0][1] for c in lines[rank].calls] \
            == list(range(2, calls + 2))
    tied = xprof.tie(prof, spans, lines)
    assert tied.pairs == 2 * calls and 0 <= tied.spread_s < 1e-3
    mine = xprof.runtime_events(prof, 0, lines)
    dispatch = {}
    for t, _layer, name, ph, args in spans[0]:
        if name == "dev_dispatch":
            dispatch[(args["seq"], ph)] = t + tied.offset_s
    for seq, call in mine.items():
        assert len(call.launch) == 1 and call.execute
        assert dispatch[(seq, "B")] <= call.launch[0][0]
        assert call.launch[0][1] <= dispatch[(seq, "E")]
    waited = [w for r in (0, 1)
              for c in xprof.runtime_events(prof, r, lines).values()
              for w in c.wait]
    assert waited and all(b < e for b, e in waited)
    assert xprof.device_ordinals(prof) == []        # the CPU has no plane


def test_a_real_cpu_profile_of_two_communicators(traced, tmp_path):
    """What this jax writes of a job that calls on the world and on a
    dup of it in turn: both channels count from 1, every annotation says
    its ``ctx``, and every call ties to the recorder's span of its own
    communicator."""
    calls = 3
    spans, ctxs = {}, {}

    def app(comm):
        x = jax.device_put(np.ones(1 << 16, np.float32),
                           comm.device_channel.device)
        dup = comm.dup()
        ctxs[comm.rank] = (comm.ctx_coll, dup.ctx_coll)
        comm.barrier()
        if comm.rank == 0:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        comm.barrier()
        try:
            for _ in range(calls):
                jax.block_until_ready(comm.allreduce(x))
                jax.block_until_ready(dup.allreduce(x))
            comm.barrier()
        finally:
            if comm.rank == 0:
                jax.profiler.stop_trace()
        spans[comm.rank] = list(comm.u.engine.tracer.events)

    run_ranks(2, app, device_mesh=True)
    prof = xprof.load(str(tmp_path))
    lines = xprof.rank_lines(prof)
    world, dup = ctxs[0]
    assert world != dup and ctxs[1] == ctxs[0]
    want = [(c, k) for k in range(1, calls + 1) for c in (world, dup)]
    for rank in (0, 1):
        assert [c[0] for c in lines[rank].calls] == want
    tied = xprof.tie(prof, spans, lines)
    assert tied.pairs == 2 * 2 * calls and 0 <= tied.spread_s < 1e-3
    mine = xprof.runtime_events(prof, 0, lines)
    assert sorted(mine) == sorted(want)
    assert {c.ctx for c in mine.values()} == {world, dup}


def test_mpitrace_merges_a_jax_profile_of_a_two_rank_run(tmp_path):
    """Acceptance: ``bin/mpitrace --jax-profile`` on a CPU two-rank run
    writes one JSON with, for each rank, the recorder's lanes and a
    ``runtime`` lane, every call's recorder stamps round its annotation
    once moved by the tie."""
    out = tmp_path / "merged.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", MV2T_DEVICE_COLL_MIN_BYTES="1")
    env.pop("MV2T_JAX_PROFILE", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "mpitrace"), "-np", "2",
         "--out", str(out), "--jax-profile", str(tmp_path / "prof"), "--",
         "--vpod", os.path.join(REPO, "benchmarks", "osu_allreduce.py"),
         "-m", "4096", "-i", "3", "-x", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "No Errors" in r.stdout and "# jax profile" in r.stdout
    merged = json.load(open(out))
    says = merged["metadata"]
    assert says["tie"]["pairs"] > 0 and "plane_shift" not in says
    lanes = {(e["pid"], e["args"]["name"]) for e in merged["traceEvents"]
             if e.get("name") == "thread_name"}
    for rank in (0, 1):
        assert {(rank, "mpi"), (rank, "device"), (rank, "runtime")} <= lanes
    rows = _rows(merged, 0, perfetto._RUNTIME_TID)
    assert {"dev_allreduce", "launch"} <= {e["name"] for e in rows}
    assert _bracketed(merged, (0, 1), says["tie"]["spread_us"]) \
        == says["tie"]["pairs"]
