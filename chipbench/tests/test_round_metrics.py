"""The round readers (ISSUE 36) on a context made by hand: eight ranks,
four allreduces (seq 5 to 8) two milliseconds apart, every stamp chosen
so that each reader's number can be worked out in the comments; then on
what falls off a ring, on a trace without the spans, on a run that traced
no device, and on what the program really records (a CPU rehearsal: no
time is asserted there)."""

import pytest

from chipbench import harness
from chipbench.context import DeviceTrace, RunContext
from chipbench.layer_metrics import rounds

H = 100.0               # host = trace + 100 s, so the offset is -100
RANKS = 8
PERIOD = 2.0            # ms
SEQS = (5, 6, 7, 8)
LATE = 7                # the seq in which rank 0 arrives last, 0.55 ms late
READERS = ("rank_round_us", "slice_library_us", "slice_caller_us",
           "leader_wake_us", "enqueue_to_result_over_us",
           "deposits_as_is_pct")


def ms(x):
    return H + x / 1000.0


def call(seq, rank, as_is=True, device_wait=True):
    """One allreduce of ``rank``; ``T`` is the call's own origin, ms.

    Ranks 1-7: mpi B at T - 0.07 + 0.01 r, counted in 0.04 later (the
    last, rank 7, at T + 0.04), let go at T + 1.7 + 0.01 r, out of
    ``comm.allreduce`` 0.03 after that, and back in it 0.2 later: a
    slice of 0.03 + 0.04 of the library's and 0.2 of the caller's.

    Rank 0: in at T - 0.05 (at T + 0.5 in seq ``LATE``), awake 0.05
    after the last rank's count-in (or 0.01 after its own arrival where
    it is last); then stage 0.1, dispatch 0.2, the device's 0.5,
    collect 0.01, its release 0.01."""
    T = (seq - SEQS[0]) * PERIOD
    a = {"seq": seq, "coll": "allreduce"}
    first = dict(a, tier="slot", op="sum", bytes=4096, as_is=as_is)
    if rank:
        at = T - 0.07 + 0.01 * rank
        ev = [(at, "mpi", "allreduce", "B", None),
              (at + 0.02, "device", "dev_allreduce", "B", first),
              (at + 0.03, "device", "dev_arrive", "B", a),
              (at + 0.04, "device", "dev_arrive", "E", a),
              (at + 0.04, "device", "dev_release", "B", a)]
        out = T + 1.7 + 0.01 * rank
    else:
        at = T + 0.5 if seq == LATE else T - 0.05
        up = at + 0.04 if seq == LATE else T + 0.04 + 0.05
        ev = [(at, "mpi", "allreduce", "B", None),
              (at + 0.02, "device", "dev_allreduce", "B", first),
              (at + 0.03, "device", "dev_arrive", "B", a),
              (up, "device", "dev_arrive", "E", a)]
        t = up
        for name, took, extra in (("dev_stage", 0.1, {}),
                                  ("dev_dispatch", 0.2, {"built": False}),
                                  ("dev_device_wait", 0.5, {}),
                                  ("dev_collect", 0.01, {"parts": 0})):
            if name != "dev_device_wait" or device_wait:
                ev += [(t, "device", name, "B", a),
                       (t + took, "device", name, "E", dict(a, **extra))]
            t += took
        ev.append((t, "device", "dev_release", "B", a))
        out = t + 0.01
    ev += [(out, "device", "dev_release", "E", a),
           (out, "device", "dev_allreduce", "E", a),
           (out + 0.01, "device", "dev_deliver", "B", a),
           (out + 0.02, "device", "dev_deliver", "E", dict(a, relaid=0)),
           (out + 0.03, "mpi", "allreduce", "E", None)]
    return [(ms(t), lane, name, ph, args) for t, lane, name, ph, args in ev]


def leader_stamp(seq, name, ph):
    return next(t for t, _l, n, p, _a in call(seq, 0)
                if (n, p) == (name, ph))


def made_up_context(late_ms=0.0, **over):
    """The four calls on eight ranks; the last two are the traced
    sub-window: rank 0's wait in the caller's ``block_until_ready`` runs
    from 0.01 to 0.03 ms after its ``mpi`` E, and its device is busy 0.3
    ms a call from 0.1 ms after the enqueue (``late_ms`` later on the
    device's plane)."""
    kw = {k: over.pop(k) for k in ("as_is", "device_wait") if k in over}
    spans = {r: [e for seq in SEQS
                 for e in call(seq, r, **{k: v(seq, r) for k, v in kw.items()})]
             for r in range(RANKS)}
    waits, busy = [], []
    for seq in SEQS[-2:]:
        out = leader_stamp(seq, "allreduce", "E")
        waits.append((out + 1e-5, out + 3e-5))
        enq = leader_stamp(seq, "dev_dispatch", "E") - H
        busy.append((enq + 1e-4 + late_ms / 1e3, enq + 4e-4 + late_ms / 1e3))
    lo = leader_stamp(SEQS[-2], "allreduce", "B") - H - 1e-4
    dev = DeviceTrace(0, lo, lo + 2 * PERIOD / 1e3, busy,
                      [("%mv2t_slot_reduce.1", s, e) for s, e in busy])
    args = dict(
        collective=harness.load_by_name("collectives", "allreduce"),
        config={"expect": {"least_bytes": "slot"}}, traffic={}, ranks=RANKS,
        bytes_per_rank=4096, device_kind="TPU v5 lite",
        peaks={"hbm_GBps": 819.0, "ici_GBps": 200.0},
        window_mono=(H - 1.0, H + 1.0), spans=spans, caller_waits=waits,
        devices={0: dev}, rank0_ordinal=0, traced_calls=2,
        clock_offset_s=-H)
    args.update(over)
    return RunContext(**args)


def read(name, ctx):
    return harness.load_by_name("layer_metrics", name).compute(ctx)


def test_the_round_readers_on_a_made_up_trace():
    ctx = made_up_context()
    # 7 ranks x 3 rounds of 2.0 ms; rank 0's are 2.0, 2.55 and 1.45
    assert read("rank_round_us", ctx) == pytest.approx(2000.0)
    # let go -> out of comm.allreduce 0.03, in again -> counted in 0.04
    assert read("slice_library_us", ctx) == pytest.approx(70.0)
    assert read("slice_caller_us", ctx) == pytest.approx(200.0)
    # 0.05 ms after rank 7's count-in in three calls; in seq 7 rank 0 is
    # the last to arrive and waits for nobody: 50, 50, 0, 50
    assert read("leader_wake_us", ctx) == pytest.approx(50.0)
    # the slot leader knows at its dev_device_wait E, 0.5 ms after the
    # enqueue; the device was busy 0.3 ms of them
    assert read("enqueue_to_result_over_us", ctx) == pytest.approx(200.0)
    assert read("deposits_as_is_pct", ctx) == 100.0


def test_the_last_arriver_and_the_wake_up_are_told_apart():
    """With rank 0 last in every call the wake-up reads 0 however long
    its own dev_arrive is; with rank 7 later by 0.3 ms the leader's
    wake-up is still the 0.05 ms after *its* count-in."""
    ctx = made_up_context()
    for r, evs in ctx.spans.items():
        if r == 0:      # rank 0 comes 1 ms later in every call
            ctx.spans[r] = [(t + 1e-3, *rest) for t, *rest in evs]
    assert read("leader_wake_us", ctx) == 0.0
    ctx = made_up_context()
    moved = []
    for t, lane, name, ph, args in ctx.spans[7]:
        early = (lane, name) == ("mpi", "allreduce") and ph == "B"
        moved.append((t - (3e-4 if early else 0.0), lane, name, ph, args))
    ctx.spans[7] = moved        # rank 7 enters earlier, is counted in as before
    assert read("leader_wake_us", ctx) == pytest.approx(50.0)
    # and its library slice is 0.3 ms longer: the median of 21 does not move
    assert read("slice_library_us", ctx) == pytest.approx(70.0)


def test_a_mesh_leader_knows_at_the_end_of_the_callers_wait():
    """No dev_device_wait span (the mesh channel): known ready is the end
    of rank 0's wait in block_until_ready, 0.03 ms after its mpi E: after
    the enqueue 0.5 (the made-up leader still spends them) + collect 0.01
    + its release 0.01 + the way out 0.03 + the wait 0.03 = 0.58, of
    which the device was busy 0.3."""
    ctx = made_up_context(device_wait=lambda seq, r: False)
    assert read("enqueue_to_result_over_us", ctx) == pytest.approx(280.0)
    # a wait that matches no call of the ring is left out, not guessed at
    ctx.caller_waits.insert(0, (H - 0.5, H - 0.4))
    assert read("enqueue_to_result_over_us", ctx) == pytest.approx(280.0)
    ctx.caller_waits[:] = []
    assert read("enqueue_to_result_over_us", ctx) is None


def test_a_deposit_made_by_reshape_shows():
    ctx = made_up_context(as_is=lambda seq, r: (seq, r) != (6, 3))
    assert read("deposits_as_is_pct", ctx) == pytest.approx(100 * 31 / 32)
    # a program that does not say as_is gives nothing to read
    old = {r: [(t, lane, n, p, {k: v for k, v in (a or {}).items()
                                if k != "as_is"} or None)
               for t, lane, n, p, a in evs]
           for r, evs in made_up_context().spans.items()}
    assert read("deposits_as_is_pct", made_up_context(spans=old)) is None


def test_a_seq_whose_other_half_fell_off_a_ring_is_dropped():
    ctx = made_up_context()
    # every ring but rank 0's lost seq 5 and 6 and seq 7 up to its
    # dev_release B: one whole call a rank is left, so no two successive
    for r in range(1, RANKS):
        cut = next(i for i, e in enumerate(ctx.spans[r])
                   if e[2] == "dev_release" and e[3] == "B"
                   and e[4]["seq"] == 7) + 1
        ctx.spans[r] = ctx.spans[r][cut:]
    # the slice from seq 7's release to seq 8's count-in is whole
    assert read("slice_library_us", ctx) == pytest.approx(70.0)
    assert read("slice_caller_us", ctx) == pytest.approx(200.0)
    # rank 0's three rounds are all that is left of the rounds
    assert read("rank_round_us", ctx) == pytest.approx(2000.0)
    # seq 8 alone has every rank's dev_arrive
    assert read("leader_wake_us", ctx) == pytest.approx(50.0)
    # rank 3's ring lost seq 8's dev_arrive too: nothing is left to join
    ctx.spans[3] = [e for e in ctx.spans[3]
                    if not (e[2] == "dev_arrive" and e[4]["seq"] == 8)]
    assert read("leader_wake_us", ctx) is None
    # rank 0's ring lost its dev_dispatch: no enqueue to reckon from
    ctx.spans[0] = [e for e in ctx.spans[0] if e[2] != "dev_dispatch"]
    assert read("enqueue_to_result_over_us", ctx) is None


def test_nothing_is_read_where_there_is_nothing_to_read():
    """A program without the phase spans (``mpi`` and ``dev_<coll>``
    events with no args), a run that traced no device (a CPU rehearsal
    files no host time under a metric's name), an empty trace, and a
    window that holds none of the calls."""
    old = {r: [(t, lane, n, p, None) for t, lane, n, p, _a in evs
               if n in ("allreduce", "dev_allreduce")]
           for r, evs in made_up_context().spans.items()}
    for ctx in (made_up_context(spans=old), made_up_context(devices={}),
                made_up_context(devices={}, spans={}, traced_calls=0,
                                caller_waits=[]),
                made_up_context(window_mono=(0.0, 1.0))):
        got = {name: read(name, ctx) for name in READERS}
        if ctx.devices and ctx.window_mono[0] == 0.0:
            # the lag is read over the traced sub-window, not the window
            assert got.pop("enqueue_to_result_over_us") == pytest.approx(200)
        assert set(got.values()) == {None}, got


def test_the_two_planes_a_device_plane_one_millisecond_late():
    """Each busy interval (0.3 ms) has 0.4 ms of room inside its call's
    [dev_dispatch B, known ready] (0.2 + 0.5 ms): 0.3 before it, 0.1
    after. On time the bounds hold 0; a millisecond late they lie a
    millisecond off. A shift by a whole call fits neither: the first
    and the last op would fall outside every traced call."""
    on_time = rounds.shift_bounds(made_up_context())
    assert len(on_time) == 1
    assert on_time[0] == pytest.approx((-3e-4, 1e-4))
    late = rounds.shift_bounds(made_up_context(late_ms=1.0))
    assert len(late) == 1
    assert late[0] == pytest.approx((-1.3e-3, -0.9e-3))
    # no shift within the scan puts a 0.3 ms op inside a 0.2 ms call
    ctx = made_up_context()
    ctx.devices[0].busy[:] = [(s, s + 9e-4) for s, _e in ctx.devices[0].busy]
    assert rounds.shift_bounds(ctx) == []
    assert rounds.shift_bounds(made_up_context(devices={})) is None
    assert rounds.shift_bounds(made_up_context(clock_offset_s=None)) is None


def test_the_readers_read_what_the_program_records(monkeypatch):
    """A rehearsal: the events of a real slot-channel run (four ranks on
    one CPU device, the recorder on, rank 0's waits noted as the harness
    notes them) beside a made-up device. Every reader finds what it
    joins; no time is asserted."""
    import time

    import jax
    import numpy as np
    from mvapich2_tpu import run_ranks
    from mvapich2_tpu.parallel.mesh import make_mesh
    from mvapich2_tpu.utils.config import get_config
    monkeypatch.setenv("MV2T_TRACE", "1")
    get_config().reload()
    spans, waits = {}, []

    def app(comm):
        x = jax.device_put(np.ones(1024, np.float32),
                           comm.device_channel.device)
        for _ in range(4):
            out = comm.allreduce(x)
            t1 = time.monotonic()
            jax.block_until_ready(out)
            if comm.rank == 0:
                waits.append((t1, time.monotonic()))
        spans[comm.rank] = list(comm.u.engine.tracer.events)

    try:
        run_ranks(4, app, device_mesh=make_mesh((1,), ("x",),
                                                jax.devices()[:1]))
    finally:
        monkeypatch.undo()
        get_config().reload()
    times = [e[0] for evs in spans.values() for e in evs]
    ctx = made_up_context(spans=spans, ranks=4, caller_waits=waits[-2:],
                          window_mono=(min(times), max(times)))
    got = {name: read(name, ctx) for name in READERS}
    assert all(v is not None for v in got.values()), got
    assert got["deposits_as_is_pct"] == 100.0
    assert min(got["rank_round_us"], got["slice_library_us"],
               got["slice_caller_us"], got["leader_wake_us"]) >= 0
    # each rank's four calls came out whole, seq 1 to 4, as_is said
    for r, evs in spans.items():
        mine = rounds.calls(evs, "allreduce")
        assert [c["seq"] for c in mine] == [1, 2, 3, 4]
        assert all(c["as_is"] is True and ("mpi", "E") in c for c in mine)
    assert len(rounds.known_ready(ctx)) == 2
