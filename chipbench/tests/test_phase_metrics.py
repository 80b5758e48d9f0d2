"""The phase-span and kernel readers on a context made by hand: three
ranks, two collectives (seq 7 and 8) in a 20 ms sub-window whose trace
axis leads the host's clock by 100 s, ops with and without the
``mv2t_`` token. Every expected number is worked out in the comments."""

import pytest

from chipbench import breakdown, harness
from chipbench.context import DeviceTrace, RunContext

MiB = 1 << 20
H = 100.0           # host = trace + 100 s, so the offset is -100
PHASE_READERS = ("entry_us", "arrive_wait_us", "leader_stage_us",
                 "leader_dispatch_us", "leader_device_wait_us",
                 "leader_collect_us", "release_us", "deliver_us")
DEVICE_READERS = ("kernel_us", "kernel_roofline_pct")


def ms(x):
    return H + x / 1000.0


def collective(seq, t0, rank, leader_ms, built=False):
    """One allreduce of ``rank`` starting ``t0`` ms into the window.
    Rank 0 (the leader): entry 0.5, arrive 1.0, stage 2.0, dispatch 0.4,
    device wait 1.5, collect 0.1, release ``leader_ms``-dependent below.
    Other ranks: arrive, then release until the leader is done."""
    a = {"seq": seq, "coll": "allreduce"}
    ev = [(ms(t0), "mpi", "allreduce", "B", None),
          (ms(t0 + 0.5), "device", "dev_allreduce", "B", dict(a, tier="slot")),
          (ms(t0 + 0.5), "device", "dev_arrive", "B", a),
          (ms(t0 + 1.5), "device", "dev_arrive", "E", a)]
    at = t0 + 1.5
    if rank == 0:
        for name, took, extra in (("dev_stage", 2.0, {}),
                                  ("dev_dispatch", 0.4, {"built": built}),
                                  ("dev_device_wait", 1.5, {}),
                                  ("dev_collect", 0.1, {})):
            ev += [(ms(at), "device", name, "B", a),
                   (ms(at + took), "device", name, "E", dict(a, **extra))]
            at += took
        assert at == pytest.approx(t0 + leader_ms)
    # rank r leaves the second barrier 0.2 ms x (r + 1) after the leader
    # entered it
    out = t0 + leader_ms + 0.2 * (rank + 1)
    ev += [(ms(at), "device", "dev_release", "B", a),
           (ms(out), "device", "dev_release", "E", a),
           (ms(out), "device", "dev_allreduce", "E", dict(a, tier="slot")),
           (ms(out + 0.1), "device", "dev_deliver", "B", a),
           (ms(out + 0.4), "device", "dev_deliver", "E", a),
           (ms(out + 0.5), "mpi", "allreduce", "E", None)]
    return ev


def made_up_context(**over):
    spans = {r: collective(7, 0.0, r, 5.5) + collective(8, 10.0, r, 5.5)
             for r in range(3)}
    # the device: a staging copy, then the kernel, per collective. The
    # leader's dispatch ends 3.9 ms (13.9 ms) into the window
    ops = [("%copy.3 = f32[8,1024]", 0.0020, 0.0040),
           ("%mv2t_slot_reduce.1 = f32[1024,128] custom-call", 0.0045, 0.0053),
           ("%copy.3 = f32[8,1024]", 0.0120, 0.0140),
           ("%mv2t_slot_reduce.1 = f32[1024,128] custom-call", 0.0146, 0.0154)]
    dev = DeviceTrace(0, 0.0, 0.020,
                      [(0.0020, 0.0040), (0.0045, 0.0053),
                       (0.0120, 0.0140), (0.0146, 0.0154)], ops)
    args = dict(
        collective=harness.load_by_name("collectives", "allreduce"),
        config={"expect": {"least_bytes": "slot"}}, traffic={}, ranks=8,
        bytes_per_rank=64 * MiB, device_kind="TPU v5 lite",
        peaks={"hbm_GBps": 819.0, "ici_GBps": 200.0},
        window_mono=(H - 1.0, H + 1.0), spans=spans, devices={0: dev},
        rank0_ordinal=0, traced_calls=2, clock_offset_s=-H)
    args.update(over)
    return RunContext(**args)


def read(name, ctx):
    return harness.load_by_name("layer_metrics", name).compute(ctx)


def test_phase_readers_on_a_made_up_trace():
    ctx = made_up_context()
    assert read("entry_us", ctx) == pytest.approx(500.0)
    assert read("arrive_wait_us", ctx) == pytest.approx(1000.0)
    assert read("leader_stage_us", ctx) == pytest.approx(2000.0)
    assert read("leader_dispatch_us", ctx) == pytest.approx(400.0)
    assert read("leader_device_wait_us", ctx) == pytest.approx(1500.0)
    assert read("leader_collect_us", ctx) == pytest.approx(100.0)
    assert read("deliver_us", ctx) == pytest.approx(300.0)
    # rank 0 enters the second barrier at 5.5 ms; the last rank (2) is
    # out 0.2 x 3 = 0.6 ms later, not rank 0's own 0.2 ms
    assert read("release_us", ctx) == pytest.approx(600.0)
    # the old span is the sum of its parts: 1.0 + 2.0 + 0.4 + 1.5 + 0.1
    # + rank 0's own 0.2 ms of release
    assert read("rendezvous_span_us", ctx) == pytest.approx(5200.0)


def test_dispatch_leaves_out_the_call_that_built():
    spans = {0: collective(7, 0.0, 0, 5.5, built=True)
             + collective(8, 10.0, 0, 5.5)}
    slow = [(t + (0.004 if (n, p, (a or {}).get("seq")) ==
                  ("dev_dispatch", "E", 7) else 0.0), lay, n, p, a)
            for t, lay, n, p, a in spans[0]]
    # seq 7's dispatch now takes 4.4 ms, but it built: only seq 8 counts
    assert read("leader_dispatch_us", made_up_context(spans={0: slow})) \
        == pytest.approx(400.0)
    only_built = {0: collective(7, 0.0, 0, 5.5, built=True)}
    assert read("leader_dispatch_us",
                made_up_context(spans=only_built)) is None


def test_release_join_drops_what_fell_off_the_ring():
    ctx = made_up_context()
    # rank 0's ring lost the first collective up to its dev_release B
    cut = next(i for i, e in enumerate(ctx.spans[0])
               if e[2] == "dev_release" and e[3] == "B") + 1
    ctx.spans[0] = ctx.spans[0][cut:]
    assert read("release_us", ctx) == pytest.approx(600.0)    # seq 8 alone
    # rank 2's ring lost its E of seq 8 as well: nothing is left to join
    ctx.spans[2] = [e for e in ctx.spans[2]
                    if not (e[2] == "dev_allreduce" and e[3] == "E"
                            and e[4]["seq"] == 8)]
    assert read("release_us", ctx) is None
    # and a half-open pair is dropped, not guessed at
    assert read("arrive_wait_us", ctx) == pytest.approx(1000.0)


def test_kernel_readers_on_a_made_up_trace():
    ctx = made_up_context()
    # two kernel ops of 0.8 ms in two collectives; the copies (4 ms) have
    # no token and are left out
    assert read("kernel_us", ctx) == pytest.approx(800.0)
    assert read("device_busy_us", ctx) == pytest.approx(2800.0)
    # 9 x 64 MiB over 819 GB/s = 737.46 us of the kernel's 800 us
    assert read("kernel_roofline_pct", ctx) == pytest.approx(92.18, rel=1e-3)
    ring = made_up_context(config={"expect": {"least_bytes": "ring"}},
                           ranks=4)
    assert read("kernel_roofline_pct", ring) == pytest.approx(
        96 * MiB / 200e9 / 800e-6 * 100)
    # a kernel op that straddles the sub-window's end counts only inside
    dev = ctx.devices[0]
    dev.ops.append(("%mv2t_slot_reduce.1", 0.0198, 0.0210))
    assert read("kernel_us", ctx) == pytest.approx(900.0)


def test_readers_return_nothing_on_a_program_without_the_spans():
    """The parent of the PR that added the spans: ``mpi`` and
    ``dev_<coll>`` events only (no ``seq``), ops under XLA's names."""
    old = {r: [e for e in evs if e[2] in ("allreduce", "dev_allreduce")]
           for r, evs in made_up_context().spans.items()}
    old = {r: [(t, lay, n, p, None) for t, lay, n, p, _a in evs]
           for r, evs in old.items()}
    ctx = made_up_context(spans=old)
    ctx.devices[0].ops[:] = [("%f.1 = f32[1024,128] custom-call", s, e)
                             for _n, s, e in ctx.devices[0].ops]
    got = {n: read(n, ctx) for n in PHASE_READERS + DEVICE_READERS}
    assert got.pop("entry_us") == pytest.approx(500.0)   # both predate it
    assert set(got.values()) == {None}
    empty = made_up_context(devices={}, spans={}, traced_calls=0)
    for name in PHASE_READERS + DEVICE_READERS:
        assert read(name, empty) is None
    outside = made_up_context(window_mono=(0.0, 1.0))
    for name in PHASE_READERS:
        assert read(name, outside) is None


def test_readers_read_what_the_program_records(monkeypatch):
    """The spans of a real slot-channel run (four ranks on one CPU
    device, the recorder on) beside the made-up device: every phase
    reader finds its span. No time is asserted."""
    import jax
    import numpy as np
    from mvapich2_tpu import run_ranks
    from mvapich2_tpu.parallel.mesh import make_mesh
    from mvapich2_tpu.utils.config import get_config
    monkeypatch.setenv("MV2T_TRACE", "1")
    get_config().reload()
    spans = {}

    def app(comm):
        x = jax.device_put(np.ones(1024, np.float32),
                           comm.device_channel.device)
        for _ in range(3):
            jax.block_until_ready(comm.allreduce(x))
        spans[comm.rank] = list(comm.u.engine.tracer.events)

    try:
        run_ranks(4, app, device_mesh=make_mesh((1,), ("x",),
                                                jax.devices()[:1]))
    finally:
        monkeypatch.undo()
        get_config().reload()
    times = [e[0] for evs in spans.values() for e in evs]
    ctx = made_up_context(spans=spans, ranks=4,
                          window_mono=(min(times), max(times)))
    got = {name: read(name, ctx) for name in PHASE_READERS}
    assert all(v is not None and v >= 0 for v in got.values()), got


def test_a_run_that_traced_no_device_files_no_phase_time():
    """A CPU rehearsal: the program records the spans, the trace has no
    device plane; test_rehearsal.py expects rendezvous_span_us alone."""
    ctx = made_up_context(devices={})
    for name in PHASE_READERS + DEVICE_READERS:
        assert read(name, ctx) is None
    assert read("rendezvous_span_us", ctx) == pytest.approx(5200.0)


def test_idle_gaps_are_labelled_by_the_phase_rank0_was_in():
    """breakdown.py is untouched: it already picks the innermost open
    span, so the phases relabel what used to read dev_allreduce."""
    gaps = dict(breakdown.idle_gaps(made_up_context()))
    # idle 0-2 ms (middle 1.0 ms: dev_arrive), 4.0-4.5 (4.25: dispatch
    # ended at 3.9, so dev_device_wait), 5.3-12 (8.65: between calls),
    # 14.0-14.6 (14.3: dev_device_wait), 15.4-20 (17.7: between calls)
    assert gaps == {
        "device:dev_arrive": pytest.approx(0.002),
        "device:dev_device_wait": pytest.approx(0.0011),
        "harness loop between calls": pytest.approx(0.0113)}


def test_every_reader_of_benchmark_json_is_there_and_named():
    bench = harness.read_json(harness.ROOT, "BENCHMARK.json")
    cells = {w["name"] for w in bench["workloads"]}
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert harness.load_by_name("layer_metrics", m["name"]).NAME \
            == m["name"]
        assert m["moves"] in end_to_end
        assert set(m.get("workloads", cells)) <= cells
