"""The NPB FT transpose cell (``osu1.alltoall_split.128MiB.dev``) end to
end at a size the CPU holds: eight ranks on one device, the alltoall on
``commslice1 = comm.split(me1, me2)`` and not on the world. The cell is
``correct``; its lower-precision control is not; with the library's
``derive`` hook answering ``None`` (no device channel for a derived
communicator: the parent of ISSUE 55) it is not ``correct`` and the
level pvar and the fallback count both say so; ``derived_calls_pct``
reads 100 from a traced rehearsal and ``None`` from a world cell's
events; and the sixteenth cell's entry is what the records asked for.
``test_rehearsal_slot_alltoall.py`` does the same for the twin on the
world."""

import json
import time

import jax
import pytest

from chipbench import check, control, harness
from chipbench.context import DeviceTrace, RunContext

CELL = "osu1.alltoall_split.128MiB.dev"
TWIN = "osu1.alltoall.128MiB.dev"
ROW_256 = "osu4.allreduce.256MiB.dev"
E2E = {"lat_us_p50", "lat_us_p95", "busbw_GBps", "setup_s"}
MiB = 1 << 20
RANKS = 8


def one_device(bytes_per_rank=RANKS * 4096 * 4):
    from mvapich2_tpu.parallel.mesh import make_mesh
    return harness.Rehearsal(
        bytes_per_rank=bytes_per_rank,
        device_mesh=make_mesh((1,), ("x",), jax.devices()[:1]))


def run(seed, trace=False, seconds=0.3, cell=CELL, **kw):
    return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            rehearsal=one_device(**kw))


def test_the_cell_end_to_end():
    from mvapich2_tpu import mpit
    names = ("coll_level_chip", "dev_coll_derived")
    before = [mpit.pvar(n).read() for n in names]
    r = run(2**31 + 55)
    json.dumps(r)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == E2E
    assert all(m["value"] > 0 for m in r["metrics"].values())
    # every call of the run, warm-up and window, ran on commslice1's channel
    rose = [mpit.pvar(n).read() - b for n, b in zip(names, before)]
    assert rose[0] == rose[1] >= RANKS * (3 + r["attempted"])


def test_the_call_makes_the_two_communicators_of_setup():
    """``commslice1`` spans the world in world order, ``commslice2`` is
    one rank each, both made once a rank; the transpose runs on the
    first and comes back on the device."""
    import numpy as np

    from mvapich2_tpu import run_ranks
    coll = harness.load_by_name("collectives", "alltoall_split")
    assert coll.NAME == "alltoall"      # the spans' name
    world = harness.load_by_name("collectives", "alltoall")
    assert coll.reference is world.reference
    assert coll.least_bytes is world.least_bytes
    seen = [None] * RANKS

    def app(comm):
        dev = comm.device_channel.device
        x = jax.device_put(np.arange(RANKS * 128, dtype=np.float32)
                           + 1000 * comm.rank, dev)
        first = coll.call(comm, x)
        s1, s2 = comm.commslice1, comm.commslice2
        coll.call(comm, x)
        assert comm.commslice1 is s1 and comm.commslice2 is s2
        seen[comm.rank] = (s1.rank, s1.size, s2.size,
                           type(s1.device_channel).__name__,
                           s2.device_channel, first.devices() == {dev},
                           np.asarray(first))
    run_ranks(RANKS, app, device_mesh=one_device().device_mesh)
    want = world.reference([np.arange(RANKS * 128, dtype=np.float32)
                            + 1000 * r for r in range(RANKS)])
    for r, (rank1, size1, size2, chan, chan2, home, got) in enumerate(seen):
        assert (rank1, size1, size2, chan, chan2, home) == \
            (r, RANKS, 1, "HBMSlotChannel", None, True)
        assert np.array_equal(got, want[r])


def test_control_fails_at_a_size_a_test_can_hold():
    """The payload carried in bfloat16: whole numbers up to 2^20 keep 8
    of their 21 bits."""
    for seed in (11, 12, 2**31 + 5):
        compared = control.control_once(CELL, seed, bytes_per_rank=65536)
        assert not check.verdict(compared)
        assert compared[0].value > 0.9 * 16384


def test_without_a_derived_channel_the_cell_is_not_correct(monkeypatch):
    """``derive`` answers ``None``, as a library that binds the world
    alone: ``commslice1`` takes the host arm and hands numpy back. The
    run ends on the first result that is no device array, and the
    comparison says why twice: ``dev_coll_derived`` rose by 0 and the
    ``dev_coll_fallback_*`` family by one a rank."""
    from mvapich2_tpu.coll.device import HBMSlotChannel
    monkeypatch.setattr(HBMSlotChannel, "derive",
                        lambda self, members, ctx: None)
    said = []
    monkeypatch.setattr(harness, "say", said.append)
    r = run(21)
    assert r["correct"] is False and r["metrics"] == {}
    failed = [ln for ln in said if ln.startswith("correct:")
              and ln.endswith("FAILED")]
    derived = [ln for ln in failed if ln.startswith("correct: dev_coll_derived")]
    fallback = [ln for ln in failed
                if ln.startswith("correct: dev_coll_fallback_*")]
    assert len(derived) == 1 and "= 0 " in derived[0]
    assert len(fallback) == 1 and f"= {RANKS} " in fallback[0]
    # coll_level_chip, too: nothing ran on a slot channel
    assert any(ln.startswith("correct: coll_level_chip") for ln in failed)


def with_a_device_plane(monkeypatch, seen):
    """A stand-in for rank 0's device plane (the CPU has none): 40
    made-up ops in a one-second sub-window."""
    sound = harness._reduce_trace

    def standing_in(ctx, sh, device_ids, need_devices):
        sound(ctx, sh, device_ids, need_devices)
        ops = [("%fusion = f32[8,8,4096]", 0.01 * i, 0.01 * i + 0.005)
               for i in range(40)]
        ctx.devices[device_ids[0]] = DeviceTrace(
            device_ids[0], 0.0, 1.0, [(s, e) for _n, s, e in ops], ops)
        seen.append(ctx)
    monkeypatch.setattr(harness, "_reduce_trace", standing_in)


def test_traced_rehearsal_reads_derived_calls(monkeypatch):
    seen = []
    with_a_device_plane(monkeypatch, seen)
    r = run(9, trace=True, seconds=0.5)
    assert r["correct"] is True
    assert r["metrics"]["derived_calls_pct"] == {"value": 100.0, "unit": "%"}
    # listed where the twin is, but for the reader whose span left the
    # slot channel with PR 50, and on this one more
    bench = harness.read_json(harness.ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in bench["per_layer"]
              if harness.reported_in(m, CELL)}
    twin = {m["name"] for m in bench["per_layer"]
            if harness.reported_in(m, TWIN)}
    assert listed == (twin - {"leader_device_wait_us"}) | {"derived_calls_pct"}
    assert set(r["metrics"]) <= listed
    # the spans: every dev_alltoall B of the window says derived and the
    # ctx of commslice1, which is not the world's
    (ctx,) = seen
    lo, hi = ctx.window_mono
    begins = [a for t, lay, nam, ph, a in ctx.spans[0]
              if (lay, nam, ph) == ("device", "dev_alltoall", "B")
              and lo <= t <= hi]
    assert begins and all(a["derived"] is True for a in begins)
    assert len({a["ctx"] for a in begins}) == 1 and begins[0]["ctx"] != 1
    # one dev_comm_derive span a split a rank, as far as the ring holds
    # the set-up: commslice1 bound to a slot channel with the world's
    # programs, commslice2 (one rank) to none
    derives = [a for _t, lay, nam, ph, a in ctx.spans[0]
               if (lay, nam, ph) == ("device", "dev_comm_derive", "E")]
    assert [(a["size"], a["channel"], a["same_mesh"]) for a in derives] == \
        [(RANKS, "HBMSlotChannel", True), (1, "none", False)]


def test_a_world_cell_reads_no_derived_call(monkeypatch):
    """The twin's traced rehearsal: its calls are the world's, the
    metric is not on its list and its reader finds nothing."""
    seen = []
    with_a_device_plane(monkeypatch, seen)
    r = run(10, trace=True, seconds=0.5, cell=TWIN)
    assert r["correct"] is True and "derived_calls_pct" not in r["metrics"]
    (ctx,) = seen
    reader = harness.load_by_name("layer_metrics", "derived_calls_pct")
    assert reader.compute(ctx) is None
    # the same readers find something in both cells: the split cell's
    # line is the twin's and the one metric more
    split = run(11, trace=True, seconds=0.5)
    assert set(split["metrics"]) == set(r["metrics"]) | {"derived_calls_pct"}


def test_derived_calls_pct_by_hand():
    reader = harness.load_by_name("layer_metrics", "derived_calls_pct")

    def ctx(events, device=True):
        dev = DeviceTrace(0, 0.0, 0.020, [], [])
        return RunContext(
            collective=harness.load_by_name("collectives", "alltoall_split"),
            config={}, traffic={}, ranks=8, bytes_per_rank=128 * MiB,
            device_kind="TPU v5 lite", peaks={}, window_mono=(10.0, 20.0),
            spans={0: events}, devices={0: dev} if device else {},
            rank0_ordinal=0, traced_calls=2)

    def b(t, **args):
        return (t, "device", "dev_alltoall", "B",
                dict({"seq": 1, "coll": "alltoall"}, **args))
    assert reader.compute(ctx([b(11.0, derived=True, ctx=9),
                               b(12.0, derived=True, ctx=9)])) == 100.0
    # a call that went to the world's channel
    assert reader.compute(ctx([b(11.0, derived=True, ctx=9),
                               b(12.0, derived=False, ctx=1)])) == 50.0
    # a program whose B says nothing of it (the parent), no call at all,
    # calls outside the window, a run that traced no device
    assert reader.compute(ctx([b(11.0), b(12.0)])) is None
    assert reader.compute(ctx([])) is None
    assert reader.compute(ctx([b(9.0, derived=True, ctx=9)])) is None
    assert reader.compute(ctx([b(11.0, derived=True, ctx=9)],
                              device=False)) is None
    # a cell on the world: nothing ran on a derived channel
    assert reader.compute(ctx([b(11.0, derived=False, ctx=1)])) is None


def test_the_entries_of_the_two_cells():
    bench = harness.read_json(harness.ROOT, "BENCHMARK.json")
    # the fifteenth and the sixteenth cell, the sixteenth in the eighth
    # four-chip seat (a prefix check: a later cell is appended behind)
    cells = bench["workloads"][:16]
    assert [c["name"] for c in cells[14:]] == [CELL, ROW_256]
    assert sum(c["chips"] == 4 for c in cells) == 8
    _b, cell, config, traffic, coll = harness.load_cell(CELL)
    assert (cell["chips"], config["ranks"], config["dtype"]) == \
        (1, 8, "float32")
    assert config["expect"] == {
        "channel": "HBMSlotChannel",
        "level_pvars": ["coll_level_chip", "dev_coll_derived"],
        "least_bytes": "slot"}
    _b, _c, twin_config, twin_traffic, _coll = harness.load_cell(TWIN)
    for key in ("bytes_per_rank", "op", "loop", "warmup_calls", "values",
                "buffers"):
        assert traffic[key] == twin_traffic[key], key
    assert traffic["collective"] == "alltoall_split"
    for key in ("ranks", "chips", "dtype", "buffers", "front_door", "reduced"):
        assert config[key] == twin_config[key], key
    assert set(twin_config["assumed"]) < set(config["assumed"])
    # the sixteenth cell: the 64 MiB cell's configuration, the one-chip
    # 256 MiB row's traffic, the 64 MiB cell's lists
    _b, cell, config, traffic, _coll = harness.load_cell(ROW_256)
    assert (cell["chips"], cell["config"], cell["traffic"]) == \
        (4, "osu-dd-4chip-4r", "allreduce.256MiB.dev")
    assert traffic["bytes_per_rank"] == 256 * MiB
    # (the metrics this PR found and the one it added: a prefix again)
    for m in bench["end_to_end"] + bench["per_layer"][:38]:
        if "workloads" in m and m["name"] != "derived_calls_pct":
            assert (ROW_256 in m["workloads"]) == \
                ("osu4.allreduce.64MiB.dev" in m["workloads"]), m["name"]
    for name in ("leader_device_wait_us", "fold_kernel_us",
                 "fold_kernel_roofline_pct"):
        m = harness.by_name(bench["per_layer"], name, "metric")
        assert CELL not in m["workloads"] and ROW_256 not in m["workloads"]
