"""The one-chip alltoall cell end to end at a size the CPU holds: eight
ranks on one device; a traced rehearsal whose spans say the eager ops
behind every result (``parts``, ``relaid``) and what the two readers
ISSUE 32 added make of them; the same run with the timed path broken
underneath two ways, each of which has to come out as not correct; the
control; the arithmetic of the cell's roofline by hand; the four-chip
rehearsal once more at the 4 KiB of ``osu4.allreduce.4KiB.dev``; and the
two readers on contexts made by hand. ``test_rehearsal_alltoall.py``
does the same for the four-chip alltoall cell."""

import json
import time

import jax
import pytest

from chipbench import check, control, harness
from chipbench.context import DeviceTrace, RunContext
from mvapich2_tpu.utils.config import get_config

CELL = "osu1.alltoall.128MiB.dev"
FOUR_4K = "osu4.allreduce.4KiB.dev"
ACCEPTED = ("osu1.allreduce.64MiB.dev", "osu1.allreduce.4KiB.dev",
            "osu4.allreduce.64MiB.dev", "osu4.alltoall.192MiB.dev")
E2E = {"lat_us_p50", "lat_us_p95", "busbw_GBps", "setup_s"}
MiB = 1 << 20
RANKS = 8


def one_device(bytes_per_rank=RANKS * 4096 * 4):
    from mvapich2_tpu.parallel.mesh import make_mesh
    return harness.Rehearsal(
        bytes_per_rank=bytes_per_rank,
        device_mesh=make_mesh((1,), ("x",), jax.devices()[:1]))


def run(seed, trace=False, seconds=0.3, **kw):
    return harness.run_cell(CELL, seed, seconds, trace, time.perf_counter(),
                            rehearsal=one_device(**kw))


def test_the_cell_end_to_end():
    r = run(2**31 + 32)
    json.dumps(r)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == E2E
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_ragged_blocks_are_correct_too():
    """1000 float32 a pair: no whole 128-lane row."""
    r = run(7, bytes_per_rank=RANKS * 1000 * 4)
    assert r["correct"] is True and r["failed"] == 0


def test_a_cpu_rehearsal_files_no_count_of_device_ops():
    r = run(5, trace=True)
    assert r["correct"] is True
    # the CPU has no device plane: only the span reader finds something
    assert set(r["metrics"]) == {"rendezvous_span_us"}


def test_traced_rehearsal_reads_the_eager_ops(monkeypatch):
    """A traced rehearsal with a stand-in for rank 0's device plane (the
    CPU has none): 40 made-up ops in a one-second sub-window. The
    program's spans are its own: ``parts`` 8 on the leader's
    ``dev_collect``, ``relaid`` 1 on every rank's ``dev_deliver``."""
    sound, seen = harness._reduce_trace, []

    def with_a_device_plane(ctx, sh, device_ids, need_devices):
        sound(ctx, sh, device_ids, need_devices)
        ops = [("%fusion = f32[8,8,4096]", 0.01 * i, 0.01 * i + 0.005)
               for i in range(40)]
        ctx.devices[device_ids[0]] = DeviceTrace(
            device_ids[0], 0.0, 1.0, [(s, e) for _n, s, e in ops], ops)
        seen.append(ctx)
    monkeypatch.setattr(harness, "_reduce_trace", with_a_device_plane)
    r = run(9, trace=True, seconds=0.5)
    assert r["correct"] is True
    assert r["metrics"]["result_eager_ops"] == {"value": 16.0, "unit": "ops"}
    (ctx,) = seen
    assert r["metrics"]["device_ops_per_call"]["value"] == \
        pytest.approx(40 / ctx.traced_calls)
    # every per-layer metric whose list names the cell is in the line,
    # and no reader that would find nothing is asked
    bench = harness.read_json(harness.ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in bench["per_layer"]
              if harness.reported_in(m, CELL)}
    assert set(r["metrics"]) == listed
    assert not listed & {"kernel_us", "kernel_roofline_pct",
                         "wire_overhead_pct"}
    # the spans themselves
    lo, hi = ctx.window_mono
    collect = [a for t, lay, nam, ph, a in ctx.spans[0]
               if (lay, nam, ph) == ("device", "dev_collect", "E")
               and lo <= t <= hi]
    assert collect and all(a["parts"] == RANKS for a in collect)
    for rank in range(RANKS):
        deliver = [a for t, lay, nam, ph, a in ctx.spans[rank]
                   if (lay, nam, ph) == ("device", "dev_deliver", "E")
                   and lo <= t <= hi]
        assert deliver and all(a["relaid"] == 1 for a in deliver), rank


def test_blocks_in_the_wrong_sender_order_are_not_correct(monkeypatch):
    """Every block arrives at its rank, and is handed back in descending
    sender order."""
    from mvapich2_tpu.coll.device import HBMSlotChannel
    sound = HBMSlotChannel._leader

    def backwards(self, name, op, root):
        return [o[::-1] for o in sound(self, name, op, root)]
    monkeypatch.setattr(HBMSlotChannel, "_leader", backwards)
    r = run(13)
    assert r["correct"] is False and r["failed"] == 0


def test_a_slice_handed_to_the_wrong_rank_is_not_correct(monkeypatch):
    """The exchange is sound; ranks 2 and 5 get each other's slice, and
    only after the warm-up."""
    from mvapich2_tpu.coll.device import HBMSlotChannel
    sound, calls = HBMSlotChannel._leader, []

    def swapped(self, name, op, root):
        out = sound(self, name, op, root)
        calls.append(1)
        if len(calls) > 3:
            out[2], out[5] = out[5], out[2]
        return out
    monkeypatch.setattr(HBMSlotChannel, "_leader", swapped)
    said = []
    monkeypatch.setattr(harness, "say", said.append)
    r = run(17)
    assert r["correct"] is False and r["failed"] == 0
    failed = [ln for ln in said if ln.startswith("correct:")
              and ln.endswith("FAILED")]
    assert failed and all("last call of the window" in ln for ln in failed)


def test_control_fails_at_a_size_a_test_can_hold():
    """The payload carried in bfloat16: whole numbers up to 2^20 keep 8
    of their 21 bits."""
    for seed in (11, 12, 2**31 + 5):
        compared = control.control_once(CELL, seed, bytes_per_rank=65536)
        assert not check.verdict(compared)
        assert compared[0].value > 0.9 * 16384


def test_arithmetic_by_hand():
    coll = harness.load_by_name("collectives", "alltoall")
    _bench, cell, config, traffic, _coll = harness.load_cell(CELL)
    assert (cell["chips"], config["ranks"], config["dtype"]) == \
        (1, 8, "float32")
    # 512^3 complex64 points over 8 ranks: 128 MiB a rank, 16 MiB a pair
    assert traffic["bytes_per_rank"] == 512 ** 3 * 8 // 8 == 134217728
    assert traffic["bytes_per_rank"] // 8 == 16777216
    # the transpose reads and writes every rank's buffer once: 2 GiB,
    # 2.62 ms at 819 GB/s
    nbytes, peak = coll.least_bytes(config["expect"]["least_bytes"], 8,
                                    134217728)
    assert (nbytes, peak) == (2147483648, "hbm_GBps")
    peaks = harness.read_json(harness.HERE, "peaks.json")["TPU v5 lite"]
    assert nbytes / (peaks[peak] * 1e9) * 1e3 == pytest.approx(2.622, abs=5e-4)
    # of 8 blocks, the rank's own never leaves it
    assert coll.bus_factor(8) == 0.875


@pytest.fixture
def interpreted_ring(monkeypatch):
    """The four-device ring kernels under the TPU interpreter, the tier
    edges where the program's defaults put them on a chip (said out
    loud: the CPU's measured profile would send every size to XLA):
    4 KiB rides the VMEM ring."""
    cfg = get_config()
    monkeypatch.setenv("MV2T_ICI_INTERPRET", "1")
    monkeypatch.setenv("MV2T_DEV_TIER_VMEM_MAX", str(4 * MiB))
    monkeypatch.setenv("MV2T_DEV_TIER_XLA_MIN", "-1")
    cfg.reload()
    yield
    monkeypatch.undo()
    cfg.reload()


def test_four_chip_cell_at_4KiB(interpreted_ring):
    """``osu4.allreduce.4KiB.dev`` at its own size: the configuration
    and the traffic file are older than the cell."""
    from mvapich2_tpu import mpit
    from mvapich2_tpu.parallel.mesh import make_mesh
    _bench, cell, config, traffic, _coll = harness.load_cell(FOUR_4K)
    assert (cell["chips"], config["ranks"], traffic["bytes_per_rank"]) == \
        (4, 4, 4096)
    vmem0 = mpit.pvar("dev_coll_tier_vmem").read()
    r = harness.run_cell(
        FOUR_4K, 2**31 + 41, 0.2, False, time.perf_counter(),
        rehearsal=harness.Rehearsal(
            bytes_per_rank=4096,
            device_mesh=make_mesh((4,), ("x",), jax.devices()[:4])))
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == {"lat_us_p50", "lat_us_p95", "setup_s"}
    calls = r["attempted"] + traffic["warmup_calls"]
    assert mpit.pvar("dev_coll_tier_vmem").read() - vmem0 == 4 * calls


# -- the two readers on contexts made by hand ------------------------------

def read(name, ctx):
    return harness.load_by_name("layer_metrics", name).compute(ctx)


def call_events(seq, t0, rank, parts=None, relaid=None, coll="alltoall"):
    """One collective's ``dev_collect`` (rank 0) and ``dev_deliver``
    spans, ``t0`` seconds on the host's clock; ``None`` leaves the arg
    out, as a program older than ISSUE 32 does."""
    a = {"seq": seq, "coll": coll}
    ev = []
    if rank == 0:
        ev += [(t0, "device", "dev_collect", "B", a),
               (t0 + 0.001, "device", "dev_collect", "E",
                a if parts is None else dict(a, parts=parts))]
    ev += [(t0 + 0.002, "device", "dev_deliver", "B", a),
           (t0 + 0.003, "device", "dev_deliver", "E",
            a if relaid is None else dict(a, relaid=relaid))]
    return ev


def context(spans, ops=(), traced_calls=2, device=True):
    dev = DeviceTrace(0, 0.0, 0.020, [(s, e) for _n, s, e in ops], list(ops))
    return RunContext(
        collective=harness.load_by_name("collectives", "alltoall"),
        config={"expect": {"least_bytes": "slot"}}, traffic={}, ranks=3,
        bytes_per_rank=128 * MiB, device_kind="TPU v5 lite",
        peaks={"hbm_GBps": 819.0}, window_mono=(10.0, 20.0), spans=spans,
        devices={0: dev} if device else {}, rank0_ordinal=0,
        traced_calls=traced_calls)


def test_result_eager_ops_by_hand():
    def spans(parts, relaid, seqs=((7, 11.0), (8, 12.0), (9, 13.0))):
        return {r: [e for seq, t0 in seqs
                    for e in call_events(seq, t0, r, parts, relaid)]
                for r in range(3)}
    # three ranks: 3 slices by the leader and a reshape in every rank
    assert read("result_eager_ops", context(spans(3, 1))) == 6
    # results shared or the program's own outputs, handed out flat
    assert read("result_eager_ops", context(spans(0, 0))) == 0
    # a program that records neither arg (the parent of ISSUE 32)
    assert read("result_eager_ops", context(spans(None, None))) is None
    assert read("result_eager_ops", context({})) is None
    # a run that traced no device files no count
    assert read("result_eager_ops", context(spans(3, 1), device=False)) is None
    # the median over the calls: 6, 6 and one call whose leader cut
    # nothing; a seq one rank's ring no longer holds is dropped
    mixed = spans(3, 1)
    mixed[0] = (call_events(7, 11.0, 0, 3, 1) + call_events(8, 12.0, 0, 3, 1)
                + call_events(9, 13.0, 0, 0, 0))
    assert read("result_eager_ops", context(mixed)) == 6
    mixed[2] = call_events(9, 13.0, 2, 3, 0)
    assert read("result_eager_ops", context(mixed)) == 1
    # outside the measured window: not read
    assert read("result_eager_ops",
                context(spans(3, 1, seqs=((7, 9.0),)))) is None


def test_device_ops_per_call_by_hand():
    ops = [("%fusion", 0.001, 0.004), ("%slice.1", 0.005, 0.006),
           ("%reshape.2", 0.007, 0.008), ("%fusion", 0.011, 0.014),
           ("%slice.1", 0.015, 0.016), ("%reshape.2", 0.017, 0.018),
           # before the sub-window, and cut by its end: not counted
           ("%fusion", -0.004, -0.001), ("%fusion", 0.019, 0.023),
           # cut by its start: counted where it ends
           ("%reshape.2", -0.001, 0.0005)]
    assert read("device_ops_per_call", context({}, ops)) == 7 / 2
    assert read("device_ops_per_call", context({}, ops, traced_calls=0)) \
        is None
    assert read("device_ops_per_call", context({}, ())) is None
    assert read("device_ops_per_call", context({}, ops, device=False)) is None


def test_the_lists_of_the_two_new_cells():
    """The one-chip alltoall runs no ``mv2t_`` kernel and no wire: it is
    on no list whose reader would file nothing for it; both new readers
    are asked in all six cells."""
    bench = harness.read_json(harness.ROOT, "BENCHMARK.json")
    cells = [c["name"] for c in bench["workloads"]]
    assert cells == list(ACCEPTED) + [CELL, FOUR_4K]
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 3
    lists = {m["name"]: m.get("workloads") for m in
             bench["end_to_end"] + bench["per_layer"]}
    off = {n for n, w in lists.items() if w is not None and CELL not in w}
    assert off == {"kernel_us", "kernel_roofline_pct", "wire_overhead_pct"}
    # the four-chip 4 KiB cell: no bandwidth, no slot leader's wait; its
    # VMEM ring is an ``mv2t_`` op, so ``kernel_us`` reads it
    off4 = {n for n, w in lists.items() if w is not None and FOUR_4K not in w}
    assert off4 == {"busbw_GBps", "busy_roofline_pct", "kernel_roofline_pct",
                    "leader_device_wait_us", "wire_overhead_pct"}
    for name in ("result_eager_ops", "device_ops_per_call"):
        assert lists[name] == cells
