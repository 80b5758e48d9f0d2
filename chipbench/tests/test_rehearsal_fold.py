"""The two-ranks-a-chip cell end to end at a size the CPU holds: eight
ranks over four interpreted devices (``DeviceFoldChannel``); the same run
with the timed path broken underneath two ways, each of which has to
come out as not correct; the control; the three readers that came with
the cell on a context made by hand; the collective module's arithmetic;
and the files of ``osu1.allreduce.256MiB.dev``, the data-only row that
came with it, rehearsed once on one device."""

import time

import jax
import numpy as np
import pytest

from chipbench import check, control, harness
from chipbench.context import DeviceTrace, RunContext
from mvapich2_tpu.utils.config import get_config

CELL = "osu4.allreduce_2level.64MiB.dev"
BIG_ROW = "osu1.allreduce.256MiB.dev"
E2E = {"lat_us_p50", "lat_us_p95", "busbw_GBps", "setup_s"}
MiB = 1 << 20


def four_devices(bytes_per_rank=16384):
    from mvapich2_tpu.parallel.mesh import make_mesh
    return harness.Rehearsal(
        bytes_per_rank=bytes_per_rank,
        device_mesh=make_mesh((4,), ("x",), jax.devices()[:4]))


@pytest.fixture(autouse=True)
def interpreted_kernels(monkeypatch):
    """The ring under the TPU interpreter, the streaming tier from 8 KiB
    up (as test_rehearsal_allgather.py)."""
    cfg = get_config()
    monkeypatch.setenv("MV2T_ICI_INTERPRET", "1")
    monkeypatch.setenv("MV2T_DEV_TIER_VMEM_MAX", "8192")
    monkeypatch.setenv("MV2T_DEV_TIER_XLA_MIN", "-1")
    cfg.reload()
    yield
    monkeypatch.undo()
    cfg.reload()


def run(seed, trace=False, **kw):
    return harness.run_cell(CELL, seed, 0.2, trace, time.perf_counter(),
                            rehearsal=four_devices(**kw))


def test_the_cell_end_to_end():
    r = run(2**31 + 38)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == E2E
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_traced_rehearsal_files_no_device_number():
    """The CPU has no device plane: the phase readers, ``chip_fold_us``
    among them, and the kernel readers file nothing."""
    r = run(5, trace=True)
    assert r["correct"] is True
    assert set(r["metrics"]) == {"rendezvous_span_us"}


def test_a_chip_that_folds_one_of_its_two_ranks_is_not_correct(monkeypatch):
    """Chip 1 hands the ring its first rank's deposit alone: rank 3 is
    in no sum."""
    from mvapich2_tpu.coll.device import DeviceFoldChannel
    sound = DeviceFoldChannel._fold_chip

    def short(self, j, n, dtype, op):
        if j != 1:
            return sound(self, j, n, dtype, op)
        return self.rv.slots[j * self.k]
    monkeypatch.setattr(DeviceFoldChannel, "_fold_chip", short)
    r = run(13)
    assert r["correct"] is False and r["failed"] == 0


def test_a_ring_over_unfolded_deposits_is_not_correct(monkeypatch):
    """Level 1 left out: the ring sums each chip's first rank."""
    from mvapich2_tpu.coll.device import DeviceFoldChannel
    monkeypatch.setattr(DeviceFoldChannel, "_fold_chip",
                        lambda self, j, n, dtype, op: self.rv.slots[j * self.k])
    said = []
    monkeypatch.setattr(harness, "say", said.append)
    r = run(17)
    assert r["correct"] is False and r["failed"] == 0
    failed = [ln for ln in said if ln.startswith("correct:")
              and ln.endswith("FAILED")]
    # both calls read back differ; the counts are sound
    assert len(failed) == 4 and all("elements differing" in ln
                                    or "largest" in ln for ln in failed)


def test_control_fails_at_a_size_a_test_can_hold():
    """The sum carried in bfloat16: whole numbers up to 2^20 keep 8 of
    their 21 bits."""
    for cell in (CELL, BIG_ROW):
        for seed in (11, 12, 2**31 + 5):
            compared = control.control_once(cell, seed, bytes_per_rank=65536)
            assert not check.verdict(compared)
            assert compared[0].value > 0.9 * 16384


def made_up_context(**over):
    """Two calls in a 20 ms sub-window on rank 0's device: a stack, the
    fold kernel (0.3 ms), the ring (1.2 ms) and the result's copy each;
    rank 0's ``dev_chip_fold`` spans take 0.9 and 1.1 ms."""
    def call(at):
        return [("%concatenate.1", at, at + 0.0004),
                ("%mv2t_slot_reduce.3", at + 0.0004, at + 0.0007),
                ("%mv2t_hbm_all_reduce.5", at + 0.0007, at + 0.0019),
                ("%copy.3", at + 0.0019, at + 0.0020)]
    ops = call(0.002) + call(0.012)
    dev = DeviceTrace(0, 0.0, 0.020, [(0.002, 0.004), (0.012, 0.014)], ops)
    h = 100.0
    spans = {0: [(h + 0.0010, "device", "dev_stage", "B", {"seq": 1}),
                 (h + 0.0011, "device", "dev_chip_fold", "B", {"seq": 1}),
                 (h + 0.0020, "device", "dev_chip_fold", "E",
                  {"seq": 1, "k": 2, "chips": 4, "stacked": 4}),
                 (h + 0.0021, "device", "dev_stage", "E", {"seq": 1}),
                 (h + 0.0110, "device", "dev_chip_fold", "B", {"seq": 2}),
                 (h + 0.0121, "device", "dev_chip_fold", "E", {"seq": 2})],
             1: [(h + 0.0011, "device", "dev_chip_fold", "B", {"seq": 1}),
                 (h + 0.0091, "device", "dev_chip_fold", "E", {"seq": 1})]}
    args = dict(
        collective=harness.load_by_name("collectives", "allreduce_2level"),
        config={"ranks": 8, "chips": 4,
                "expect": {"least_bytes": "fold_k2"}}, traffic={}, ranks=8,
        bytes_per_rank=64 * MiB, device_kind="TPU v5 lite",
        peaks={"hbm_GBps": 819.0, "ici_GBps": 200.0},
        window_mono=(h - 1.0, h + 1.0), spans=spans, devices={0: dev},
        rank0_ordinal=0, traced_calls=2, clock_offset_s=-h)
    args.update(over)
    return RunContext(**args)


def reader(name):
    return harness.load_by_name("layer_metrics", name)


def test_the_three_readers_on_a_made_up_trace():
    ctx = made_up_context()
    # rank 0's two spans; rank 1's is not read
    assert reader("chip_fold_us").compute(ctx) == pytest.approx(1000.0)
    assert reader("fold_kernel_us").compute(ctx) == pytest.approx(300.0)
    # both kernels carry the token: the ring is the difference
    assert reader("kernel_us").compute(ctx) == pytest.approx(1500.0)
    # 3 x 64 MiB over 819 GB/s = 245.8 us of the 300 the kernel took
    assert reader("fold_kernel_roofline_pct").compute(ctx) == pytest.approx(
        100 * 3 * 64 * MiB / 819e9 / 300e-6)
    # the ICI phase alone: 96 MiB over 200 GB/s = 503.3 us of 1 500
    assert reader("kernel_roofline_pct").compute(ctx) == pytest.approx(
        100 * 96 * MiB / 200e9 / 1500e-6)
    # a fold kernel that starts before the sub-window counts from its edge
    early = made_up_context()
    early.devices[0].ops[1] = ("%mv2t_slot_reduce.3", -0.001, 0.0001)
    assert reader("fold_kernel_us").compute(early) == pytest.approx(200.0)


def test_the_three_readers_file_nothing_where_nothing_is_to_read():
    """A program without the span (the parent of the PR that added it),
    a channel without a fold, a run that traced no device."""
    no_span = made_up_context(spans={0: []})
    assert reader("chip_fold_us").compute(no_span) is None
    no_fold = made_up_context()
    no_fold.devices[0].ops[:] = [op for op in no_fold.devices[0].ops
                                 if "slot_reduce" not in op[0]]
    assert reader("fold_kernel_us").compute(no_fold) is None
    assert reader("fold_kernel_roofline_pct").compute(no_fold) is None
    other = made_up_context(
        collective=harness.load_by_name("collectives", "allreduce"))
    assert reader("fold_kernel_roofline_pct").compute(other) is None
    untraced = made_up_context(devices={}, traced_calls=0)
    for name in ("chip_fold_us", "fold_kernel_us",
                 "fold_kernel_roofline_pct"):
        assert reader(name).compute(untraced) is None


def test_arithmetic_by_hand():
    coll = harness.load_by_name("collectives", "allreduce_2level")
    _bench, cell, config, traffic, loaded = harness.load_cell(CELL)
    assert loaded is coll and coll.NAME == "allreduce"
    assert (cell["chips"], config["ranks"], config["dtype"]) == \
        (4, 8, "float32")
    assert config["expect"]["channel"] == "DeviceFoldChannel"
    assert traffic["bytes_per_rank"] == 64 * MiB
    # OSU's factor goes by the job's ranks
    assert coll.bus_factor(8) == 1.75
    # the ICI phase alone, over chips: 2 x 3/4 x m out of every chip
    m = 64 * MiB
    assert coll.least_bytes("fold_k2", 8, m) == (1.5 * m, "ici_GBps")
    assert coll.least_bytes(config["expect"]["least_bytes"], 8, m)[0] == \
        96 * MiB
    # where allreduce.py's ring over eight ranks would reckon 2 x 7/8 x m
    flat = harness.load_by_name("collectives", "allreduce")
    assert flat.least_bytes("ring", 8, m)[0] == 1.75 * m
    assert coll.least_bytes("fold_k1", 4, m) == flat.least_bytes("ring", 4, m)
    assert coll.least_bytes("fold_k4", 8, m)[0] == 1.0 * m
    peaks = harness.read_json(harness.HERE, "peaks.json")["TPU v5 lite"]
    assert 1.5 * m / (peaks["ici_GBps"] * 1e9) * 1e6 == \
        pytest.approx(503.3, abs=0.1)
    # level 1: two deposits read, one sum written, 245.8 us of HBM
    assert coll.fold_bytes(2, m) == 3 * m
    assert 3 * m / (peaks["hbm_GBps"] * 1e9) * 1e6 == \
        pytest.approx(245.8, abs=0.1)
    for bad in ("ring", "slot", "fold_k", "fold_k0", "fold_kx"):
        with pytest.raises(KeyError):
            coll.least_bytes(bad, 8, m)
    with pytest.raises(ValueError):
        coll.least_bytes("fold_k3", 8, m)
    # the reference by hand, and the control rounds
    a, b = np.arange(2, dtype=np.float32), np.arange(2, 4, dtype=np.float32)
    assert [g.tolist() for g in coll.reference([a, b])] == [[2, 4]] * 2
    low = coll.lower_precision([a + 1025, b])
    assert low[0].dtype == np.float32 and low[0][0] != np.float32(1027)


def test_the_two_cells_are_listed_where_they_read_something():
    bench = harness.read_json(harness.ROOT, "BENCHMARK.json")

    def listed(cell):
        return {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                if harness.reported_in(m, cell)}
    ring = listed("osu4.allreduce.64MiB.dev")
    assert listed(CELL) == ring | {"chip_fold_us", "fold_kernel_us",
                                   "fold_kernel_roofline_pct"}
    # no rate (one stalled iteration moves it by over half its bound),
    # so neither of the shares that move it
    assert listed(BIG_ROW) == listed("osu1.allreduce.64MiB.dev") - \
        {"busbw_GBps", "busy_roofline_pct", "kernel_roofline_pct"}
    cells = bench["workloads"]
    assert [c["name"] for c in cells[8:]] == [CELL, BIG_ROW]
    assert sum(c["chips"] == 4 for c in cells) <= len(cells) // 2


def test_the_256MiB_slot_row_end_to_end():
    """``osu1.allreduce.256MiB.dev``: a traffic file and entries beside
    a configuration that was there; eight ranks on one device."""
    from mvapich2_tpu.parallel.mesh import make_mesh
    _bench, cell, config, traffic, coll = harness.load_cell(BIG_ROW)
    assert (cell["chips"], cell["config"], coll.NAME) == \
        (1, "osu-dd-1chip-8r", "allreduce")
    assert traffic["bytes_per_rank"] == 256 * MiB
    # the slot rule: eight deposits read, one sum written
    assert coll.least_bytes(config["expect"]["least_bytes"], 8,
                            256 * MiB) == (9 * 256 * MiB, "hbm_GBps")
    r = harness.run_cell(
        BIG_ROW, 2**31 + 39, 0.3, False, time.perf_counter(),
        rehearsal=harness.Rehearsal(
            bytes_per_rank=8 * 4096 * 4,
            device_mesh=make_mesh((1,), ("x",), jax.devices()[:1])))
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == {"lat_us_p50", "lat_us_p95", "setup_s"}
