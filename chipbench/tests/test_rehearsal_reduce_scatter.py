"""The reduce-scatter cell end to end at a size the CPU holds, on four
interpreted devices; a traced rehearsal that reads the kernel's wire
count, at whole tiles and at a ragged block; the same run with the path
broken underneath two ways, each of which has to come out as not
correct; the control; the collective module's arithmetic by hand; and
the files of ``osu1.alltoall.32MiB.dev``, the data-only row that came
with it, rehearsed once on one device. ``test_rehearsal_allgather.py``
does the same for the all-gather cell."""

import time

import jax
import numpy as np
import pytest

from chipbench import check, control, harness
from mvapich2_tpu.utils.config import get_config

CELL = "osu4.reduce_scatter.128MiB.dev"
SLOT_ROW = "osu1.alltoall.32MiB.dev"
E2E = {"lat_us_p50", "lat_us_p95", "busbw_GBps", "setup_s"}
MiB = 1 << 20
# Moonlight's own gradient (31 199 808 float32 a rank, blocks of
# 60 937.125 rows of 128) cut to what the interpreter holds: 30 468
# elements a rank, blocks of 7 617 (59.5 rows)
RAGGED_BYTES = (31_199_808 >> 10) * 4


def four_devices(bytes_per_rank=16384):
    from mvapich2_tpu.parallel.mesh import make_mesh
    return harness.Rehearsal(
        bytes_per_rank=bytes_per_rank,
        device_mesh=make_mesh((4,), ("x",), jax.devices()[:4]))


@pytest.fixture(autouse=True)
def interpreted_kernels(monkeypatch):
    """The four-device kernels under the TPU interpreter, the streaming
    tier from 8 KiB up (as test_rehearsal_allgather.py)."""
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
    r = run(2**31 + 42)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == E2E
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_traced_run_reads_the_wire_bytes():
    """Blocks of whole tiles put nothing on the wire beyond the p - 1
    partial blocks every chip has to send; every call ran a plan but
    the first, on the caller's own array."""
    r = run(5, trace=True)
    assert r["correct"] is True
    # the CPU has no device plane: only the program's own records read
    assert set(r["metrics"]) == {"rendezvous_span_us", "wire_overhead_pct"}
    assert r["metrics"]["wire_overhead_pct"] == {"value": 0.0, "unit": "%"}


def test_a_ragged_gradient_is_correct_and_pays_for_its_tiles():
    """Blocks of 7 617 float32 travel as eight (8, 128) tiles, 8 192
    elements."""
    r = run(7, trace=True, bytes_per_rank=RAGGED_BYTES)
    assert r["correct"] is True and r["failed"] == 0
    assert r["metrics"]["wire_overhead_pct"]["value"] == \
        pytest.approx(100.0 * (8192 / 7617 - 1))


def test_the_next_ranks_block_is_not_correct(monkeypatch):
    """Every block is folded, and rank r is handed block (r + 1) % p on
    its own device."""
    from mvapich2_tpu.coll.device import DeviceCollChannel
    sound = DeviceCollChannel._leader

    def rotated(self, name, op, root):
        out = sound(self, name, op, root)
        return [jax.device_put(out[(r + 1) % self.size], self.devices[r])
                for r in range(self.size)]
    monkeypatch.setattr(DeviceCollChannel, "_leader", rotated)
    r = run(13)
    assert r["correct"] is False and r["failed"] == 0


def test_a_missing_addend_is_not_correct(monkeypatch):
    """The sum is sound through the warm-up; after it rank 2's
    contribution is left out of every block."""
    from mvapich2_tpu.coll.device import DeviceCollChannel
    sound, calls = DeviceCollChannel._leader, []

    def short(self, name, op, root):
        calls.append(1)
        # made in every call, so that nothing compiles in the window
        nothing = self.rv.slots[2] * 0
        if len(calls) > 3:
            self.rv.slots[2] = nothing
        return sound(self, name, op, root)
    monkeypatch.setattr(DeviceCollChannel, "_leader", short)
    said = []
    monkeypatch.setattr(harness, "say", said.append)
    r = run(17)
    assert r["correct"] is False and r["failed"] == 0
    failed = [ln for ln in said if ln.startswith("correct:")
              and ln.endswith("FAILED")]
    assert failed and all("last call of the window" in ln for ln in failed)


def test_control_fails_at_a_size_a_test_can_hold():
    """The sum carried in bfloat16: whole numbers up to 2^20 keep 8 of
    their 21 bits."""
    for seed in (11, 12, 2**31 + 5):
        compared = control.control_once(CELL, seed, bytes_per_rank=262144)
        assert not check.verdict(compared)
        # every rank's block: a quarter of 65 536 elements
        assert compared[0].value > 0.9 * 16384


def test_arithmetic_by_hand():
    coll = harness.load_by_name("collectives", "reduce_scatter")
    # the file has OSU's name, the spans the readers join have the call's
    assert coll.NAME == "reduce_scatter_block"
    _bench, cell, config, traffic, _coll = harness.load_cell(CELL)
    assert (cell["chips"], config["ranks"], config["dtype"]) == \
        (4, 4, "float32")
    assert config["expect"] == {
        "channel": "DeviceCollChannel", "least_bytes": "ring",
        "level_pvars": ["coll_level_ici", "dev_coll_tier_hbm"]}
    # one Moonlight layer outside its routed experts (term by term in
    # test_rehearsal_allgather.py), its gradients in float32: 119.0 MiB
    # a rank, padded to the 128 MiB row; a quarter comes back
    params = 13763072 + 17301504 + 131136 + 4096
    assert params == 31199808 and params * 4 == 124799232
    assert params * 4 < traffic["bytes_per_rank"] == 128 * MiB
    assert traffic["bytes_per_rank"] / (params * 4) == \
        pytest.approx(1.075, abs=1e-3)
    # bytes_per_rank is the send buffer: a rank's own block never leaves
    assert coll.bus_factor(4) == 0.75 and coll.bus_factor(8) == 0.875
    # 96 MiB leave each chip, 0.503 ms at 200 GB/s
    nbytes, peak = coll.least_bytes(config["expect"]["least_bytes"], 4,
                                    128 * MiB)
    assert (nbytes, peak) == (96 * MiB, "ici_GBps")
    peaks = harness.read_json(harness.HERE, "peaks.json")["TPU v5 lite"]
    assert nbytes / (peaks[peak] * 1e9) * 1e6 == pytest.approx(503.3, abs=0.1)
    # eight ranks of 1 MiB on one chip: every buffer read once, the
    # blocks of the sum, one buffer in all, written once
    assert coll.least_bytes("slot", 8, MiB) == (9 * MiB, "hbm_GBps")
    with pytest.raises(KeyError):
        coll.least_bytes("pairwise", 4, MiB)
    # the reference by hand on 2 ranks of 4
    a = np.arange(4, dtype=np.float32)
    b = np.array([10, 20, 30, 40], dtype=np.float32)
    got = coll.reference([a, b])
    assert [g.tolist() for g in got] == [[10, 21], [32, 43]]
    # the control sums in bfloat16 and hands float32 blocks back
    low = coll.lower_precision([a + 1000.0, b + 0.5])
    assert [g.dtype for g in low] == [np.float32] * 2
    assert [g.shape for g in low] == [(2,)] * 2
    assert low[0][1] != np.float32(1021.5)      # 1021.5 has 11 bits


def test_the_cells_are_listed_where_their_siblings_are():
    bench = harness.read_json(harness.ROOT, "BENCHMARK.json")

    def listed(cell):
        return {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                if harness.reported_in(m, cell)}
    assert listed(CELL) == listed("osu4.allgather.16MiB.dev")
    assert "kernel_roofline_pct" in listed(CELL)
    assert listed(SLOT_ROW) == listed("osu1.alltoall.16MiB.dev")
    assert "busbw_GBps" not in listed(SLOT_ROW)
    # appended in this order, behind the ten cells there were; a later
    # cell goes behind them (no count is pinned), within the seats
    names = [w["name"] for w in bench["workloads"]]
    assert names[10:12] == [CELL, SLOT_ROW]
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert CELL in four and len(four) <= len(names) // 2


def test_the_32MiB_slot_row_end_to_end():
    """``osu1.alltoall.32MiB.dev``: a traffic file and entries beside a
    configuration that was there; eight ranks on one device."""
    from mvapich2_tpu.parallel.mesh import make_mesh
    _bench, cell, config, traffic, coll = harness.load_cell(SLOT_ROW)
    assert (cell["chips"], cell["config"], coll.NAME) == \
        (1, "osu-a2a-dd-1chip-8r", "alltoall")
    # NPB FT class B's 512 x 256 x 256 complex64 grid over 8 ranks:
    # 32 MiB a rank, 4 MiB a pair
    assert traffic["bytes_per_rank"] == 512 * 256 * 256 * 8 // 8 == 32 * MiB
    r = harness.run_cell(
        SLOT_ROW, 2**31 + 43, 0.3, False, time.perf_counter(),
        rehearsal=harness.Rehearsal(
            bytes_per_rank=8 * 4096 * 4,
            device_mesh=make_mesh((1,), ("x",), jax.devices()[:1])))
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    # no rate: one stalled iteration moves it by over half its bound
    assert set(r["metrics"]) == {"lat_us_p50", "lat_us_p95", "setup_s"}
