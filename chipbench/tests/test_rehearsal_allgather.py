"""The all-gather cell end to end at a size the CPU holds, on four
interpreted devices, at a whole-tile shard and at an FSDP-ragged one; a
traced rehearsal that reads the kernel's wire count; the same run with
the path broken underneath two ways, each of which has to come out as
not correct; the control; the collective module's arithmetic by hand;
and the files of ``osu1.alltoall.16MiB.dev``, the data-only row that
came with it, rehearsed once on one device. ``test_rehearsal_alltoall.py``
does the same for the four-chip alltoall cell."""

import time

import jax
import numpy as np
import pytest

from chipbench import check, control, harness
from mvapich2_tpu.utils.config import get_config

CELL = "osu4.allgather.16MiB.dev"
SLOT_ROW = "osu1.alltoall.16MiB.dev"
E2E = {"lat_us_p50", "lat_us_p95", "busbw_GBps", "setup_s"}
MiB = 1 << 20
# Moonlight's shard (7 799 952 bfloat16 a rank: 60 937.125 rows of 128)
# cut to what the interpreter holds: 7 617 elements, 59.5 rows
RAGGED_BYTES = (7_799_952 >> 10) * 2


def four_devices(bytes_per_rank=16384):
    from mvapich2_tpu.parallel.mesh import make_mesh
    return harness.Rehearsal(
        bytes_per_rank=bytes_per_rank,
        device_mesh=make_mesh((4,), ("x",), jax.devices()[:4]))


@pytest.fixture(autouse=True)
def interpreted_kernels(monkeypatch):
    """The four-device kernels under the TPU interpreter, the streaming
    tier from 8 KiB of output up (as test_rehearsal_alltoall.py)."""
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
    r = run(2**31 + 34)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == E2E
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_traced_run_reads_the_wire_bytes():
    """A shard of whole tiles puts nothing on the wire beyond the p - 1
    blocks every chip has to send."""
    r = run(5, trace=True)
    assert r["correct"] is True
    # the CPU has no device plane: only the program's own records read
    assert set(r["metrics"]) == {"rendezvous_span_us", "wire_overhead_pct"}
    assert r["metrics"]["wire_overhead_pct"] == {"value": 0.0, "unit": "%"}


def test_an_fsdp_ragged_shard_is_correct_and_pays_for_its_tiles():
    """7 617 bfloat16 travel as four (16, 128) tiles, 8 192 elements."""
    r = run(7, trace=True, bytes_per_rank=RAGGED_BYTES)
    assert r["correct"] is True and r["failed"] == 0
    assert r["metrics"]["wire_overhead_pct"]["value"] == \
        pytest.approx(100.0 * (8192 / 7617 - 1))


def test_blocks_in_the_wrong_rank_order_are_not_correct(monkeypatch):
    """Every block arrives on every rank, and is handed back in
    descending rank order."""
    from mvapich2_tpu.coll.device import DeviceCollChannel
    sound = DeviceCollChannel._leader

    def backwards(self, name, op, root):
        n = self.rv.slots[0].size
        return [o.reshape(self.size, n)[::-1].reshape(-1)
                for o in sound(self, name, op, root)]
    monkeypatch.setattr(DeviceCollChannel, "_leader", backwards)
    r = run(13)
    assert r["correct"] is False and r["failed"] == 0


def test_a_stale_block_is_not_correct(monkeypatch):
    """The gather is sound through the warm-up; after it rank 2's block
    holds what its slot held a ring round earlier, rank 1's block, on
    every rank."""
    from mvapich2_tpu.coll.device import DeviceCollChannel
    sound, calls = DeviceCollChannel._leader, []

    def stale(self, name, op, root):
        out = sound(self, name, op, root)
        calls.append(1)
        n = self.rv.slots[0].size
        # made in every call, so that nothing compiles in the window
        bad = [o.at[2 * n:3 * n].set(o[n:2 * n]) for o in out]
        return out if len(calls) <= 3 else bad
    monkeypatch.setattr(DeviceCollChannel, "_leader", stale)
    said = []
    monkeypatch.setattr(harness, "say", said.append)
    r = run(17)
    assert r["correct"] is False and r["failed"] == 0
    failed = [ln for ln in said if ln.startswith("correct:")
              and ln.endswith("FAILED")]
    assert failed and all("last call of the window" in ln for ln in failed)


def test_control_fails_at_a_size_a_test_can_hold():
    """The payload carried in float8_e5m2: whole numbers up to 2^20 in
    bfloat16 keep 3 of their 8 bits, and those above 57 344 none."""
    for seed in (11, 12, 2**31 + 5):
        compared = control.control_once(CELL, seed, bytes_per_rank=65536)
        assert not check.verdict(compared)
        assert compared[0].value > 0.9 * 4 * 32768


def test_arithmetic_by_hand():
    coll = harness.load_by_name("collectives", "allgather")
    _bench, cell, config, traffic, _coll = harness.load_cell(CELL)
    assert (cell["chips"], config["ranks"], config["dtype"]) == \
        (4, 4, "bfloat16")
    # one Moonlight layer outside its routed experts, term by term
    attention = (2048 * 16 * (128 + 64) + 2048 * (512 + 64) + 512
                 + 512 * 16 * (128 + 128) + 16 * 128 * 2048)
    shared = 3 * 2048 * (2 * 1408)
    router, norms = 64 * 2048 + 64, 2 * 2048
    assert (attention, shared) == (13763072, 17301504)
    params = attention + shared + router + norms
    assert params == 31199808 and params // 4 == 7_799_952
    # 14.9 MiB a rank in bfloat16, padded to the 16 MiB row
    assert params // 4 * 2 == 15599904 < traffic["bytes_per_rank"] == 16 * MiB
    # bytes_per_rank is the shard: p - 1 blocks reach every rank
    assert coll.bus_factor(4) == 3.0 and coll.bus_factor(8) == 7.0
    # 48 MiB leave each chip, 0.252 ms at 200 GB/s
    nbytes, peak = coll.least_bytes(config["expect"]["least_bytes"], 4,
                                    16 * MiB)
    assert (nbytes, peak) == (48 * MiB, "ici_GBps")
    peaks = harness.read_json(harness.HERE, "peaks.json")["TPU v5 lite"]
    assert nbytes / (peaks[peak] * 1e9) * 1e6 == pytest.approx(251.7, abs=0.1)
    # eight ranks of 1 MiB on one chip: every block read once, the
    # gathered array written once
    assert coll.least_bytes("slot", 8, MiB) == (16 * MiB, "hbm_GBps")
    with pytest.raises(KeyError):
        coll.least_bytes("pairwise", 4, MiB)
    # the reference by hand on 2 ranks of 2
    a, b = np.arange(2, dtype=np.float32), np.arange(2, 4, dtype=np.float32)
    got = coll.reference([a, b])
    assert [g.tolist() for g in got] == [[0, 1, 2, 3]] * 2
    # the control hands the payload back in its own type, rounded
    low = coll.lower_precision([a + 0.3, b])
    assert low[0].dtype == np.float32 and low[0][0] != np.float32(0.3)


def test_the_cell_is_listed_where_the_four_chip_alltoall_is():
    bench = harness.read_json(harness.ROOT, "BENCHMARK.json")

    def listed(cell):
        return {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                if harness.reported_in(m, cell)}
    assert listed(CELL) == listed("osu4.alltoall.192MiB.dev")
    assert listed(SLOT_ROW) == listed("osu1.alltoall.128MiB.dev") - \
        {"busbw_GBps", "busy_roofline_pct"}


def test_the_16MiB_slot_row_end_to_end():
    """``osu1.alltoall.16MiB.dev``: a traffic file and entries beside a
    configuration that was there; eight ranks on one device."""
    from mvapich2_tpu.parallel.mesh import make_mesh
    _bench, cell, config, traffic, coll = harness.load_cell(SLOT_ROW)
    assert (cell["chips"], cell["config"], coll.NAME) == \
        (1, "osu-a2a-dd-1chip-8r", "alltoall")
    # a 256^3 complex64 grid over 8 ranks: 16 MiB a rank, 2 MiB a pair
    assert traffic["bytes_per_rank"] == 256 ** 3 * 8 // 8 == 16 * MiB
    r = harness.run_cell(
        SLOT_ROW, 2**31 + 35, 0.3, False, time.perf_counter(),
        rehearsal=harness.Rehearsal(
            bytes_per_rank=8 * 4096 * 4,
            device_mesh=make_mesh((1,), ("x",), jax.devices()[:1])))
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    # no rate: one stalled iteration moves it by over half its bound
    assert set(r["metrics"]) == {"lat_us_p50", "lat_us_p95", "setup_s"}
