"""The alltoall cell end to end at a size the CPU holds, on four
interpreted devices; the same run with the path broken underneath three
times, each of which has to come out as not correct; the control; and
the collective module's arithmetic by hand. ``test_rehearsal.py`` does
the same for the allreduce cells."""

import time

import jax
import numpy as np
import pytest

from chipbench import check, control, harness
from chipbench.context import RunContext
from mvapich2_tpu.utils.config import get_config

CELL = "osu4.alltoall.192MiB.dev"
E2E = {"lat_us_p50", "lat_us_p95", "busbw_GBps", "setup_s"}
MiB = 1 << 20


def four_devices(bytes_per_rank=16384):
    from mvapich2_tpu.parallel.mesh import make_mesh
    return harness.Rehearsal(
        bytes_per_rank=bytes_per_rank,
        device_mesh=make_mesh((4,), ("x",), jax.devices()[:4]))


@pytest.fixture(autouse=True)
def interpreted_kernels(monkeypatch):
    """The four-device kernels under the TPU interpreter, the streaming
    tier at every size (as tests/test_chip_smoke.py)."""
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
    r = run(2**31 + 11)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == E2E
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_traced_run_reads_the_wire_bytes():
    """Blocks of 1000 bfloat16 travel as one (16, 128) tile each: the
    kernel sends 3 x 4096 B where 3 x 2000 B would do."""
    r = run(5, trace=True, bytes_per_rank=8000)
    assert r["correct"] is True
    # the CPU has no device plane: only the program's own records read
    assert set(r["metrics"]) == {"rendezvous_span_us", "wire_overhead_pct"}
    assert r["metrics"]["wire_overhead_pct"]["value"] == \
        pytest.approx(100.0 * (4096 / 2000 - 1))


def test_wire_overhead_reads_nothing_where_nothing_was_recorded():
    reader = harness.load_by_name("layer_metrics", "wire_overhead_pct")
    _bench, _cell, config, traffic, coll = harness.load_cell(CELL)

    def ctx(events):
        return RunContext(collective=coll, config=config, traffic=traffic,
                          ranks=4, bytes_per_rank=192 * MiB,
                          device_kind="TPU v5 lite", peaks={},
                          window_mono=(10.0, 20.0), spans={0: events})
    call = {"seq": 7, "coll": "alltoall"}
    # a program that records no wire count (the parent of ISSUE 28)
    assert reader.compute(ctx([(11.0, "device", "dev_alltoall", "B", call),
                               (12.0, "device", "dev_alltoall", "E", call)])
                          ) is None
    assert reader.compute(ctx([])) is None
    # the cell's own shape: whole tiles, nothing beyond the least
    wire = dict(call, wire_bytes=3 * 48 * MiB)
    assert reader.compute(ctx([(11.0, "device", "dev_a2a_wire", "i", wire)])
                          ) == 0.0
    # outside the measured window: not read
    assert reader.compute(ctx([(9.0, "device", "dev_a2a_wire", "i", wire)])
                          ) is None


def test_a_permutation_step_left_out_is_not_correct(monkeypatch):
    """The third pairwise step never runs: every rank misses the block
    of the peer three places back."""
    from mvapich2_tpu.ops import pallas_alltoall
    sound = pallas_alltoall._lane_steps
    monkeypatch.setattr(
        pallas_alltoall, "_lane_steps",
        lambda p, ndir: [[s for s in lane if s != 3]
                         for lane in sound(p, ndir)])
    r = run(13)
    assert r["correct"] is False and r["failed"] == 0


def test_blocks_in_the_wrong_sender_order_are_not_correct(monkeypatch):
    """Every block arrives, and is handed back in descending sender
    order."""
    from mvapich2_tpu.coll.device import DeviceCollChannel
    sound = DeviceCollChannel._leader

    def backwards(self, name, op, root):
        return [o[::-1] for o in sound(self, name, op, root)]
    monkeypatch.setattr(DeviceCollChannel, "_leader", backwards)
    r = run(17)
    assert r["correct"] is False and r["failed"] == 0


def test_the_old_dtype_gate_is_not_correct(monkeypatch):
    """``_dtype_lowers`` as it was before ISSUE 28: numpy says kind 'V'
    of bfloat16, the host arm carries every call, the answer is right
    and the run is not correct: no level pvar rises, and the call turned
    away for its dtype is counted among the fallbacks."""
    from mvapich2_tpu.coll import device
    monkeypatch.setattr(device, "_dtype_lowers",
                        lambda dtype: dtype.kind in "fiu")
    said = []
    monkeypatch.setattr(harness, "say", said.append)
    r = run(19)
    assert r["correct"] is False and r["failed"] == 0
    failed = [ln for ln in said if ln.startswith("correct:")
              and ln.endswith("FAILED")]
    assert len(failed) == 3 and all(
        key in ln for key, ln in zip(("coll_level_ici", "dev_coll_tier_hbm",
                                      "dev_coll_fallback_*"), failed))


def test_control_fails_at_a_size_a_test_can_hold():
    for seed in (1, 2, 2**31 + 5):
        compared = control.control_once(CELL, seed, bytes_per_rank=65536)
        assert not check.verdict(compared)
        assert compared[0].value > 0.9 * 32768


def test_arithmetic_by_hand():
    coll = harness.load_by_name("collectives", "alltoall")
    assert coll.bus_factor(4) == 0.75 and coll.bus_factor(8) == 0.875
    # 192 MiB a rank on four chips: 144 MiB leave each chip, 0.755 ms at
    # 200 GB/s
    nbytes, peak = coll.least_bytes("pairwise", 4, 192 * MiB)
    assert (nbytes, peak) == (144 * MiB, "ici_GBps")
    assert nbytes / 200e9 * 1e3 == pytest.approx(0.755, abs=5e-4)
    # eight ranks of 1 MiB on one chip: the transpose reads and writes
    # every rank's buffer once
    assert coll.least_bytes("slot", 8, MiB) == (16 * MiB, "hbm_GBps")
    with pytest.raises(KeyError):
        coll.least_bytes("ring", 4, MiB)
    # the reference by hand on 2 ranks x 2 blocks of 2
    a, b = np.arange(4, dtype=np.float32), np.arange(4, 8, dtype=np.float32)
    got = coll.reference([a, b])
    assert [g.tolist() for g in got] == [[0, 1, 4, 5], [2, 3, 6, 7]]
