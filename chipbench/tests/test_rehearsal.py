"""A whole run of each configuration at a tiny size on the CPU, without
the look for a chip; the same run with the timed path broken underneath,
which has to come out as not correct; and the refusal without a chip."""

import json
import time

import jax
import numpy as np
import pytest

from chipbench import control, harness
from mvapich2_tpu.utils.config import get_config

ONE = "osu1.allreduce.4KiB.dev"
FOUR = "osu4.allreduce.64MiB.dev"


def one_device():
    from mvapich2_tpu.parallel.mesh import make_mesh
    return harness.Rehearsal(
        bytes_per_rank=4096,
        device_mesh=make_mesh((1,), ("x",), jax.devices()[:1]))


def four_devices():
    from mvapich2_tpu.parallel.mesh import make_mesh
    return harness.Rehearsal(
        bytes_per_rank=16384,
        device_mesh=make_mesh((4,), ("x",), jax.devices()[:4]))


@pytest.fixture
def interpreted_ring(monkeypatch):
    """The four-device ring kernels under the TPU interpreter, at a size
    where the HBM tier is what runs (as tests/test_chip_smoke.py)."""
    cfg = get_config()
    monkeypatch.setenv("MV2T_ICI_INTERPRET", "1")
    monkeypatch.setenv("MV2T_DEV_TIER_VMEM_MAX", "8192")
    monkeypatch.setenv("MV2T_DEV_TIER_XLA_MIN", "-1")
    cfg.reload()
    yield
    monkeypatch.undo()
    cfg.reload()


def test_one_chip_cell_end_to_end_metrics():
    r = harness.run_cell(ONE, 2**31 + 3, 0.5, False, time.perf_counter(),
                         rehearsal=one_device())
    json.dumps(r)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == {"lat_us_p50", "lat_us_p95", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["metrics"]["lat_us_p95"]["value"] >= \
        r["metrics"]["lat_us_p50"]["value"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(r["device"])


def test_one_chip_cell_traced_run_reads_the_program_span():
    r = harness.run_cell("osu1.allreduce.64MiB.dev", 5, 1.0, True,
                         time.perf_counter(), rehearsal=one_device())
    assert r["correct"] is True
    # the CPU has no device plane: only the span reader finds something
    assert set(r["metrics"]) == {"rendezvous_span_us"}
    assert r["metrics"]["rendezvous_span_us"]["value"] > 0


def test_four_chip_cell(interpreted_ring):
    r = harness.run_cell(FOUR, 7, 0.2, False, time.perf_counter(),
                         rehearsal=four_devices())
    assert r["correct"] is True and r["attempted"] >= 2
    assert set(r["metrics"]) == {"lat_us_p50", "lat_us_p95", "busbw_GBps",
                                 "setup_s"}


def test_a_rank_left_out_of_the_slot_reduce_is_not_correct(monkeypatch):
    """The timed path broken underneath: the leader reduces seven of the
    eight deposited buffers."""
    import jax.numpy as jnp
    from mvapich2_tpu.coll.device import HBMSlotChannel
    sound = HBMSlotChannel._leader

    def seven_of_eight(self, name, op, root):
        self.rv.slots[3] = jnp.zeros_like(self.rv.slots[3])
        return sound(self, name, op, root)
    monkeypatch.setattr(HBMSlotChannel, "_leader", seven_of_eight)
    r = harness.run_cell(ONE, 11, 0.3, False, time.perf_counter(),
                         rehearsal=one_device())
    assert r["correct"] is False and r["failed"] == 0


def test_a_ring_without_the_exchange_is_not_correct(monkeypatch):
    """The exchange between chips left out: every rank gets its own
    buffer back."""
    from mvapich2_tpu.coll.device import DeviceCollChannel

    def no_exchange(self, name, op, root):
        return [s.reshape(-1) for s in self.rv.slots]
    monkeypatch.setattr(DeviceCollChannel, "_leader", no_exchange)
    r = harness.run_cell(FOUR, 13, 0.2, False, time.perf_counter(),
                         rehearsal=four_devices())
    assert r["correct"] is False


def test_one_altered_element_is_not_correct(monkeypatch):
    """An answer altered where it is produced, in one element of the
    result every rank shares, and only after the warm-up."""
    from mvapich2_tpu.coll.device import HBMSlotChannel
    sound = HBMSlotChannel._leader
    calls = []

    def one_off(self, name, op, root):
        out = sound(self, name, op, root)
        calls.append(1)
        if len(calls) <= 3:
            return out
        return [o.at[5].add(1.0) for o in out]
    monkeypatch.setattr(HBMSlotChannel, "_leader", one_off)
    r = harness.run_cell(ONE, 17, 0.3, False, time.perf_counter(),
                         rehearsal=one_device())
    assert r["correct"] is False


def test_a_failing_rank_counts_as_failed_and_not_correct(monkeypatch):
    from mvapich2_tpu.coll.device import HBMSlotChannel
    sound = HBMSlotChannel._leader
    calls = []

    def dies_later(self, name, op, root):
        calls.append(1)
        if len(calls) == 6:
            raise RuntimeError("made-up device fault")
        return sound(self, name, op, root)
    monkeypatch.setattr(HBMSlotChannel, "_leader", dies_later)
    r = harness.run_cell(ONE, 19, 5.0, False, time.perf_counter(),
                         rehearsal=one_device())
    assert r["correct"] is False and r["failed"] == 1
    assert r["attempted"] == 3 and r["metrics"] == {}


def test_the_command_refuses_without_a_chip(capsys):
    assert harness.main(["--workload", ONE, "--seed", "1", "--seconds", "1",
                         "--trace", "0"], time.perf_counter()) != 0
    assert '"correct"' not in capsys.readouterr().out
    with pytest.raises(harness.NoChip):
        harness.run_cell(ONE, 1, 1.0, False, time.perf_counter())


def test_control_fails_at_a_size_a_test_can_hold():
    from chipbench import check
    for seed in (1, 2, 2**31 + 5):
        for cell in (ONE, FOUR):
            compared = control.control_once(cell, seed, bytes_per_rank=65536)
            assert not check.verdict(compared)
            assert compared[0].value > 0.9 * 16384


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        harness.run_cell("no.such.cell", 1, 1.0, False, time.perf_counter())
    with pytest.raises(FileNotFoundError):
        harness.load_by_name("layer_metrics", "no_such_metric")
    assert np.dtype("float32").itemsize == 4
