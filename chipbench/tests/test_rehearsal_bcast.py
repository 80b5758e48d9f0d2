"""The broadcast cell end to end at a size the CPU holds, on four
interpreted devices; a traced rehearsal that reads the kernel's wire
count; the same run with the path broken underneath two ways, each of
which has to come out as not correct; the control; and the collective
module's arithmetic by hand. ``test_rehearsal_allgather.py`` does the
same for the all-gather cell, this cell's mirror."""

import time

import jax
import numpy as np
import pytest

from chipbench import check, control, harness
from mvapich2_tpu.utils.config import get_config

CELL = "osu4.bcast.64MiB.dev"
E2E = {"lat_us_p50", "lat_us_p95", "busbw_GBps", "setup_s"}
MiB = 1 << 20


def four_devices(bytes_per_rank=32768):
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
    r = run(2**31 + 51)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == E2E
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_traced_run_reads_the_wire_bytes():
    """A message of whole tiles leaves the root once and nothing more."""
    r = run(5, trace=True)
    assert r["correct"] is True
    # the CPU has no device plane: only the program's own records read
    assert set(r["metrics"]) == {"rendezvous_span_us", "wire_overhead_pct"}
    assert r["metrics"]["wire_overhead_pct"] == {"value": 0.0, "unit": "%"}


def test_a_ragged_message_pays_for_its_tiles():
    """Moonlight's layer as it is, cut to what the interpreter holds:
    15 234 bfloat16 travel as eight (16, 128) tiles, 16 384 elements."""
    r = run(7, trace=True, bytes_per_rank=(31_199_808 >> 11) * 2)
    assert r["correct"] is True and r["failed"] == 0
    assert r["metrics"]["wire_overhead_pct"]["value"] == \
        pytest.approx(100.0 * (16384 / 15234 - 1))


def test_every_rank_handed_its_own_buffer_back_is_not_correct(monkeypatch):
    """No data moves: a broadcast that returns what it was given. The
    root is right, every other rank is not."""
    from mvapich2_tpu.coll.device import DeviceCollChannel
    sound = DeviceCollChannel._leader

    def identity(self, name, op, root):
        sound(self, name, op, root)     # the program runs all the same
        return list(self.rv.slots)
    monkeypatch.setattr(DeviceCollChannel, "_leader", identity)
    said = []
    monkeypatch.setattr(harness, "say", said.append)
    r = run(13)
    assert r["correct"] is False and r["failed"] == 0
    failed = [ln for ln in said if ln.startswith("correct:")
              and ln.endswith("FAILED")]
    assert any("warm-up call" in ln for ln in failed)


def test_a_non_roots_deposit_handed_on_is_not_correct(monkeypatch):
    """The broadcast is sound through the warm-up; from the first timed
    call on rank 2 is handed rank 1's deposit (a read of a non-root's
    operand, as a chain forwarding from the wrong buffer would make)."""
    from mvapich2_tpu.coll.device import DeviceCollChannel
    sound, calls = DeviceCollChannel._leader, []

    def leaky(self, name, op, root):
        out = sound(self, name, op, root)
        calls.append(1)
        # made in every call, so that nothing compiles in the window
        wrong = jax.device_put(self.rv.slots[1], self.devices[2])
        if len(calls) > 3:
            out = list(out)
            out[2] = wrong
        return out
    monkeypatch.setattr(DeviceCollChannel, "_leader", leaky)
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
        assert compared[0].value > 0.9 * 32768


def test_arithmetic_by_hand():
    coll = harness.load_by_name("collectives", "bcast")
    _bench, cell, config, traffic, _coll = harness.load_cell(CELL)
    assert (cell["chips"], config["ranks"], config["dtype"]) == \
        (4, 4, "bfloat16")
    assert config["expect"]["level_pvars"] == ["coll_level_ici",
                                               "dev_coll_tier_hbm"]
    # one Moonlight layer outside its routed experts, term by term (the
    # all-gather cell's unit), whole on every rank
    attention = (2048 * 16 * (128 + 64) + 2048 * (512 + 64) + 512
                 + 512 * 16 * (128 + 128) + 16 * 128 * 2048)
    shared = 3 * 2048 * (2 * 1408)
    params = attention + shared + 64 * 2048 + 64 + 2 * 2048
    assert params == 31199808
    # 59.5 MiB in bfloat16, padded to the 64 MiB row
    assert params * 2 == 62399616 < traffic["bytes_per_rank"] == 64 * MiB
    # the message over the time, whatever the number of ranks
    assert coll.bus_factor(4) == coll.bus_factor(8) == 1.0
    # 64 MiB leave the root, 0.336 ms at 200 GB/s
    nbytes, peak = coll.least_bytes(config["expect"]["least_bytes"], 4,
                                    64 * MiB)
    assert (nbytes, peak) == (64 * MiB, "ici_GBps")
    peaks = harness.read_json(harness.HERE, "peaks.json")["TPU v5 lite"]
    assert nbytes / (peaks[peak] * 1e9) * 1e6 == pytest.approx(335.5, abs=0.1)
    with pytest.raises(KeyError):
        coll.least_bytes("slot", 8, MiB)
    # the reference by hand on 3 ranks of 2: everybody holds rank 0's
    ins = [np.arange(2 * r, 2 * r + 2, dtype=np.float32) for r in range(3)]
    assert [g.tolist() for g in coll.reference(ins)] == [[0, 1]] * 3
    # the control hands the payload back in its own type, rounded
    low = coll.lower_precision([ins[0] + 0.3, ins[1]])
    assert low[0].dtype == np.float32 and low[0][0] != np.float32(0.3)
    assert low[1] is not None and np.array_equal(low[0], low[1])


def test_the_cell_is_listed_where_the_all_gather_cell_is():
    bench = harness.read_json(harness.ROOT, "BENCHMARK.json")

    def listed(cell):
        return {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                if harness.reported_in(m, cell)}
    assert listed(CELL) == listed("osu4.allgather.16MiB.dev")
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "osu-bc-dd-4chip-4r"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 7
    assert len(bench["workloads"]) == 14
