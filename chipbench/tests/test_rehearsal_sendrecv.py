"""The sendrecv cell end to end at a size the CPU holds, two ranks on
one device; the same run with the lane broken underneath twice, each of
which has to come out as not correct; the control; and the module's
arithmetic by hand. ``test_rehearsal.py`` does the same for the
allreduce cells."""

import json
import time

import jax
import numpy as np
import pytest

from chipbench import check, control, harness

CELL = "osu1.sendrecv.1MiB.dev"
MiB = 1 << 20
P2P = {"p2p_call_us", "p2p_send_us", "p2p_recv_us", "p2p_copy_enqueue_us",
       "p2p_unexpected_pct"}


def one_device(bytes_per_rank=4096):
    from mvapich2_tpu.parallel.mesh import make_mesh
    return harness.Rehearsal(
        bytes_per_rank=bytes_per_rank,
        device_mesh=make_mesh((1,), ("x",), jax.devices()[:1]))


def run(seed, trace=False, seconds=0.3):
    return harness.run_cell(CELL, seed, seconds, trace, time.perf_counter(),
                            rehearsal=one_device())


def failed_lines(said):
    return [ln for ln in said if ln.startswith("correct:")
            and ln.endswith("FAILED")]


def test_the_cell_end_to_end(monkeypatch):
    said = []
    monkeypatch.setattr(harness, "say", said.append)
    r = run(2**31 + 11)
    json.dumps(r)
    assert failed_lines(said) == []
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == {"lat_us_p50", "lat_us_p95", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    # both of the lane's per-message pvars were held to ranks x calls
    counted = [ln for ln in said if "rose by (ranks x calls" in ln]
    assert len(counted) == 2 and all(ln.endswith("ok") for ln in counted)
    assert "dev_pt2pt_recv" in counted[0] and "dev_pt2pt_send" in counted[1]


def test_traced_run_files_nothing_under_a_device_metrics_name():
    """The CPU has no device plane: the readers of the lane's spans file
    a chip's numbers only, and find their spans in the recorder."""
    r = run(5, trace=True, seconds=0.6)
    assert r["correct"] is True
    assert set(r["metrics"]) == set()


def test_the_lanes_readers_on_recorded_spans():
    """Each reader on a recorder's events written by hand, beside a
    device's timeline (without one they file nothing)."""
    from chipbench.context import DeviceTrace, RunContext
    _bench, _cell, config, traffic, coll = harness.load_cell(CELL)
    send = {"dest": 1, "tag": 0, "seq": 4}
    ev = [(11.000, "mpi", "sendrecv", "B", None),
          (11.001, "mpi", "irecv", "B", None),
          (11.002, "device", "dev_recv", "B",
           {"source": 1, "tag": 0, "capacity": MiB}),
          (11.003, "mpi", "irecv", "E", None),
          (11.004, "device", "dev_send", "B", dict(send, bytes=MiB)),
          (11.005, "device", "dev_p2p_copy", "B",
           {"bytes": MiB, "d2d": False}),
          (11.007, "device", "dev_p2p_copy", "E", None),
          (11.009, "device", "dev_send", "E", send),
          (11.012, "device", "dev_recv", "E",
           {"source": 1, "tag": 0, "bytes": MiB, "seq": 4,
            "unexpected": False}),
          (11.020, "mpi", "sendrecv", "E", None)]
    late = [(t + 1.0, lay, nam, ph,
             dict(args, unexpected=True) if nam == "dev_recv" and ph == "E"
             else args) for t, lay, nam, ph, args in ev]
    outside = [(e[0] + 20.0,) + e[1:] for e in ev]

    def ctx(events, chip=True):
        c = RunContext(collective=coll, config=config, traffic=traffic,
                       ranks=2, bytes_per_rank=MiB,
                       device_kind="TPU v5 lite", peaks={},
                       window_mono=(10.0, 20.0), spans={0: events})
        if chip:
            c.devices[0] = DeviceTrace(0, 0.0, 1.0, [], [])
        return c

    def read(name, events, **kw):
        return harness.load_by_name("layer_metrics", name).compute(
            ctx(events, **kw))

    both = ev + late + outside
    assert read("p2p_call_us", both) == pytest.approx(20000.0)
    assert read("p2p_send_us", both) == pytest.approx(5000.0)
    assert read("p2p_recv_us", both) == pytest.approx(10000.0)
    assert read("p2p_copy_enqueue_us", both) == pytest.approx(2000.0)
    assert read("p2p_unexpected_pct", both) == pytest.approx(50.0)
    assert read("p2p_unexpected_pct", ev) == 0.0
    for name in P2P:
        # a program with no lane (the parent), a window that holds no
        # call, a run that traced no device
        assert read(name, [e for e in both if e[1] == "mpi"]) is None
        assert read(name, outside) is None
        assert read(name, both, chip=False) is None


def test_each_rank_handed_its_own_plane_back_is_not_correct(monkeypatch):
    """The lane broken underneath: the packet that should carry the
    sender's copy to the partner carries the partner's own send array
    (what a lane that mixed up its two ends would deliver)."""
    from mvapich2_tpu.pt2pt import protocol
    sent = {}
    sound = protocol.Pt2ptProtocol._dev_isend

    def swapped(self, x, channel, dest_world, *rest):
        sent[self.u.world_rank] = x
        return sound(self, x, channel, dest_world, *rest)

    def own_plane_back(self, req, pkt):
        pkt.data = sent[self.u.world_rank]
        return delivered(self, req, pkt)
    delivered = protocol.Pt2ptProtocol._dev_deliver
    monkeypatch.setattr(protocol.Pt2ptProtocol, "_dev_isend", swapped)
    monkeypatch.setattr(protocol.Pt2ptProtocol, "_dev_deliver",
                        own_plane_back)
    said = []
    monkeypatch.setattr(harness, "say", said.append)
    r = run(13)
    assert r["correct"] is False and r["failed"] == 0
    assert any("elements differing" in ln for ln in failed_lines(said))


def test_a_stale_plane_is_not_correct(monkeypatch):
    """A lane that kept a plane it once carried: a sound run on one seed
    leaves rank 1's plane behind, and in the next run, on another seed,
    rank 0 is handed that one from the first timed call on. The
    warm-up's comparison passes; the window's does not."""
    from mvapich2_tpu.pt2pt import protocol
    delivered = protocol.Pt2ptProtocol._dev_deliver
    kept, seen = {}, {"at rank 0": 0}

    def keeps(self, req, pkt):
        if self.u.world_rank == 0:
            kept.setdefault("plane", pkt.data)
        return delivered(self, req, pkt)
    monkeypatch.setattr(protocol.Pt2ptProtocol, "_dev_deliver", keeps)
    assert run(17)["correct"] is True

    warmup = harness.load_cell(CELL)[3]["warmup_calls"]

    def stale(self, req, pkt):
        if self.u.world_rank == 0:
            seen["at rank 0"] += 1
            if seen["at rank 0"] > warmup:
                pkt.data = kept["plane"]
        return delivered(self, req, pkt)
    monkeypatch.setattr(protocol.Pt2ptProtocol, "_dev_deliver", stale)
    said = []
    monkeypatch.setattr(harness, "say", said.append)
    r = run(19)
    assert r["correct"] is False and r["failed"] == 0
    bad = failed_lines(said)
    assert bad and all("last call of the window" in ln for ln in bad)


def test_control_fails_at_a_size_a_test_can_hold():
    for seed in (1, 2, 2**31 + 5):
        compared = control.control_once(CELL, seed, bytes_per_rank=65536)
        assert not check.verdict(compared)
        assert compared[0].value > 0.9 * 16384


def test_arithmetic_by_hand():
    coll = harness.load_by_name("collectives", "sendrecv")
    assert coll.NAME == "sendrecv"
    assert coll.bus_factor(2) == 1.0
    # two planes of 1 MiB, each read once and written once: 4 MiB a
    # call, 5.1 us at 819 GB/s
    nbytes, peak = coll.least_bytes("slot", 2, MiB)
    assert (nbytes, peak) == (4 * MiB, "hbm_GBps")
    assert nbytes / 819e9 * 1e6 == pytest.approx(5.12, abs=0.01)
    with pytest.raises(KeyError):
        coll.least_bytes("ring", 2, MiB)
    a, b = np.arange(4, dtype=np.float32), np.arange(4, 8, dtype=np.float32)
    got = coll.reference([a, b])
    assert got[0] is b and got[1] is a
    with pytest.raises(ValueError):
        coll.reference([a, b, a])
    # the control carries the planes in bfloat16: 8 bits of mantissa
    big = np.array([1048575.0, 3.0], dtype=np.float32)
    low = coll.lower_precision([big, big])[0]
    assert low.dtype == np.float32 and low.tolist() == [1048576.0, 3.0]
