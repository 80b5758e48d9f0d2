"""The per-layer readers and the breakdown on a context made by hand."""

import pytest

from chipbench import breakdown, harness
from chipbench.context import DeviceTrace, RunContext

MiB = 1 << 20


def made_up_context(**over):
    """Two collectives in a 20 ms sub-window on device 0: busy 2-6 ms
    and 12-16 ms of the trace's axis, which leads the host's by 100 s."""
    ops = [("%copy", 0.002, 0.004), ("%kernel", 0.004, 0.006),
           ("%copy", 0.012, 0.014), ("%kernel", 0.014, 0.016)]
    dev = DeviceTrace(0, 0.0, 0.020, [(0.002, 0.006), (0.012, 0.016)], ops)
    h = 100.0       # host = trace + 100 s, so the offset is -100
    spans = {0: [(h + 0.000, "mpi", "allreduce", "B", None),
                 (h + 0.0005, "device", "dev_allreduce", "B", None),
                 (h + 0.007, "device", "dev_allreduce", "E", None),
                 (h + 0.0075, "mpi", "allreduce", "E", None),
                 (h + 0.010, "mpi", "allreduce", "B", None),
                 (h + 0.0105, "device", "dev_allreduce", "B", None),
                 (h + 0.0165, "device", "dev_allreduce", "E", None),
                 (h + 0.017, "mpi", "allreduce", "E", None)]}
    args = dict(
        collective=harness.load_by_name("collectives", "allreduce"),
        config={"expect": {"least_bytes": "slot"}}, traffic={}, ranks=8,
        bytes_per_rank=64 * MiB, device_kind="TPU v5 lite",
        peaks={"hbm_GBps": 819.0, "ici_GBps": 200.0},
        window_mono=(h - 1.0, h + 1.0), spans=spans, devices={0: dev},
        rank0_ordinal=0, traced_calls=2, clock_offset_s=-h,
        caller_waits=[(h + 0.0075, h + 0.0085)])
    args.update(over)
    return RunContext(**args)


def reader(name):
    return harness.load_by_name("layer_metrics", name)


def test_readers_on_a_made_up_trace():
    ctx = made_up_context()
    assert reader("device_busy_us").compute(ctx) == pytest.approx(4000.0)
    assert reader("device_idle_pct").compute(ctx) == pytest.approx(60.0)
    # 9 x 64 MiB over 819 GB/s = 737.46 us of the 4000 us the call took
    assert reader("busy_roofline_pct").compute(ctx) == pytest.approx(
        18.4365, rel=1e-4)
    assert reader("rendezvous_span_us").compute(ctx) == pytest.approx(6250.0)
    ring = made_up_context(config={"expect": {"least_bytes": "ring"}},
                           ranks=4)
    assert reader("busy_roofline_pct").compute(ring) == pytest.approx(
        96 * MiB / 200e9 / 4000e-6 * 100)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    empty = made_up_context(devices={}, spans={}, traced_calls=0)
    for name in ("device_busy_us", "device_idle_pct", "busy_roofline_pct",
                 "rendezvous_span_us"):
        assert reader(name).compute(empty) is None
    outside = made_up_context(window_mono=(0.0, 1.0))
    assert reader("rendezvous_span_us").compute(outside) is None


def test_breakdown_names_the_ops_and_what_the_host_was_in():
    ctx = made_up_context()
    assert breakdown.device_ops(ctx) == [["%copy", pytest.approx(0.004)],
                                         ["%kernel", pytest.approx(0.004)]]
    gaps = dict(breakdown.idle_gaps(ctx))
    # gaps: 0-2 ms (middle 1 ms: in dev_allreduce), 6-12 ms (middle 9 ms:
    # between calls), 16-20 ms (middle 18 ms: between calls)
    assert gaps == {"device:dev_allreduce": pytest.approx(0.002),
                    "harness loop between calls": pytest.approx(0.010)}
    waiting = made_up_context(caller_waits=[(100.0075, 100.0095)])
    assert dict(breakdown.idle_gaps(waiting))[
        "caller waiting in block_until_ready (call returned)"] == \
        pytest.approx(0.006)
    assert breakdown.idle_gaps(made_up_context(clock_offset_s=None)) == []
