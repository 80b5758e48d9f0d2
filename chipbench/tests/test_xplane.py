"""The reduction from a trace to intervals: on made-up intervals, and
on a tiny trace recorded here on the CPU."""

import time

import jax
import pytest

from chipbench import xplane
from chipbench.context import paired_spans


def test_union_clip_length_gaps():
    busy = xplane.union([(5, 6), (1, 2), (1.5, 3), (3, 3.5), (8, 9)])
    assert busy == [(1, 3.5), (5, 6), (8, 9)]
    assert xplane.length(busy) == pytest.approx(4.5)
    assert xplane.clip(busy, 2, 8.5) == [(2, 3.5), (5, 6), (8, 8.5)]
    assert xplane.gaps(busy, 0, 10) == [(0, 1), (3.5, 5), (6, 8), (9, 10)]
    assert xplane.gaps(busy, 2, 5.5) == [(3.5, 5)]
    assert xplane.gaps([], 0, 1) == [(0, 1)]
    idle = xplane.length(xplane.gaps(busy, 0, 10))
    assert idle + xplane.length(xplane.clip(busy, 0, 10)) == pytest.approx(10)


def test_time_by_name_clips_to_the_window():
    ev = [("a", 0.0, 1.0), ("b", 0.5, 0.7), ("a", 2.0, 4.0), ("c", 9, 10)]
    assert xplane.time_by_name(ev, 0.5, 3.0) == [
        ("a", pytest.approx(1.5)), ("b", pytest.approx(0.2))]


def test_paired_spans_drop_what_the_ring_cut():
    ev = [(1.0, "device", "dev_allreduce", "E", None),      # its B fell off
          (2.0, "device", "dev_allreduce", "B", None),
          (2.5, "mpi", "allreduce", "B", None),
          (3.0, "device", "dev_allreduce", "E", None),
          (4.0, "device", "dev_allreduce", "B", None)]       # still open
    assert paired_spans(ev, "device", "dev_allreduce") == [(2.0, 3.0)]


def test_recorded_cpu_trace(tmp_path):
    """Annotations of known lengths come back as intervals of those
    lengths, and the clock offset puts host stamps on the trace's axis."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    stamps = {}
    try:
        for i in range(3):
            stamps[i] = time.monotonic()
            with jax.profiler.TraceAnnotation("chipbench_iter", i=i):
                time.sleep(0.02)
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    profile = xplane.load(xplane.newest_trace(str(tmp_path)))
    assert any(line.startswith("plane '/host:CPU'")
               for line in xplane.describe(profile))
    marks = xplane.annotations(profile, "chipbench_iter")
    assert [st["i"] for _s, _e, st in marks] == [0, 1, 2]
    busy = xplane.union((s, e) for s, e, _st in marks)
    assert len(busy) == 3
    assert xplane.length(busy) == pytest.approx(0.06, abs=0.015)
    idle = xplane.gaps(busy, marks[0][0], marks[-1][1])
    assert len(idle) == 2
    assert xplane.length(idle) == pytest.approx(0.02, abs=0.01)
    off = xplane.clock_offset(marks, stamps)
    for (s, _e, st) in marks:
        assert stamps[st["i"]] + off == pytest.approx(s, abs=2e-3)
    assert xplane.device_planes(profile) == {}
    assert xplane.clock_offset(marks, {}) is None
