"""The host-plane readers (ISSUE 53) on a context and a profile made by
hand: four ranks on one device, three allreduces (seq 5 to 7) two
milliseconds apart, in the form a TPU v5e's trace has (no wait on a
waiting thread's line; a chain of flow ids from rank 0's launch event to
the runtime's completion event and to the run on the device's plane),
every stamp chosen so that each reader's number can be worked out in
the comments; then on a program without the join, on a
run that traced no device, and on what the program and this jax really
write (a CPU rehearsal through the harness: no time is asserted
there)."""

import sys
import time
from types import SimpleNamespace as NS

import pytest

from chipbench import harness
from chipbench.context import DeviceTrace, RunContext
from chipbench.layer_metrics import hostplane

H = 100.0               # host = trace + 100 s, so the tie is -100
RANKS = 4
SEQS = (5, 6, 7)
NEW = ("launch_runtime_us", "result_seen_over_us", "result_to_last_entry_us",
       "gate_handover_us", "plane_shift_width_us")
# the seqs whose program the runtime's thread is seen to complete (the
# TPU's form), at 1.07 ms after the call's origin
DONE = {5, 6, 7}
# when each rank's wait for the call's result ends (the CPU client's
# form: no entry, no wait written), ms after the call's origin
WOKEN = {}


def read(name, ctx):
    return harness.load_by_name("layer_metrics", name).compute(ctx)


def ev(name, start_ms, end_ms, **stats):
    return NS(name=name, start_ns=start_ms * 1e6,
              duration_ns=(end_ms - start_ms) * 1e6,
              stats=list(stats.items()))


def line(rank):
    """Rank ``rank``'s thread on the trace's axis, T = 2 ms a call: the
    annotation from T + 0.10 + 0.01 r to T + 0.60; on rank 0 the launch
    event from T + 0.25 to T + 0.40 (twice, as jaxlib writes it) with
    the mark that ties it to libtpu's half; a wait (``WOKEN``) as the
    CPU client's two marks."""
    out = []
    for k, seq in enumerate(SEQS):
        T = 2.0 * k
        out.append(ev("dev_allreduce", T + 0.10 + 0.01 * rank, T + 0.60,
                      seq=seq, rank=rank))
        if rank == 0:
            out += [ev("PjitFunction(f)", T + 0.25, T + 0.40),
                    ev("PjitFunction(f)", T + 0.251, T + 0.399),
                    ev("ParseArguments", T + 0.26, T + 0.27),
                    ev("PJRT_LoadedExecutable_Execute linkage", T + 0.28,
                       T + 0.281, _p=1000 + seq)]
        if rank in WOKEN.get(seq, ()):
            began, woken = T + 0.70 + 0.01 * rank, T + WOKEN[seq][rank]
            flow = 100 * seq + rank
            out += [ev("CommonPjRtBuffer::Await", began, began + 0.001,
                       _p=flow),
                    ev("CommonPjRtBuffer::Await", woken - 0.001, woken,
                       _c=flow)]
    return NS(name="python", events=out)


def profile():
    """The rank lines; libtpu's half of each launch on a nameless line
    (the program's enqueue from T + 0.30 to T + 0.35); the runtime's own
    thread, which sees the chip done at T + 1.07; and the device's
    plane: one run a call from T + 0.42 to T + 1.02."""
    libtpu, runtime, runs = [], [], []
    for k, seq in enumerate(SEQS):
        T = 2.0 * k
        libtpu += [ev("PJRT_LoadedExecutable_Execute", T + 0.285, T + 0.39,
                      _c=1000 + seq),
                   ev("DoEnqueueProgram", T + 0.30, T + 0.35, _p=2000 + seq,
                      device_ordinal=0)]
        if seq in DONE:
            runtime.append(ev("CompleteCallbacks", T + 1.07, T + 1.20,
                              _c=2000 + seq, device_ordinal=0))
        runs.append(ev("jit_f(1)", T + 0.42, T + 1.02, _c=2000 + seq))
    host = NS(name="/host:CPU", lines=[line(r) for r in range(RANKS)] + [
        NS(name="", events=libtpu), NS(name="futex", events=runtime)])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=runs)])
    return NS(planes=[host, dev])


def spans(rank, turns=True, built=()):
    """The recorder's side, on ``time.monotonic``: ``mpi:allreduce`` B at
    T - 0.05 + 0.02 r, ``dev_allreduce`` B where the annotation starts;
    rank 0's ``dev_dispatch`` from T + 0.20 to T + 0.45 and its release
    at T + 0.48; the others let go last in, first out, 30 us apart from
    T + 0.50 (rank 3 has turn 0)."""
    out = []
    for k, seq in enumerate(SEQS):
        T = 2.0 * k
        a = {"seq": seq, "coll": "allreduce"}
        evs = [(T - 0.05 + 0.02 * rank, "mpi", "allreduce", "B", None),
               (T + 0.10 + 0.01 * rank, "device", "dev_allreduce", "B",
                dict(a, as_is=True))]
        if rank == 0:
            evs += [(T + 0.20, "device", "dev_dispatch", "B", a),
                    (T + 0.45, "device", "dev_dispatch", "E",
                     dict(a, built=seq in built)),
                    (T + 0.47, "device", "dev_release", "B", a),
                    (T + 0.48, "device", "dev_release", "E", a)]
            out_at = T + 0.48
        else:
            turn = RANKS - 1 - rank
            out_at = T + 0.50 + 0.03 * turn
            evs += [(T + 0.15, "device", "dev_release", "B", a),
                    (out_at, "device", "dev_release", "E",
                     dict(a, turn=turn) if turns else a)]
        evs += [(out_at + 0.01, "device", "dev_allreduce", "E", a),
                (out_at + 0.02, "mpi", "allreduce", "E", None)]
        out += [(H + t / 1000.0, lane, name, ph, args)
                for t, lane, name, ph, args in evs]
    return out


def made_up_context(**over):
    """One op a call on device 0, from T + 0.42 to T + 1.02 ms: 600 us
    busy a call."""
    ops = [("%fusion", (2.0 * k + 0.42) / 1e3, (2.0 * k + 1.02) / 1e3)
           for k in range(len(SEQS))]
    dev = DeviceTrace(0, 0.0, 0.006, [(s, e) for _n, s, e in ops], ops)
    args = dict(
        collective=harness.load_by_name("collectives", "allreduce"),
        config={"expect": {"least_bytes": "slot"}}, traffic={}, ranks=RANKS,
        bytes_per_rank=1 << 26, device_kind="TPU v5 lite",
        peaks={"hbm_GBps": 819.0, "ici_GBps": 200.0},
        window_mono=(H - 1.0, H + 1.0),
        spans={r: spans(r) for r in range(RANKS)}, devices={0: dev},
        rank0_ordinal=0, traced_calls=len(SEQS), clock_offset_s=-H)
    args.update(over)
    return RunContext(**args)


@pytest.fixture
def traced(monkeypatch):
    """The made-up profile is what the run's trace directory holds."""
    monkeypatch.setattr(hostplane, "_last", (None, None))
    monkeypatch.setattr(hostplane.xplane, "newest_trace", lambda d: d)
    monkeypatch.setattr(hostplane.xplane, "load", lambda path: profile())


def test_the_host_plane_readers_on_a_made_up_trace(traced):
    ctx = made_up_context()
    tb = hostplane.tables(ctx)
    assert tb.tie.pairs == 12 and tb.tie.offset_s == pytest.approx(-H)
    # dev_dispatch 0.20 to 0.45, the outer launch event 0.25 to 0.40 of
    # it: 100 us of the 250 are outside the launch
    assert read("launch_runtime_us", ctx) == pytest.approx(150.0, abs=1e-3)
    # seen done at 1.07, launch ended 0.40, 600 us of it the device's
    assert read("result_seen_over_us", ctx) == pytest.approx(70.0, abs=1e-3)
    # from 1.07 to rank 3's next entry at 2.0 - 0.05 + 0.06; seq 7 has
    # no next call
    assert read("result_to_last_entry_us", ctx) == pytest.approx(
        940.0, abs=1e-3)
    assert read("gate_handover_us", ctx) == pytest.approx(30.0, abs=1e-3)
    # a run may move back to its enqueue's start (0.30 - 0.42) and on to
    # where it was seen done (1.07 - 1.02)
    assert read("plane_shift_width_us", ctx) == pytest.approx(170.0, abs=1e-3)
    low, high = harness.load_by_name(
        "layer_metrics", "plane_shift_width_us").fit(ctx)
    assert (low, high) == (pytest.approx(-120e-6), pytest.approx(50e-6))


def test_a_client_that_writes_the_waits(traced, monkeypatch):
    """The CPU client's form: a wait on each waiting thread's line. The
    earliest wait end is when the result was first seen, whatever the
    runtime's threads say."""
    monkeypatch.setitem(WOKEN, 5, {0: 1.10, 1: 1.09, 2: 1.08, 3: 1.05})
    monkeypatch.setitem(WOKEN, 6, {0: 1.10, 2: 1.05})
    monkeypatch.setitem(WOKEN, 7, {3: 1.05})
    ctx = made_up_context()
    assert read("result_seen_over_us", ctx) == pytest.approx(50.0, abs=1e-3)
    assert read("result_to_last_entry_us", ctx) == pytest.approx(
        960.0, abs=1e-3)


def test_a_call_that_built(traced):
    built = made_up_context(spans={r: spans(r, built=(5, 6, 7))
                                   for r in range(RANKS)})
    assert read("launch_runtime_us", built) is None
    assert read("result_seen_over_us", built) is None
    assert read("plane_shift_width_us", built) == pytest.approx(
        170.0, abs=1e-3)


def test_fewer_than_half_the_calls_seen_done(traced, monkeypatch):
    monkeypatch.setattr(sys.modules[__name__], "DONE", {7})
    ctx = made_up_context()
    assert read("result_seen_over_us", ctx) is None
    assert read("launch_runtime_us", ctx) == pytest.approx(150.0, abs=1e-3)
    # seq 7's run alone is paired
    assert read("plane_shift_width_us", ctx) == pytest.approx(170.0, abs=1e-3)
    monkeypatch.setattr(sys.modules[__name__], "DONE", set())
    monkeypatch.setattr(hostplane, "_last", (None, None))
    assert read("plane_shift_width_us", made_up_context()) is None


@pytest.mark.parametrize("what", ["no join in the program", "no device",
                                  "no annotation with a rank",
                                  "no trace on disk"])
def test_nothing_is_read_where_there_is_nothing_to_read(traced, monkeypatch,
                                                        what):
    ctx = made_up_context()
    if what == "no join in the program":        # the parent of the PR
        monkeypatch.setattr(hostplane, "xprof", None)
        ctx.spans = {r: spans(r, turns=False) for r in range(RANKS)}
    elif what == "no device":                   # a CPU rehearsal
        ctx.devices = {}
    elif what == "no annotation with a rank":
        monkeypatch.setattr(hostplane.xplane, "load",
                            lambda path: NS(planes=[]))
        ctx.spans = {r: spans(r, turns=False) for r in range(RANKS)}
    else:
        def gone(path):
            raise FileNotFoundError(path)
        monkeypatch.setattr(hostplane.xplane, "newest_trace", gone)
        ctx.spans = {r: spans(r, turns=False) for r in range(RANKS)}
    for name in NEW:
        assert read(name, ctx) is None, name


def test_the_appended_entries_have_their_files():
    bench = harness.read_json(harness.ROOT, "BENCHMARK.json")
    assert tuple(m["name"] for m in bench["per_layer"][-len(NEW):]) == NEW
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"][-len(NEW):]:
        mod = harness.load_by_name("layer_metrics", m["name"])
        assert mod.NAME == m["name"] and callable(mod.compute)
        assert m["moves"] == "lat_us_p50"
        assert set(m["workloads"]) <= cells - {"osu1.sendrecv.1MiB.dev"}
    assert not any(m["name"] == "hostplane" for m in bench["per_layer"])


def test_a_rehearsal_reads_a_value_or_nothing_and_never_raises(monkeypatch):
    """A traced run of the one-chip cell at a tiny size on the CPU, the
    readers then asked beside a made-up device (one op in the middle of
    every marked iteration): the program's annotations and ``turn`` and
    this jax's launch events are found; what the CPU's trace lacks reads
    as ``None``."""
    import jax
    from chipbench import xplane
    from mvapich2_tpu.parallel.mesh import make_mesh
    kept = {}
    real = harness._reduce_trace

    def keeping(ctx, sh, ids, need_devices):
        real(ctx, sh, ids, need_devices)
        kept["ctx"] = ctx
    monkeypatch.setattr(harness, "_reduce_trace", keeping)
    r = harness.run_cell(
        "osu1.allreduce.4KiB.dev", 2**31 + 53, 1.0, True, time.perf_counter(),
        rehearsal=harness.Rehearsal(
            bytes_per_rank=4096,
            device_mesh=make_mesh((1,), ("x",), jax.devices()[:1])))
    assert r["correct"] is True
    assert set(r["metrics"]) == {"rendezvous_span_us"}  # no device, no time
    ctx = kept["ctx"]
    marks = xplane.annotations(
        xplane.load(xplane.newest_trace(harness.TRACE_DIR)), harness.ITER_MARK)
    lo, hi = marks[0][0], max(e for _s, e, _st in marks)
    ops = [("%made_up", (s + e) / 2 - 1e-6, (s + e) / 2) for s, e, _st in marks]
    ctx.devices[ctx.rank0_ordinal] = DeviceTrace(
        ctx.rank0_ordinal, lo, hi, [(s, e) for _n, s, e in ops], ops)
    monkeypatch.setattr(hostplane, "_last", (None, None))
    tb = hostplane.tables(ctx)
    assert sorted(tb.lines) == list(range(8)) and tb.tie.pairs > 8
    got = {name: read(name, ctx) for name in NEW}
    assert all(v is None or isinstance(v, float) for v in got.values()), got
    assert got["launch_runtime_us"] > 0 and got["gate_handover_us"] > 0
