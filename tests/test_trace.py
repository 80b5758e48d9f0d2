"""Distributed event tracing + stall watchdog tests (trace/).

Covers: ring-buffer recorder attach/dump over the thread harness,
Perfetto merge schema + event ordering (enter<=exit, vertex issue before
complete), the bin/mpitrace end-to-end flow on a 4-rank process-mode
allreduce+NBC workload, the one-shot stall watchdog, drain_all leftover
reporting, and the tracing-off overhead guard.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from mvapich2_tpu import mpit, trace
from mvapich2_tpu.runtime.universe import local_universe, run_ranks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _workload(comm):
    comm.allreduce(np.full(64, float(comm.rank + 1)))
    big = np.full(1 << 17, float(comm.rank), np.float64)
    rbig = np.zeros(1 << 17, np.float64)
    comm.sendrecv(big, (comm.rank + 1) % comm.size, 3,
                  rbig, (comm.rank - 1) % comm.size, 3)
    rg = np.zeros(comm.size, np.float64)
    req = comm.iallgather(np.array([comm.rank * 2.0]), rg)
    req.wait()
    assert rg.tolist() == [r * 2.0 for r in range(comm.size)]
    return True


def _check_merged(merged, nranks):
    """Shared schema/ordering assertions for a merged trace."""
    evs = [e for e in merged["traceEvents"] if e["ph"] != "M"]
    assert {e["pid"] for e in evs} == set(range(nranks))
    layers = {e["cat"] for e in evs}
    assert {"mpi", "protocol", "progress", "nbc"} <= layers
    # B/E spans nest per (pid, cat, name): every E matches an open B at
    # an earlier-or-equal timestamp
    stacks = {}
    for e in evs:
        key = (e["pid"], e["cat"], e["name"])
        if e["ph"] == "B":
            stacks.setdefault(key, []).append(e["ts"])
        elif e["ph"] == "E":
            opens = stacks.get(key)
            assert opens, f"E without B: {key}"
            assert opens.pop() <= e["ts"]
    # nbc: per (pid, sched, vid) issue precedes complete
    marks = {}
    for e in evs:
        if e["cat"] != "nbc" or "args" not in e:
            continue
        a = e["args"]
        if e["name"] in ("vertex_issue", "vertex_complete"):
            key = (e["pid"], a["sched"], a["vid"])
            marks.setdefault(key, {})[e["name"]] = e["ts"]
    assert marks, "no nbc vertex events recorded"
    for key, m in marks.items():
        assert "vertex_issue" in m, f"complete without issue: {key}"
        if "vertex_complete" in m:
            assert m["vertex_issue"] <= m["vertex_complete"], key


def test_trace_inprocess_merge_schema_and_ordering(tmp_path, monkeypatch):
    """Thread-harness tracing: 4 ranks dump at finalize; the merged
    Perfetto JSON carries all ranks across >=4 layers with consistent
    event ordering."""
    monkeypatch.setenv("MV2T_TRACE", "1")
    monkeypatch.setenv("MV2T_TRACE_DIR", str(tmp_path))
    assert all(run_ranks(4, _workload))
    dumps = trace.read_dumps(str(tmp_path))
    assert [d["rank"] for d in dumps] == [0, 1, 2, 3]
    merged = trace.merge_dir(str(tmp_path),
                             str(tmp_path / "merged.json"))
    _check_merged(merged, 4)
    # the thread fabric routes through python send_packet, so the
    # channel lane is populated too (process mode may route around it
    # via the C plane's own counters — see README)
    assert "channel" in {e["cat"] for e in merged["traceEvents"]
                         if e["ph"] != "M"}
    assert json.load(open(tmp_path / "merged.json"))["traceEvents"]
    text = trace.summarize(dumps)
    assert "mpi" in text and "nbc" in text


def test_trace_off_is_detached():
    """Default (cvar off): no recorder attaches and the MPI method table
    stays unwrapped after a traced run ends."""
    from mvapich2_tpu import profile

    def body(comm):
        comm.barrier()
        return comm.u.engine.tracer is None

    assert all(run_ranks(2, body))
    assert not profile._installed


def test_trace_ring_buffer_bounded(monkeypatch):
    monkeypatch.setenv("MV2T_TRACE", "1")
    monkeypatch.setenv("MV2T_TRACE_BUF", "256")
    caps = []

    def body(comm):
        for _ in range(50):
            comm.allreduce(np.ones(4))
        caps.append(len(comm.u.engine.tracer.events))
        return True

    assert all(run_ranks(2, body))
    assert all(c <= 256 for c in caps)


def test_mpitrace_end_to_end(tmp_path):
    """Acceptance: bin/mpitrace -np 4 on an allreduce+iallgather+ireduce
    prog produces ONE merged Perfetto JSON with events from all 4 ranks
    across >=4 layers, plus the per-layer summary."""
    out = tmp_path / "merged.json"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "mpitrace"),
         "-np", "4", "--out", str(out), "--dir", str(tmp_path / "dumps"),
         sys.executable,
         os.path.join(REPO, "tests", "progs", "trace_workload_prog.py")],
        cwd=REPO, capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "No Errors" in r.stdout
    assert "# trace summary" in r.stdout
    merged = json.load(open(out))
    _check_merged(merged, 4)
    # conformance stamp (ISSUE 19): a clean tier-1 run replays through
    # the protocol automata violation-free, on BOTH loader paths
    for target in (str(out), str(tmp_path / "dumps")):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "mv2tconform"),
             target], capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, f"{target}:\n{r.stdout}{r.stderr}"
        assert "0 violation(s)" in r.stdout


def test_stall_watchdog_trips_exactly_once(monkeypatch):
    """A receiver that never posts trips the watchdog ONCE, dumping the
    posted/unexpected queues, outstanding requests, and active NBC
    schedules — then the wait keeps going and completes normally."""
    monkeypatch.setenv("MV2T_STALL_TIMEOUT", "0.3")
    before = mpit.pvar("stall_watchdog_trips").read()
    reports = []

    def body(comm):
        if comm.rank == 0:
            nbc_req = comm.ibarrier()       # peer is asleep: stays active
            req = comm.irecv(np.zeros(4), source=1, tag=99)
            comm.u.engine.progress_wait(lambda: req.complete_flag,
                                        timeout=5.0)
            nbc_req.wait()
            reports.append(getattr(comm.u.engine, "_stall_report", ""))
            assert comm.u.engine._stall_tripped
        else:
            time.sleep(1.0)                 # force the stall window
            comm.send(np.ones(4), dest=0, tag=99)
            comm.ibarrier().wait()
        return True

    assert all(run_ranks(2, body))
    assert mpit.pvar("stall_watchdog_trips").read() - before == 1
    rep = reports[0]
    assert "stall watchdog" in rep
    assert "posted receives" in rep and "tag=99" in rep
    assert "unexpected messages" in rep
    assert "outstanding requests" in rep
    assert "active NBC schedules (1)" in rep


def test_stall_watchdog_off_by_default():
    def body(comm):
        assert comm.u.engine._stall_limit is None
        comm.barrier()
        return True

    assert all(run_ranks(2, body))


def test_drain_all_reports_leftover_work():
    """Satellite: drain_all returns how many packets/hook advances it
    retired so Finalize can log leftover traffic."""
    universes = local_universe(2)
    try:
        u0, u1 = universes
        from mvapich2_tpu.core import datatype as dt
        buf = np.ones(8, np.float64)
        u0.protocol.isend(buf, 8, dt.DOUBLE, dest_world=1, comm_src=0,
                          ctx=0, tag=5).wait()
        # the eager packet sits undispatched in rank 1's inbox
        assert u1.engine.drain_all() >= 1
        assert u1.engine.drain_all() == 0   # idempotent once quiet
    finally:
        for u in universes:
            u.finalize()


def test_trace_off_overhead_guard():
    """Satellite: tracing-off adds <5% to an osu_latency-shaped
    ping-pong in process mode (gate + counter unit costs vs measured
    latency; see the prog for the methodology)."""
    r = subprocess.run(
        [sys.executable, "-m", "mvapich2_tpu.run", "-np", "2",
         sys.executable,
         os.path.join(REPO, "tests", "progs", "trace_overhead_prog.py")],
        cwd=REPO, capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "No Errors" in r.stdout


def test_new_nbc_entry_points_profiled():
    """Satellite: ireduce and the v-collectives are on the PMPI
    interposition surface (PROFILED_METHODS) and work end-to-end."""
    from mvapich2_tpu import profile
    for name in ("ireduce", "igatherv", "iscatterv", "iallgatherv",
                 "ialltoallv", "iscan", "ireduce_scatter_block"):
        assert name in profile.PROFILED_METHODS
        assert hasattr(__import__("mvapich2_tpu.core.comm",
                                  fromlist=["Comm"]).Comm, name)

    def body(comm):
        size, rank = comm.size, comm.rank
        out = np.zeros(size, np.float64)
        comm.iallgatherv(np.array([float(rank)]), out,
                         [1] * size).wait()
        assert out.tolist() == [float(r) for r in range(size)]
        rr = np.zeros(2, np.float64)
        comm.ireduce(np.full(2, 1.0), rr, root=0).wait()
        if rank == 0:
            assert rr[0] == size
        sc = np.zeros(1, np.float64)
        comm.iscan(np.array([1.0]), sc).wait()
        assert sc[0] == rank + 1
        rs = np.zeros(1, np.float64)
        comm.ireduce_scatter_block(np.full(size, 1.0), rs).wait()
        assert rs[0] == size
        return True

    with profile.Profiler() as prof:
        assert all(run_ranks(3, body))
    assert prof.calls["iallgatherv"] == 3
    assert prof.calls["ireduce"] == 3
    assert prof.calls["iscan"] == 3
    assert prof.calls["ireduce_scatter_block"] == 3


# -- native C-plane trace ring (ISSUE 10 tentpole) -----------------------

import shutil


def _cplane_events(merged):
    return [e for e in merged["traceEvents"]
            if e.get("ph") != "M" and e.get("cat") == "cplane"]


def test_native_ring_events_in_merged_trace(tmp_path):
    """A traced process-mode job (MV2T_NTRACE follows MV2T_TRACE)
    merges >=3 native C-plane event types into the Perfetto JSON,
    time-aligned with the python layers on the shared monotonic axis."""
    out = tmp_path / "merged.json"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "mpitrace"),
         "-np", "2", "--out", str(out), "--dir", str(tmp_path / "d"),
         sys.executable,
         os.path.join(REPO, "tests", "progs", "trace_workload_prog.py")],
        cwd=REPO, capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    merged = json.load(open(out))
    nt = _cplane_events(merged)
    names = {e["name"] for e in nt}
    assert len(names) >= 3, names
    assert {e["pid"] for e in nt} == {0, 1}
    # time-aligned: native instants land inside the job's overall span
    all_ts = [e["ts"] for e in merged["traceEvents"]
              if e.get("ph") != "M"]
    for e in nt:
        assert min(all_ts) <= e["ts"] <= max(all_ts)
        assert e["ph"] == "i"


def test_native_ring_disable_env(tmp_path):
    """MV2T_NTRACE=0 with tracing on: python layers trace, the cplane
    lane stays empty (the runtime gate works independently)."""
    env = dict(os.environ)
    env.update({"MV2T_TRACE": "1", "MV2T_TRACE_DIR": str(tmp_path),
                "MV2T_NTRACE": "0"})
    r = subprocess.run(
        [sys.executable, "-m", "mvapich2_tpu.run", "-np", "2",
         sys.executable,
         os.path.join(REPO, "tests", "progs", "trace_workload_prog.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    dumps = trace.read_dumps(str(tmp_path))
    assert dumps
    layers = {ev[1] for d in dumps for ev in d["events"]}
    assert "mpi" in layers and "cplane" not in layers


def test_ntrace_drain_survives_owner_unlink(tmp_path):
    """Teardown-skew regression (found as a load-dependent loss of
    ranks' cplane lanes in the mixed-ABI merge): the segment OWNER
    unlinks the .ntrace file at its close, which can precede a slower
    rank's Finalize drain. Each rank holds its own fd from attach time
    and read_ring accepts it — an unlinked-but-open inode stays
    readable, so the lane survives; the path-based read (mpistat's
    attach-from-outside mode) correctly fails once the file is gone."""
    import struct as _struct

    from mvapich2_tpu.trace import native as nt
    path = tmp_path / "ring.ntrace"
    stride = nt._NTR_HDR_BYTES + nt._NTR_RING_EVENTS * nt._NTR_EV_BYTES
    buf = bytearray(nt._NTR_FILE_HDR + stride)
    _struct.pack_into("<Q", buf, nt._NTR_FILE_HDR, 2)   # rank 0 seq=2
    ev_base = nt._NTR_FILE_HDR + nt._NTR_HDR_BYTES
    nt._REC.pack_into(buf, ev_base, 1000, 1, 0, 7, 8)
    nt._REC.pack_into(buf, ev_base + nt._NTR_EV_BYTES, 2000, 2, 1, 9, 0)
    path.write_bytes(buf)
    held = open(path, "rb")
    try:
        os.unlink(path)                      # the owner's close
        evs = nt.read_ring(held, 0)
        assert [(e[0], e[1]) for e in evs] == [(1000, 1), (2000, 2)]
        assert nt.ring_depth(held, 0) == 2
        with pytest.raises(OSError):
            nt.read_ring(str(path), 0)

        class Chan:                          # drain_channel via the fd
            plane = object()
            _ntrace_f = held
            my_rank = 0
            local_index = {0: 0}
        rows = nt.drain_channel(Chan())
        assert len(rows) == 2 and rows[0][2] == nt.event_name(1)
    finally:
        held.close()


@pytest.mark.skipif(
    __import__("shutil").which("gcc") is None
    or __import__("shutil").which("python3-config") is None,
    reason="no C toolchain")
def test_mixed_abi_merged_trace(tmp_path):
    """ISSUE 10 acceptance: a 4-rank job with C-ABI (even) + python
    (odd) ranks under MV2T_TRACE yields ONE merged Perfetto JSON where
    >=3 native C-plane event types appear on BOTH ABIs' ranks,
    correctly interleaved with python mpi spans on the shared clock."""
    import tempfile
    cbin = os.path.join(tempfile.mkdtemp(), "ntrace_cabi_test")
    r = subprocess.run(
        [os.path.join(REPO, "bin", "mpicc"),
         os.path.join(REPO, "tests", "progs", "ntrace_cabi_test.c"),
         "-o", cbin], capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, f"mpicc failed:\n{r.stdout}\n{r.stderr}"
    out = tmp_path / "mixed.json"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "mpitrace"),
         "-np", "4", "--out", str(out), "--dir", str(tmp_path / "d"),
         sys.executable,
         os.path.join(REPO, "tests", "progs", "mixed_trace_prog.py"),
         cbin],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "No Errors" in r.stdout
    merged = json.load(open(out))
    nt = _cplane_events(merged)
    by_pid = {}
    for e in nt:
        by_pid.setdefault(e["pid"], set()).add(e["name"])
    # every rank of BOTH ABIs carries >=3 native event types
    assert set(by_pid) == {0, 1, 2, 3}, by_pid
    for pid, names in by_pid.items():
        assert len(names) >= 3, (pid, names)
    # flat waves visible across the ABI boundary: a C rank folded or
    # fanned in, a python rank fanned out of the SAME tier
    assert "flat_fanin" in by_pid[0] and "flat_fanin" in by_pid[1]
    # python ranks still carry mpi spans, on the same rebased axis
    py_mpi = [e for e in merged["traceEvents"] if e.get("ph") != "M"
              and e.get("cat") == "mpi" and e["pid"] in (1, 3)]
    assert py_mpi
    lo = min(e["ts"] for e in merged["traceEvents"]
             if e.get("ph") != "M")
    hi = max(e["ts"] for e in merged["traceEvents"]
             if e.get("ph") != "M")
    for e in nt:
        assert lo <= e["ts"] <= hi


def test_watchdog_report_carries_native_tail(monkeypatch, tmp_path):
    """ISSUE 10 satellite: a stall report of a process-mode job with
    the native ring armed includes the per-rank C-plane event tail,
    region-tagged via the shared-field map."""
    env = dict(os.environ)
    env.update({"MV2T_NTRACE": "1", "MV2T_STALL_TIMEOUT": "0.5"})
    prog = tmp_path / "stall_prog.py"
    prog.write_text(
        "import sys, time\n"
        "sys.path.insert(0, '.')\n"
        "import numpy as np\n"
        "from mvapich2_tpu import mpi\n"
        "mpi.Init()\n"
        "comm = mpi.COMM_WORLD\n"
        "comm.allreduce(np.ones(8))\n"
        "if comm.rank == 0:\n"
        "    req = comm.irecv(np.zeros(4), source=1, tag=9)\n"
        "    comm.u.engine.progress_wait(lambda: req.complete_flag,\n"
        "                                timeout=8.0)\n"
        "    rep = getattr(comm.u.engine, '_stall_report', '')\n"
        "    assert 'native C-plane trace tail' in rep, rep[:2000]\n"
        "    assert 'flat_fanin' in rep or 'eager_tx' in rep, rep\n"
        "    assert '[seqlock(flat)]' in rep or '[atomic(inbox)]' in rep\n"
        "else:\n"
        "    time.sleep(2.0)\n"
        "    comm.send(np.ones(4), dest=0, tag=9)\n"
        "comm.barrier()\n"
        "if comm.rank == 0:\n"
        "    print('No Errors')\n"
        "mpi.Finalize()\n")
    r = subprocess.run(
        [sys.executable, "-m", "mvapich2_tpu.run", "-np", "2",
         sys.executable, str(prog)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "No Errors" in r.stdout


# -- the recorder's hot path (ISSUE 36) -----------------------------------

def test_record_enters_no_import_and_no_fire_while_no_fault_is_armed(
        monkeypatch):
    """One event while MV2T_FAULTS arms nothing costs the fault table's
    one attribute test: no import statement runs and ``faults.fire`` is
    not called; armed, the ``trace_stamp`` site fires once an event."""
    import builtins

    from mvapich2_tpu import faults
    from mvapich2_tpu.trace.recorder import Recorder
    from mvapich2_tpu.utils.config import get_config
    faults.deconfigure()
    rec = Recorder(0, 256)
    imports, fired = [], []
    real_import, real_fire = builtins.__import__, faults.fire

    def fire(site):
        fired.append(site)
        return real_fire(site)

    def counting_import(name, *a, **kw):
        imports.append(name)
        return real_import(name, *a, **kw)

    monkeypatch.setattr(faults, "fire", fire)
    args = {"seq": 1, "coll": "allreduce"}
    builtins.__import__ = counting_import
    try:
        rec.record("mpi", "allreduce", "B")
        rec.record("device", "dev_arrive", "B", args)
        rec.record("device", "dev_arrive", "E", args, built=True)
        rec.record("channel", "shm_send", "i", bytes=64)
    finally:
        builtins.__import__ = real_import
    assert imports == [] and fired == []
    got = list(rec.events)
    assert [e[1:4] for e in got] == [
        ("mpi", "allreduce", "B"), ("device", "dev_arrive", "B"),
        ("device", "dev_arrive", "E"), ("channel", "shm_send", "i")]
    assert got[0][4] is None
    assert got[1][4] is args            # handed through as the object
    assert got[2][4] == {"seq": 1, "coll": "allreduce", "built": True}
    assert args == {"seq": 1, "coll": "allreduce"}      # and not changed
    assert got[3][4] == {"bytes": 64}
    assert all(isinstance(e[0], float) and len(e) == 5 for e in got)
    assert [e[0] for e in got] == sorted(e[0] for e in got)

    cfg = get_config()
    old = cfg.get("FAULTS", "")
    try:
        cfg.set("FAULTS", "trace_stamp:skip_stamp:0:2")
        assert faults.configure(0) == 1
        rec.record("mpi", "barrier", "B")
        rec.record("mpi", "barrier", "E")       # the second: dropped
        rec.record("mpi", "bcast", "B")
    finally:
        cfg.set("FAULTS", old)
        faults.deconfigure()
    assert fired == ["trace_stamp"] * 3
    assert [e[2:4] for e in list(rec.events)[4:]] == [("barrier", "B"),
                                                      ("bcast", "B")]
