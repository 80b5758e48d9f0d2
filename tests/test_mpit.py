"""MPI_T introspection tests — modeled on the reference's test/mpi/mpi_t
area (cvarwrite, getindex, mpit_vars) plus startup-timestamp checks."""

import numpy as np

from mvapich2_tpu import mpit
from mvapich2_tpu.runtime.universe import run_ranks
from mvapich2_tpu.utils import timestamps
from mvapich2_tpu.utils.config import get_config


def test_cvar_enumeration_and_info():
    n = mpit.cvar_get_num()
    assert n >= 5   # core knobs at minimum
    names = set()
    for i in range(n):
        info = mpit.cvar_get_info(i)
        assert info["name"] and info["env"].startswith("MV2T_")
        assert info["type"] in ("int", "bool", "str", "float")
        names.add(info["name"])
    assert "EAGER_THRESHOLD" in names
    assert "RNDV_PROTOCOL" in names


def test_cvar_read_write_roundtrip():
    i = mpit.cvar_get_index("EAGER_THRESHOLD")
    old = mpit.cvar_read(i)
    try:
        mpit.cvar_write(i, 1234)
        assert mpit.cvar_read(i) == 1234
        assert get_config()["EAGER_THRESHOLD"] == 1234  # same registry
    finally:
        mpit.cvar_write(i, old)


def test_pvar_counters_grow_with_traffic():
    pv_names = mpit._pvars.names()
    assert "recvq_match_attempts" in pv_names
    assert "pt2pt_eager_sent" in pv_names

    sess = mpit.pvar_session_create()
    h_match = sess.handle_alloc("recvq_match_attempts")
    h_eager = sess.handle_alloc("pt2pt_eager_sent")
    h_bytes = sess.handle_alloc("pt2pt_bytes_sent")
    sess.start(h_match)
    sess.start(h_eager)
    sess.start(h_bytes)

    def body(comm):
        buf = np.full(64, comm.rank, dtype=np.float64)
        out = np.zeros(64, dtype=np.float64)
        comm.sendrecv(buf, (comm.rank + 1) % comm.size, 7,
                      out, (comm.rank - 1) % comm.size, 7)
        return True

    run_ranks(4, body)
    assert sess.read(h_match) >= 4          # one recv match per rank
    assert sess.read(h_eager) >= 4          # 64*8B rides eager
    assert sess.read(h_bytes) >= 4 * 64 * 8
    sess.handle_free(h_match)


def test_pvar_session_isolation():
    pv = mpit.pvar("test_isolated_counter", mpit.PVAR_CLASS_COUNTER,
                   "test", "session isolation probe")
    s1 = mpit.pvar_session_create()
    s2 = mpit.pvar_session_create()
    h1 = s1.handle_alloc("test_isolated_counter")
    s1.start(h1)
    pv.inc(5)
    h2 = s2.handle_alloc("test_isolated_counter")
    s2.start(h2)
    pv.inc(2)
    assert s1.read(h1) == 7
    assert s2.read(h2) == 2


def test_coll_algorithm_timers():
    def body(comm):
        out = comm.allreduce(np.ones(16))
        assert out[0] == comm.size
        return True

    sess = mpit.pvar_session_create()
    run_ranks(4, body)
    # some allreduce algorithm timer + counter must now exist and be > 0
    names = [n for n in mpit._pvars.names()
             if n.startswith("coll_allreduce") and n.endswith("_calls")]
    assert names, mpit._pvars.names()
    assert any(mpit._pvars.get(n).read() > 0 for n in names)
    tnames = [n.replace("_calls", "_time") for n in names]
    assert all(mpit._pvars.get(n).klass == mpit.PVAR_CLASS_TIMER
               for n in tnames)


def test_categories():
    cats = mpit.category_names()
    assert "pt2pt" in cats and "coll" in cats
    i = cats.index("pt2pt")
    info = mpit.category_get_info(i)
    assert info["num_cvars"] >= 1
    assert "recvq_match_attempts" in info["pvars"]


def test_channel_and_protocol_pvars_in_categories():
    """The per-channel byte/message counters and the trace/watchdog pvars
    enumerate under category_get_info (mv2_mpit.c channel-counter
    discipline)."""
    import mvapich2_tpu.trace  # noqa: F401  (declares the trace pvars)

    def body(comm):
        comm.sendrecv(np.ones(8), (comm.rank + 1) % comm.size, 1,
                      np.zeros(8), (comm.rank - 1) % comm.size, 1)
        return True

    run_ranks(2, body)
    cats = mpit.category_names()
    assert "channel" in cats and "trace" in cats
    info = mpit.category_get_info(cats.index("channel"))
    assert "chan_local_msgs_sent" in info["pvars"]
    assert "chan_local_bytes_sent" in info["pvars"]
    assert mpit.pvar("chan_local_msgs_sent").read() > 0
    assert mpit.pvar("chan_local_bytes_sent").read() >= 8 * 8
    tinfo = mpit.category_get_info(cats.index("trace"))
    assert "stall_watchdog_trips" in tinfo["pvars"]
    assert "TRACE" in tinfo["cvars"] and "STALL_TIMEOUT" in tinfo["cvars"]
    ptinfo = mpit.category_get_info(cats.index("pt2pt"))
    assert "pt2pt_eager_sent" in ptinfo["pvars"]
    assert "pt2pt_rndv_sent" in ptinfo["pvars"]


def test_analysis_category_knobs():
    """The mv2t-analyze knobs enumerate under the 'analysis' category:
    the MV2T_LOCKCHECK cvar plus the checker/monitor pvars (satellite of
    the mv2tlint PR) — and lint_findings_baseline is a sourced LEVEL
    pvar tracking the committed suppression count."""
    cats = mpit.category_names()
    assert "analysis" in cats
    info = mpit.category_get_info(cats.index("analysis"))
    assert "LOCKCHECK" in info["cvars"]
    for pv in ("lint_findings_baseline", "lockcheck_cycles",
               "lockcheck_edges"):
        assert pv in info["pvars"]
    pv = mpit._pvars.get("lint_findings_baseline")
    assert pv.klass == mpit.PVAR_CLASS_LEVEL
    from mvapich2_tpu.analysis.core import load_baseline
    assert pv.read() == float(len(load_baseline().entries))
    assert mpit.pvar_get_info(
        mpit.pvar_get_index("lint_findings_baseline"))["continuous"]
    for pv_name in ("lockcheck_cycles", "lockcheck_edges"):
        assert mpit._pvars.get(pv_name).klass == mpit.PVAR_CLASS_COUNTER


def test_sourced_pvar_rebound_across_restart():
    """MPI_T session vs a universe restart: a sourced pvar's callable is
    rebound on re-declare (fresh universe), so a session created after
    the restart reads the NEW source — the stale source must not
    survive. Mirrors how progress/cplane counters rebind when process
    mode re-initializes."""
    old_engine = {"polls": 7.0}
    pv = mpit.pvar("test_restart_sourced", mpit.PVAR_CLASS_COUNTER,
                   "test", "restart rebind probe",
                   source=lambda: old_engine["polls"])
    sess = mpit.pvar_session_create()
    h = sess.handle_alloc("test_restart_sourced")
    sess.start(h)
    old_engine["polls"] = 10.0
    assert sess.read(h) == 3.0          # delta against the session base

    # "universe restart": a new owner re-declares with its own source;
    # the registry must swap callables in place (same PVar object)
    new_engine = {"polls": 100.0}
    pv2 = mpit.pvar("test_restart_sourced", mpit.PVAR_CLASS_COUNTER,
                    "test", "restart rebind probe",
                    source=lambda: new_engine["polls"])
    assert pv2 is pv
    assert pv.read() == 100.0           # stale source is gone
    old_engine["polls"] = 99999.0       # the dead universe moves on
    assert pv.read() == 100.0
    sess2 = mpit.pvar_session_create()
    h2 = sess2.handle_alloc("test_restart_sourced")
    sess2.start(h2)
    new_engine["polls"] = 130.0
    assert sess2.read(h2) == 30.0


def test_highwatermark_pvar_session_semantics():
    """Watermark (and level) pvars read INSTANTANEOUS values through a
    session — a delta against the session base would be meaningless —
    and survive a run_ranks restart monotonically."""
    pv = mpit.pvar("test_hwm_probe", mpit.PVAR_CLASS_HIGHWATERMARK,
                   "test", "watermark session probe")
    pv.mark(5.0)
    sess = mpit.pvar_session_create()
    h = sess.handle_alloc("test_hwm_probe")
    sess.start(h)
    assert sess.read(h) == 5.0          # not 0: no delta for watermarks
    pv.mark(3.0)
    assert sess.read(h) == 5.0          # lower mark never regresses
    pv.mark(9.0)
    assert sess.read(h) == 9.0
    # level pvars behave the same through the restart of the owning
    # universe: nbc_scheds_active returns to 0 after each run completes
    run_ranks(2, lambda c: c.ibarrier().wait() or True)
    run_ranks(2, lambda c: c.ibarrier().wait() or True)
    assert mpit.pvar("nbc_scheds_active").read() == 0


def test_progress_poll_pvar():
    i = mpit.pvar_get_index("progress_polls")
    info = mpit.pvar_get_info(i)
    assert info["continuous"] is False
    before = mpit._pvars.get("progress_polls").read()

    def body(comm):
        comm.barrier()
        return True

    run_ranks(2, body)
    assert mpit._pvars.get("progress_polls").read() > before


def test_dump_renders():
    text = mpit.dump()
    assert "recvq_match_attempts" in text


def test_startup_timestamps():
    get_config().set("STARTUP_TIMING", True)
    try:
        ts = timestamps.get_timestamps()
        ts.reset()
        with ts.phase("outer"):
            with ts.phase("inner"):
                pass
        text = ts.render()
        assert "outer" in text and "inner" in text
        # inner is nested one level deeper
        outer_line = next(l for l in text.splitlines() if "outer" in l)
        inner_line = next(l for l in text.splitlines() if "inner" in l)
        assert len(inner_line) - len(inner_line.lstrip()) > \
            len(outer_line) - len(outer_line.lstrip())
    finally:
        get_config().set("STARTUP_TIMING", False)
        timestamps.get_timestamps().reset()


def test_timestamps_disabled_no_overhead():
    ts = timestamps.get_timestamps()
    ts.reset()
    assert not ts.enabled
    with ts.phase("should_not_record"):
        pass
    assert "should_not_record" not in ts.render()


def test_fastpath_category():
    """The fast-path observability counters (ISSUE 5 satellite)
    enumerate under category "fastpath": hit/fallback/wait-outcome
    counters shared by the C ABI's fastpath.c and the python flat
    collective tier, plus the FP_COLL_MAX collective-tier cap cvar
    under "coll"."""
    import mvapich2_tpu.coll.tuning    # noqa: F401  (declares coll cvars)
    import mvapich2_tpu.transport.shm  # noqa: F401  (declares fp pvars)
    cats = mpit.category_names()
    assert "fastpath" in cats
    info = mpit.category_get_info(cats.index("fastpath"))
    for pv in ("fp_hits", "fp_gil_takes", "fp_fallback_dtype",
               "fp_fallback_comm", "fp_fallback_size",
               "fp_fallback_plane", "fp_coll_flat", "fp_coll_flat2",
               "fp_coll_sched", "fp_wait_spin", "fp_wait_bell",
               "fp_flat_progress"):
        assert pv in info["pvars"], pv
        assert mpit._pvars.get(pv).klass == mpit.PVAR_CLASS_COUNTER
    cinfo = mpit.category_get_info(cats.index("coll"))
    assert "FP_COLL_MAX" in cinfo["cvars"]
    # hierarchical flat2 tier cvars (ISSUE 11)
    assert "FLAT2" in cinfo["cvars"]
    assert "FLAT2_GROUP" in cinfo["cvars"]


def test_fastpath_pvars_observable():
    """The fast-path counters move for a real flat-tier workload (the
    plane only exists in process mode, so this drives the launcher)."""
    import subprocess
    import sys as _sys
    from mvapich2_tpu.transport.shm import _load_native
    if _load_native() is None:
        import pytest
        pytest.skip("native plane unavailable")
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prog = os.path.join(repo, "tests", "progs", "fp_pvar_prog.py")
    r = subprocess.run([_sys.executable, "-m", "mvapich2_tpu.run", "-np",
                        "2", _sys.executable, prog], cwd=repo,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "No Errors" in r.stdout
    assert "did not move" not in r.stdout


def test_plane_pvars_observable():
    """The C plane's counters (cp_stats) surface as MPI_T pvars — the
    fast-path hit-rate for a workload is observable through a session
    in-job (mv2_mpit.c:17-39 channel-counter discipline). The plane only
    exists in process mode, so this drives the launcher."""
    import subprocess
    import sys as _sys
    from mvapich2_tpu.transport.shm import _load_native
    if _load_native() is None:
        import pytest
        pytest.skip("native plane unavailable")
    assert mpit.pvar_get_index("cplane_eager_tx") >= 0
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prog = os.path.join(repo, "tests", "progs", "pvar_plane_prog.py")
    r = subprocess.run([_sys.executable, "-m", "mvapich2_tpu.run", "-np",
                        "2", _sys.executable, prog], cwd=repo,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "No Errors" in r.stdout
    assert "did not move" not in r.stdout


def test_device_category():
    """The device-collective engine knobs + fallback counters (ISSUE 8
    satellite) enumerate under category "device": the ICI kernel cvars
    (chunk bytes, pipeline depth, direction, interpret) and the tier /
    fallback pvar family shared by ops/pallas_ici, ops/pallas_ring and
    coll/device — declared in mpit.py so tools see them before any
    jax import."""
    cats = mpit.category_names()
    assert "device" in cats
    info = mpit.category_get_info(cats.index("device"))
    for cv in ("ICI_CHUNK_BYTES", "ICI_PIPELINE_DEPTH", "ICI_BIDIR",
               "ICI_INTERPRET", "DEV_TIER_VMEM_MAX", "DEV_TIER_XLA_MIN",
               "QUANT_COLL", "QUANT_BLOCK", "DEV_TIER_QUANT_MIN"):
        assert cv in info["cvars"], cv
    for pv in ("dev_coll_fallback_size", "dev_coll_fallback_dtype",
               "dev_coll_fallback_shape", "dev_coll_fallback_platform",
               "dev_coll_tier_vmem", "dev_coll_tier_hbm",
               "dev_coll_tier_quant", "dev_coll_quant_bytes_saved"):
        assert pv in info["pvars"], pv
        assert mpit._pvars.get(pv).klass == mpit.PVAR_CLASS_COUNTER
    # cvar surface round-trips through the indexed MPI_T view
    i = mpit.cvar_get_index("ICI_CHUNK_BYTES")
    assert mpit.cvar_get_info(i)["name"] == "ICI_CHUNK_BYTES"
    assert int(mpit.cvar_read(i)) > 0


def test_device_fallback_pvars_move():
    """A pvar session sees the fallback family move when a device
    collective is rejected to the XLA lowering (the once-silent cliff,
    now MPI_T-visible)."""
    from mvapich2_tpu.ops._compat import note_fallback
    sess = mpit.pvar_session_create()
    h = sess.handle_alloc("dev_coll_fallback_size")
    sess.start(h)
    note_fallback("allreduce", "size", 1 << 23, "float32")
    assert sess.read(h) >= 1


def test_device_category_one_sided():
    """The one-sided lane (ISSUE 16) declares its surface in mpit.py
    too: the RMA chunk cvar and the tier/fallback/sync pvar family
    ops/pallas_rma and rma/device share, under the same "device"
    category so mpistat/watchdog enumerate them with the collective
    ones."""
    cats = mpit.category_names()
    info = mpit.category_get_info(cats.index("device"))
    for cv in ("RMA_CHUNK_BYTES", "DEV_RMA_RDMA_MIN",
               "DEV_RMA_QUANT_MIN"):
        assert cv in info["cvars"], cv
    for pv in ("dev_rma_tier_rdma", "dev_rma_tier_quant",
               "dev_rma_tier_epoch", "dev_rma_fallback_noncontig",
               "dev_rma_fallback_platform", "dev_rma_fallback_size",
               "dev_rma_fallback_dtype", "dev_rma_flush",
               "dev_rma_wire_bytes"):
        assert pv in info["pvars"], pv
        assert mpit._pvars.get(pv).klass == mpit.PVAR_CLASS_COUNTER
    # RMA_CHUNK_BYTES round-trips and defaults to "inherit ici" (<= 0)
    i = mpit.cvar_get_index("RMA_CHUNK_BYTES")
    assert mpit.cvar_get_info(i)["name"] == "RMA_CHUNK_BYTES"
    assert int(mpit.cvar_read(i)) <= 0


def test_device_rma_pvars_move():
    """The one-sided fallback counters move through a pvar session
    when an op is rejected to the epoch compiler."""
    from mvapich2_tpu.ops.pallas_rma import note_rma_fallback
    sess = mpit.pvar_session_create()
    h = sess.handle_alloc("dev_rma_fallback_noncontig")
    sess.start(h)
    note_rma_fallback("put", "noncontig", 4096)
    assert sess.read(h) >= 1
