"""mv2tlint analyzer tests: each pass against its seeded fixture (exact
finding counts AND locations), a zero-findings clean fixture, the
baseline ratchet (suppression, stale-entry strictness), the inline
ignore escape, and the tier-1 gate itself — `mv2tlint --strict` over the
live repo must exit 0."""

import json
import os
import subprocess
import sys

import pytest

from mvapich2_tpu.analysis import core
from mvapich2_tpu.analysis.cli import main as lint_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "lint")

pytestmark = pytest.mark.lint


def _lint(name):
    mods, errs = core.scan_paths([os.path.join(FIXTURES, name)])
    assert not errs
    return core.run_passes(mods)


def _locs(findings, pass_id):
    return [(f.pass_id, f.line) for f in findings if f.pass_id == pass_id]


# -- one seeded fixture per pass: exact counts + locations ---------------

def test_locks_pass_fixture():
    fs = _lint("bad_locks.py")
    assert _locs(fs, "locks") == [("locks", 19)]
    assert len(fs) == 1
    (f,) = fs
    assert "'items'" in f.msg and "_lock" in f.msg and "Hot.bad" in f.msg


def test_tags_pass_fixture():
    fs = _lint("bad_tags.py")
    assert _locs(fs, "tags") == [("tags", 5), ("tags", 6)]
    assert len(fs) == 2
    assert "overlaps ALPHA_TAG_BASE" in fs[0].msg
    assert "dynamic next_coll_tag window" in fs[1].msg


def test_registry_pass_fixture():
    fs = _lint("bad_registry.py")
    assert _locs(fs, "pvars") == [("pvars", 11), ("pvars", 13),
                                  ("pvars", 17), ("pvars", 21),
                                  ("pvars", 25)]
    assert len(fs) == 5
    msgs = "\n".join(f.msg for f in fs)
    assert "badLower" in msgs and "Fixture_Bad" in msgs
    assert "fixture_never_declared" in msgs
    assert "MV2T_NOT_A_CVAR" in msgs and "UNDECLARED_KNOB" in msgs


def test_blocking_pass_fixture():
    fs = _lint("bad_blocking.py")
    assert _locs(fs, "blocking") == [("blocking", 12), ("blocking", 13),
                                     ("blocking", 17)]
    assert len(fs) == 3
    msgs = "\n".join(f.msg for f in fs)
    assert "time.sleep" in msgs and "acquire" in msgs and "wait" in msgs


def test_traceguard_pass_fixture():
    fs = _lint("bad_traceguard.py")
    assert _locs(fs, "traceguard") == [("traceguard", 8),
                                       ("traceguard", 11)]
    assert len(fs) == 2


def test_clean_fixture_zero_findings():
    assert _lint("clean.py") == []


# -- suppression machinery ----------------------------------------------

def test_inline_ignore_comment(tmp_path):
    src = ("class Chan:\n"
           "    def f(self, engine):\n"
           "        tr = engine.tracer\n"
           "        tr.record('mpi', 'y')  # mv2tlint: ignore[traceguard]\n")
    p = tmp_path / "ignored.py"
    p.write_text(src)
    mods, _ = core.scan_paths([str(p)])
    assert core.run_passes(mods) == []


def test_baseline_suppresses_and_ratchets(tmp_path):
    fixture = os.path.join(FIXTURES, "bad_locks.py")
    mods, _ = core.scan_paths([fixture])
    (f,) = core.run_passes(mods)
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps({"suppressions": [
        {"pass": f.pass_id, "path": f.path, "msg": f.msg, "reason": "t"}]}))
    # suppressed: exit 0 even under --strict
    assert lint_main([fixture, "--baseline", str(bl), "--strict"]) == 0
    # a STALE entry (nothing matches it) passes plain mode but fails
    # --strict: the invariant set only ratchets down
    bl.write_text(json.dumps({"suppressions": [
        {"pass": f.pass_id, "path": f.path, "msg": f.msg, "reason": "t"},
        {"pass": "tags", "path": "gone.py", "msg": "fixed long ago",
         "reason": "stale"}]}))
    assert lint_main([fixture, "--baseline", str(bl)]) == 0
    assert lint_main([fixture, "--baseline", str(bl), "--strict"]) == 1


def test_unsuppressed_finding_fails(tmp_path):
    fixture = os.path.join(FIXTURES, "bad_tags.py")
    assert lint_main([fixture, "--no-baseline"]) == 1


def test_write_baseline_roundtrip(tmp_path):
    fixture = os.path.join(FIXTURES, "bad_registry.py")
    bl = tmp_path / "bl.json"
    assert lint_main([fixture, "--baseline", str(bl),
                      "--write-baseline"]) == 0
    assert len(json.load(open(bl))["suppressions"]) == 5
    assert lint_main([fixture, "--baseline", str(bl), "--strict"]) == 0


def test_parse_error_is_a_finding(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    mods, errs = core.scan_paths([str(p)])
    assert not mods and len(errs) == 1 and errs[0].pass_id == "parse"


# -- the tier-1 gate: the live repo is clean under --strict --------------

def test_repo_strict_clean():
    """`mv2tlint --strict` over the package: no new findings, no stale
    baseline entries. THE ratchet — a regression in any of the five
    invariant families fails tier-1 here."""
    assert lint_main(["--strict"]) == 0


def test_bin_entrypoint_ci_invocation():
    """The CI-style command line from the issue, through bin/mv2tlint."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "mv2tlint"),
         "--baseline", "analysis/baseline.json", "--strict"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "0 finding(s)" in r.stdout


def test_list_passes():
    assert lint_main(["--list-passes"]) == 0


# -- the native pass: C-plane atomic discipline + layout -----------------

from mvapich2_tpu.analysis import native as native_mod  # noqa: E402


def _lint_native(name):
    return native_mod.NativeSourcePass(
        [os.path.join(FIXTURES, name)], layout=False).run([])


def test_native_pass_bad_fixture():
    """Seeded C fixture: exact finding count and locations, one per
    protocol family (doorbell plain store, volatile-only lease read,
    order-less __atomic, guarded-by without the lock, raw seqlock
    deref, rationale-less counter, seqlock pairing)."""
    fs = _lint_native("bad_native.c")
    assert [(f.pass_id, f.line) for f in fs] == [
        ("native", 0), ("native", 20), ("native", 26), ("native", 30),
        ("native", 34), ("native", 38), ("native", 57)]
    msgs = "\n".join(f.msg for f in fs)
    assert "doorbell" in msgs and "lease" in msgs
    assert "seqlock(wave)" in msgs and "guarded-by mu" in msgs
    assert "__ATOMIC_" in msgs and "rationale" in msgs
    assert "fanout" in msgs          # pairing: writer without reader


def test_native_pass_clean_fixture():
    assert _lint_native("clean_native.c") == []


def test_native_pass_repo_clean():
    """The committed native tree is clean: zero unbaselined findings
    from the native pass (including the layout cross-check)."""
    fs = native_mod.NativeSourcePass().run([])
    assert fs == [], [f.render() for f in fs]


def test_native_pass_catches_seed_violation_class(tmp_path):
    """Mutation check with teeth: re-introduce the exact class of bug
    fixed in this PR's seed run (plain store to the shared failure
    byte) and prove the pass catches it."""
    src = open(os.path.join(REPO, "native", "cplane.cpp")).read()
    mutated = src.replace(
        "__atomic_store_n(&p->failed[ring_index], 1, __ATOMIC_RELEASE);",
        "p->failed[ring_index] = 1;")
    assert mutated != src
    p = tmp_path / "cplane_mut.cpp"
    p.write_text(mutated)
    fs = native_mod.NativeSourcePass([str(p)], layout=False).run([])
    assert any("'failed' plainly accessed" in f.msg for f in fs), \
        [f.msg for f in fs]


def test_native_layout_mismatch_detected(tmp_path):
    """A drifted cross-language constant is a finding: doctor the
    header's ring-header size away from shm.py's _HEADER."""
    real = open(os.path.join(REPO, "native", "shm_layout.h")).read()
    hdr = tmp_path / "shm_layout.h"
    hdr.write_text(real.replace("#define MV2T_RING_HDR_BYTES 128",
                                "#define MV2T_RING_HDR_BYTES 64"))
    fs = native_mod.NativeSourcePass([], layout=True,
                                     layout_header=str(hdr)).run([])
    assert any("MV2T_RING_HDR_BYTES" in f.msg and "disagree" in f.msg
               for f in fs), [f.msg for f in fs]


def test_native_layout_fpc_drift_detected(tmp_path):
    """Renumbering a fast-path counter slot desyncs the FPC enum from
    shm.py's _FP_COUNTERS — mechanical finding, not convention."""
    real = open(os.path.join(REPO, "native", "shm_layout.h")).read()
    hdr = tmp_path / "shm_layout.h"
    hdr.write_text(real.replace("FPC_DEAD_PEER = 11",
                                "FPC_DEAD_PEER = 12"))
    fs = native_mod.NativeSourcePass([], layout=True,
                                     layout_header=str(hdr)).run([])
    assert any("FPC" in f.msg or "_FP_COUNTERS" in f.msg for f in fs), \
        [f.msg for f in fs]


def test_native_cli_routes_c_paths():
    """mv2tlint accepts C files on the command line and routes them to
    the native pass (fixture mode)."""
    assert lint_main([os.path.join(FIXTURES, "bad_native.c"),
                      "--no-baseline"]) == 1
    assert lint_main([os.path.join(FIXTURES, "clean_native.c"),
                      "--no-baseline"]) == 0


def test_native_pass_in_default_gate():
    """The tier-1 strict gate includes the native pass — a new
    unbaselined native finding fails tier-1 through
    test_repo_strict_clean above."""
    assert any(p.id == "native" for p in core.all_passes())


def test_runtests_tsan_lane_wired():
    """bin/runtests grew the --tsan lane; the Makefile has the variant
    targets and the vetted suppressions file exists."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "runtests"),
         "--help"], capture_output=True, text=True, timeout=60)
    assert "--tsan" in r.stdout and "--lint" in r.stdout
    mk = open(os.path.join(REPO, "native", "Makefile")).read()
    assert "fsanitize=thread" in mk and "tsan/libmpi.so" in mk
    assert os.path.exists(os.path.join(REPO, "native", "tsan.supp"))


def test_watchdog_shared_field_map():
    """The stall watchdog names protocol regions from the native
    pass's shared-field map (seqlock/lease/doorbell forensics)."""
    from mvapich2_tpu.trace import watchdog
    m = watchdog._field_map()
    assert m, "shared-field map is empty"
    assert m["fl_in"]["kind"] == "seqlock"
    assert m["fl_in"]["region"] == "flat"
    assert m["lease"]["kind"] == "atomic"
    assert m["flags"]["region"] == "doorbell"
    assert watchdog._region_tag(m, "lease") == " [atomic(lease)]"
    lines = watchdog._protocol_map_lines(m)
    assert any("seqlock(flat)" in ln for ln in lines)
    assert any("atomic(doorbell)" in ln for ln in lines)


def test_device_engine_under_lint_ratchet():
    """ISSUE 8 satellite: the HBM-streaming kernel modules ride the
    same passes as the host path — pallas_ici / _compat / pallas_ring
    are in the scanned set, their trace site follows the guarded idiom
    (coll/device.py dev_coll_fallback instant), and a seeded violation
    of each class in a device-engine-shaped module is caught."""
    import mvapich2_tpu
    from mvapich2_tpu.analysis import core as acore

    pkg = os.path.dirname(mvapich2_tpu.__file__)
    modules, errors = acore.scan_paths([pkg])
    assert not errors
    names = {os.path.relpath(m.path, pkg) for m in modules}
    for need in ("ops/pallas_ici.py", "ops/_compat.py",
                 "ops/pallas_ring.py"):
        assert need in names, need
    # the committed device modules are clean under the pvars +
    # traceguard passes (no new baseline entries)
    from mvapich2_tpu.analysis.registry import RegistryPass
    from mvapich2_tpu.analysis.traceguard import TraceGuardPass
    dev = [m for m in modules
           if os.path.relpath(m.path, pkg).startswith(("ops/", "bench/"))
           or os.path.relpath(m.path, pkg) == "coll/device.py"]
    fs = RegistryPass().run(modules)   # pvar decls are cross-module
    dev_paths = {m.path for m in dev}
    assert [f for f in fs if f.path in dev_paths] == []
    assert [f for f in TraceGuardPass().run(dev)] == []
    # a seeded unguarded trace site + undeclared pvar in a kernel-shaped
    # module is caught (the ratchet actually bites)
    bad = acore.SourceModule("ops/bad_ici_fixture.py", (
        "from .. import mpit\n"
        "def hbm_ring(tracer):\n"
        "    mpit.pvar('dev_coll_never_declared').inc()\n"
        "    tracer.record('channel', 'x', 'i')\n"))
    assert len(RegistryPass().run(modules + [bad])) == 1
    assert len(TraceGuardPass().run([bad])) == 1


def test_startup_modules_under_lint_ratchet():
    """ISSUE 9 satellite: the startup-path modules (light boot, warm-
    attach daemon, cabi_boot, churn bench) ride the same passes as the
    datapath — they are in the scanned set, clean under the pvars and
    blocking passes, and a seeded violation of each class in a
    daemon-shaped module is caught (the ratchet actually bites)."""
    import mvapich2_tpu
    from mvapich2_tpu.analysis import core as acore

    pkg = os.path.dirname(mvapich2_tpu.__file__)
    modules, errors = acore.scan_paths([pkg])
    assert not errors
    names = {os.path.relpath(m.path, pkg) for m in modules}
    for need in ("runtime/boot.py", "runtime/daemon.py", "cabi_boot.py",
                 "bench/churn.py"):
        assert need in names, need
    from mvapich2_tpu.analysis.blocking import BlockingCallPass
    from mvapich2_tpu.analysis.registry import RegistryPass
    start_paths = {m.path for m in modules
                   if os.path.relpath(m.path, pkg) in
                   ("runtime/boot.py", "runtime/daemon.py",
                    "cabi_boot.py", "bench/churn.py",
                    "transport/shm.py")}
    fs = RegistryPass().run(modules)   # cvar/pvar decls are cross-module
    assert [f for f in fs if f.path in start_paths] == []
    assert [f for f in BlockingCallPass().run(
        [m for m in modules if m.path in start_paths])
        if f.path in start_paths] == []
    # a seeded undeclared-cvar env read + undeclared pvar in a
    # daemon-shaped module is caught
    bad = acore.SourceModule("runtime/bad_daemon_fixture.py", (
        "import os\n"
        "from .. import mpit\n"
        "def claim():\n"
        "    os.environ.get('MV2T_DAEMON_NEVER_DECLARED')\n"
        "    mpit.pvar('daemon_claims_never_declared').inc()\n"))
    assert len(RegistryPass().run(modules + [bad])) == 2


# -- traceguard native half (MV2T_NTRACE gate discipline) ----------------

def test_traceguard_ntrace_fixture():
    """ISSUE 10 satellite: seeded native fixture — raw nt_emit calls
    (one inline-guarded, guards don't substitute for the macro) and a
    gateless MV2T_NTRACE macro definition; the inline-ignored line is
    suppressed. Exact count + locations."""
    from mvapich2_tpu.analysis.traceguard import TraceGuardPass
    p = os.path.join(FIXTURES, "bad_ntrace.c")
    fs = TraceGuardPass(native_sources=[p]).run([])
    assert sorted(_locs(fs, "traceguard")) == [
        ("traceguard", 7),    # gateless macro definition
        ("traceguard", 12),   # raw call on the send path
        ("traceguard", 16),   # raw call behind an inline guard (the
                              # statement spans lines 16-17)
    ]
    assert len(fs) == 3
    msgs = "\n".join(f.msg for f in fs)
    assert "MV2T_NTRACE" in msgs and "nt_emit" in msgs


def test_traceguard_ntrace_committed_tree_clean():
    """The committed native tree satisfies the gate discipline (every
    emit rides the macro; both macro definitions carry the gate or the
    ((void)0) stub)."""
    from mvapich2_tpu.analysis.traceguard import TraceGuardPass
    assert TraceGuardPass().run([]) == []


def test_traceguard_ntrace_mutation_caught(tmp_path):
    """Re-introduce the bug class: copy cplane.cpp's emit pattern with
    the macro bypassed — the pass flags it."""
    from mvapich2_tpu.analysis.traceguard import TraceGuardPass
    p = tmp_path / "mutated.c"
    p.write_text(
        "void nt_emit(void* p, int ev, long a1, long a2);\n"
        "static void ring_bell(void* p, int dst) {\n"
        "  nt_emit(p, 4, dst, 0);\n"
        "}\n")
    fs = TraceGuardPass(native_sources=[str(p)]).run([])
    assert len(fs) == 1 and fs[0].line == 3


# -- the device pass: Pallas DMA/semaphore discipline (ISSUE 12) ---------

def test_device_pass_fixture():
    """Seeded device fixture: exact finding count and locations, one
    per invariant family (dead pending map, early-exit unawaited copy,
    unbound copy, park-without-drain, half-drained remote park,
    unannotated creditless gate, signal-only semaphore, VMEM budget
    blow). A gateless credit op is no finding: the TPU interpreter runs
    the credit handshake, so ungated credit code is exercised code."""
    fs = _lint("bad_device.py")
    assert _locs(fs, "device") == [
        ("device", 17),   # dead pending_ghost map
        ("device", 23),   # early-exit return past started 'ld'
        ("device", 28),   # unbound make_async_copy
        ("device", 35),   # pending_acc parked, never drained
        ("device", 42),   # pending_send drains wait_send only
        ("device", 49),   # gate present but not '# device: hw-only'
        ("device", 58),   # done_sem signaled, never waited
        ("device", 64),   # 256 MiB VMEM scratch > tier cap
    ]
    assert len(fs) == 8
    msgs = "\n".join(f.msg for f in fs)
    assert "pending_ghost" in msgs and "early_exit" in msgs
    assert "wait_recv" in msgs and "hw-only" in msgs
    assert "done_sem" in msgs and "VMEM scratch budget" in msgs


def test_clean_device_fixture_zero_findings():
    assert _lint("clean_device.py") == []


def test_device_pass_in_default_gate():
    """The tier-1 strict gate includes the device and profile passes —
    a new unbaselined finding fails tier-1 through
    test_repo_strict_clean."""
    ids = {p.id for p in core.all_passes()}
    assert {"device", "profile"} <= ids


def test_device_pass_committed_kernels_clean():
    """The committed kernel modules are clean under the device pass —
    every genuine finding of the seed run (dead pending_in map,
    unannotated creditless gates) is FIXED, not baselined."""
    from mvapich2_tpu.analysis.device import DevicePass
    mods, errs = core.scan_paths([os.path.join(REPO, "mvapich2_tpu")])
    assert not errs
    assert DevicePass().run(mods) == []


_BASE_STREAMER = """
from jax.experimental.pallas import tpu as pltpu


class Streamer:
    def __init__(self):
        self.pending_store = {}

    def store(self, src, dst, sem, key):
        st = pltpu.make_async_copy(src, dst, sem)
        st.start()
        self.pending_store[key] = st

    def drain_stores(self):
        for key, h in list(self.pending_store.items()):
            h.wait()
            del self.pending_store[key]
"""

_CHILD_KERNEL = """
from jax.experimental.pallas import tpu as pltpu
{imports}

def kernel(st, x_hbm, o_hbm, sem):
    own = pltpu.make_async_copy(x_hbm, o_hbm, sem)
    own.start()
    st.pending_store["own"] = own
    st.drain_stores()
"""


@pytest.mark.parametrize("imports,findings", [
    ("from .base_streamer import Streamer", 0),
    ("from pkg.ops.base_streamer import Streamer", 0),
    ("from .another import Streamer", 1),
    ("", 1)])
def test_device_pass_counts_a_drain_the_base_class_module_holds(
        tmp_path, imports, findings):
    """A module that parks a started copy into a map of a streamer it
    imports from a sibling device module (ops/pallas_alltoall's own
    block, into ``_RingStreamer.pending_store``) is held to that
    module's drains too; with no such import, or one of a module that
    was not scanned beside it, the park is still a finding."""
    from mvapich2_tpu.analysis.device import DevicePass
    (tmp_path / "base_streamer.py").write_text(_BASE_STREAMER)
    (tmp_path / "child.py").write_text(
        _CHILD_KERNEL.format(imports=imports))
    mods, errs = core.scan_paths([str(tmp_path)])
    assert not errs and len(mods) == 2
    fs = DevicePass(profiles=[]).run(mods)
    assert len(fs) == findings, [f.msg for f in fs]
    assert all("never drained" in f.msg and f.path.endswith("child.py")
               for f in fs)


def test_device_pass_catches_seed_violation_classes(tmp_path):
    """Mutation check with teeth: re-introduce the exact classes fixed
    in this PR's seed run and prove the pass catches each one."""
    from mvapich2_tpu.analysis.device import DevicePass
    src = open(os.path.join(REPO, "mvapich2_tpu", "ops",
                            "pallas_ici.py")).read()
    # (a) the dead pending map that shipped with PR 8
    mut = src.replace(
        "self.pending_send: Dict = {}           # (d, slot) -> remote handle",
        "self.pending_send: Dict = {}           # (d, slot) -> remote handle\n"
        "        self.pending_in: Dict = {}")
    assert mut != src
    # (b) strip one hw-only annotation from a creditless gate
    mut = mut.replace("def _grant(self, d):                      "
                      "# device: hw-only",
                      "def _grant(self, d):")
    p = tmp_path / "pallas_ici_mut.py"
    p.write_text(mut)
    mods, errs = core.scan_paths([str(p)])
    assert not errs
    fs = DevicePass(profiles=[]).run(mods)
    msgs = "\n".join(f.msg for f in fs)
    assert "pending_in" in msgs, msgs
    assert "not annotated '# device: hw-only'" in msgs, msgs
    # (c) delete a wait: the handle leaks out of the kernel
    mut2 = src.replace("        ld.wait()\n", "")
    assert mut2 != src
    p2 = tmp_path / "pallas_ici_mut2.py"
    p2.write_text(mut2)
    mods2, _ = core.scan_paths([str(p2)])
    fs2 = DevicePass(profiles=[]).run(mods2)
    assert any("'ld'" in f.msg and "without a matching wait" in f.msg
               for f in fs2), [f.msg for f in fs2]


def test_device_vmem_budget_rejects_bad_profile(tmp_path):
    """A committed chunk-size/depth combination that cannot fit in VMEM
    is a lint failure, not a Mosaic OOM on the TPU host: a profile
    claiming ici_chunk_bytes=4 MiB blows the scratch budget of the
    committed streaming kernel (3 buffers x 2 dirs x depth 2)."""
    import json as _json

    from mvapich2_tpu.analysis.device import DevicePass
    prof = tmp_path / "cpu_cpu_8.json"
    prof.write_text(_json.dumps({
        "arch_key": "cpu:cpu:8", "format": "mv2t-tuning-profile-v1",
        "profile": {"kernel_params": {"ici_chunk_bytes": 4 << 20}}}))
    mods, _ = core.scan_paths([os.path.join(REPO, "mvapich2_tpu", "ops",
                                            "pallas_ici.py"),
                               os.path.join(REPO, "mvapich2_tpu",
                                            "mpit.py")])
    fs = DevicePass(profiles=[str(prof)]).run(mods)
    assert any("VMEM scratch budget" in f.msg and "cpu_cpu_8.json" in f.msg
               for f in fs), [f.msg for f in fs]
    # the committed profiles fit
    assert DevicePass().run(mods) == []


def test_device_lane_map():
    """The lane map the watchdog/mpistat device sections read: the
    committed streaming engine's pending containers with their drain
    kinds, and the paired credit semaphore."""
    from mvapich2_tpu.analysis.device import device_lane_map
    m = device_lane_map(refresh=True)
    assert m["pending_send"]["kind"] == "pending-map"
    assert m["pending_send"]["remote"] is True
    assert {"wait_send", "wait_recv"} <= set(m["pending_send"]["drains"])
    assert m["pending_store"]["drains"] == ["wait"]
    assert m["cap_sem"]["kind"] == "credit-sem"
    assert m["cap_sem"]["signals"] >= 1 and m["cap_sem"]["waits"] >= 1


def test_watchdog_device_map_lines():
    """PR 7 parity (shared_field_map region tagging): the stall report
    and mpistat share one device-lane protocol map section."""
    from mvapich2_tpu.trace import watchdog
    lines = watchdog.device_map_lines()
    text = "\n".join(lines)
    assert "device-lane protocol map" in text
    assert "pending-map pending_send [remote]" in text
    assert "credit-sem cap_sem" in text


def test_mpistat_device_map_flag(capsys):
    from mvapich2_tpu.trace.mpistat import main as mpistat_main
    assert mpistat_main(["--device-map"]) == 0
    out = capsys.readouterr().out
    assert "pending_send" in out and "cap_sem" in out


# -- the one-sided engine under the device pass (ISSUE 16) ---------------

def test_device_pass_catches_rma_seed_violation_classes(tmp_path):
    """Mutation check with teeth for ops/pallas_rma.py: re-introduce
    the violation classes the device pass guards the one-sided engine
    against — a dead pending map, an unannotated creditless gate, and
    a started fold-operand load whose handle leaks out of the kernel —
    and prove the pass catches each one."""
    from mvapich2_tpu.analysis.device import DevicePass
    src = open(os.path.join(REPO, "mvapich2_tpu", "ops",
                            "pallas_rma.py")).read()
    # (a) a pending map that is never filled or drained
    mut = src.replace(
        "self.pending_store: Dict = {}          # slot -> commit store",
        "self.pending_store: Dict = {}          # slot -> commit store\n"
        "        self.pending_ack: Dict = {}")
    assert mut != src
    # (b) strip the hw-only annotation from the credit re-grant gate
    mut = mut.replace("def _grant(self):                         "
                      "# device: hw-only",
                      "def _grant(self):")
    p = tmp_path / "pallas_rma_mut.py"
    p.write_text(mut)
    mods, errs = core.scan_paths([str(p)])
    assert not errs
    fs = DevicePass(profiles=[]).run(mods)
    msgs = "\n".join(f.msg for f in fs)
    assert "pending_ack" in msgs, msgs
    assert "not annotated '# device: hw-only'" in msgs, msgs
    # (c) drop the park: the started window-operand load leaks out of
    # the accumulate kernel with no wait on any path
    mut2 = src.replace("        st.pending_fold[slot] = ld\n", "")
    assert mut2 != src
    p2 = tmp_path / "pallas_rma_mut2.py"
    p2.write_text(mut2)
    mods2, _ = core.scan_paths([str(p2)])
    fs2 = DevicePass(profiles=[]).run(mods2)
    assert any("'ld'" in f.msg and "without a matching wait" in f.msg
               for f in fs2), [f.msg for f in fs2]


def test_device_lane_map_covers_rma_containers():
    """The lane map the watchdog/mpistat device sections read grows the
    one-sided engine's containers: the fold-operand prefetch map (local,
    drained by wait) rides next to the remote send map."""
    from mvapich2_tpu.analysis.device import device_lane_map
    m = device_lane_map(refresh=True)
    assert m["pending_fold"]["kind"] == "pending-map"
    assert m["pending_fold"]["remote"] is False
    assert m["pending_fold"]["drains"] == ["wait"]
    assert m["pending_fold"]["module"].endswith("pallas_rma.py")


def test_watchdog_device_report_one_sided_counters():
    """The stall report's device section prints the dev_rma_* counter
    line once any one-sided op has run."""
    from types import SimpleNamespace

    from mvapich2_tpu import mpit
    from mvapich2_tpu.trace import watchdog
    mpit.pvar("dev_rma_tier_epoch").inc()
    mpit.pvar("dev_rma_flush").inc()
    ch = SimpleNamespace(rank=0, size=1, rv=None)
    u = SimpleNamespace(comm_world=SimpleNamespace(device_channel=ch))
    text = "\n".join(watchdog._device_report(u))
    assert "one-sided counters:" in text
    assert "dev_rma_tier_epoch" in text and "dev_rma_flush" in text


def test_rma_win_acc_mutex_bounded_and_baseline_empty():
    """The retired r4 baseline entry stays retired: the accumulate
    mutex acquires in rma/win.py are timeout-bounded (the blocking pass
    finds nothing), the locks baseline carries zero suppressions, and
    re-introducing the unbounded acquire is caught again."""
    win = os.path.join(REPO, "mvapich2_tpu", "rma", "win.py")
    mods, errs = core.scan_paths([win])
    assert not errs
    assert [f for f in core.run_passes(mods)
            if f.pass_id in ("blocking", "locks")] == []
    bl = core.load_baseline()
    assert bl.entries == [], bl.entries


def test_rma_win_unbounded_acquire_caught_again(tmp_path):
    """Strip the timeout bound from the _on_cas mutex acquire: the
    blocking pass must flag it — the empty baseline means the finding
    cannot come back silently."""
    src = open(os.path.join(REPO, "mvapich2_tpu", "rma",
                            "win.py")).read()
    mut = src.replace("cma.acquire(timeout=_ACC_MUTEX_TIMEOUT)",
                      "cma.acquire()")
    assert mut != src
    p = tmp_path / "win_mut.py"
    p.write_text(mut)
    mods, _ = core.scan_paths([str(p)])
    fs = [f for f in core.run_passes(mods) if f.pass_id == "blocking"]
    assert fs and any("acquire" in f.msg for f in fs), \
        [f.msg for f in core.run_passes(mods)]


# -- the profile doctor (ISSUE 12 tentpole piece 3) ----------------------

def test_profile_doctor_bad_fixture():
    """Seeded profile JSON: every schema violation class caught —
    unknown keys, filename/arch mismatch, unknown collective/class,
    unregistered algo, non-monotone and non-total bins, unknown
    symbolic edge, bad crossover keys/values, vmem edge past the hard
    wrapper cap, a quant edge below the vmem->hbm edge (ISSUE 15),
    typo'd/invalid kernel params."""
    from mvapich2_tpu.analysis.profilecheck import ProfileDoctorPass
    mods, _ = core.scan_paths([os.path.join(REPO, "mvapich2_tpu")])
    fs = ProfileDoctorPass(
        profile_files=[os.path.join(FIXTURES, "bad_profile.json")]
    ).run(mods)
    msgs = "\n".join(f.msg for f in fs)
    assert len(fs) == 16, msgs
    for needle in ("surprise", "tpu_TPU-v9_8.json", "mystery_section",
                   "non-final open (None) bin", "table not total",
                   "galactic", "warp_speed", "totally_real_algo",
                   "not strictly increasing", "frobnicate",
                   "dev_tier_quux", "not a byte count",
                   "VMEM wrapper cap", "quantized bin would swallow",
                   "ici_chunk_bites", "not a positive integer"):
        assert needle in msgs, needle


def test_profile_doctor_committed_profiles_clean():
    """Every committed arch profile matches the v1 schema — the gate
    the first REAL TPU profile commit (ROADMAP item 1) must pass."""
    from mvapich2_tpu.analysis.profilecheck import ProfileDoctorPass
    mods, _ = core.scan_paths([os.path.join(REPO, "mvapich2_tpu")])
    fs = ProfileDoctorPass().run(mods)
    assert fs == [], [f.render() for f in fs]


def test_profile_doctor_catches_default_table_drift(tmp_path):
    """Mutation: drift a DEFAULT_TABLES edge past its neighbor (the r5
    cliff shape) in a copy of tuning.py — the doctor flags it."""
    from mvapich2_tpu.analysis.profilecheck import ProfileDoctorPass
    src = open(os.path.join(REPO, "mvapich2_tpu", "coll",
                            "tuning.py")).read()
    mut = src.replace('"small": [(16 * 1024, "rd"), ("eager", "ring"),',
                      '"small": [(64 * 1024, "rd"), ("eager", "ring"),')
    assert mut != src
    d = tmp_path / "coll"
    d.mkdir()
    (d / "tuning.py").write_text(mut)
    mods, _ = core.scan_paths([str(d / "tuning.py")])
    fs = ProfileDoctorPass(profile_files=[]).run(mods)
    assert any("not strictly increasing" in f.msg for f in fs), \
        [f.msg for f in fs]
    # and a renamed symbolic edge leaves a dangling alias behind
    mut2 = src.replace('("eager", "ring")', '("eagre", "ring")')
    (d / "tuning.py").write_text(mut2)
    mods2, _ = core.scan_paths([str(d / "tuning.py")])
    fs2 = ProfileDoctorPass(profile_files=[]).run(mods2)
    assert any("unknown symbolic edge 'eagre'" in f.msg for f in fs2), \
        [f.msg for f in fs2]


def test_profile_doctor_cli_routes_json_paths():
    """mv2tlint accepts profile JSONs on the command line and routes
    them to the profile doctor — the 'validate before you commit a new
    arch profile' workflow from the README."""
    assert lint_main([os.path.join(FIXTURES, "bad_profile.json"),
                      "--no-baseline"]) == 1
    committed = os.path.join(REPO, "mvapich2_tpu", "profiles",
                             "cpu_cpu_8.json")
    assert lint_main([committed, "--no-baseline"]) == 0


# -- the cvar/env drift doctor (ISSUE 12 satellite) ----------------------

def test_env_drift_doctor_catches_undeclared_surfaces(tmp_path):
    """Seeded non-python surfaces: a native getenv, a bin script token
    and a README mention of MV2T_ names with no declared cvar are all
    findings; declared/internal names are not."""
    from mvapich2_tpu.analysis.registry import RegistryPass
    c = tmp_path / "rogue.c"
    c.write_text('static int dbg() { return getenv("MV2T_ROGUE_KNOB") '
                 '!= 0; }\n/* MV2T_NOT_A_GETENV_SO_NOT_SCANNED */\n')
    sh = tmp_path / "rogue_script"
    sh.write_text("#!/bin/sh\n: ${MV2T_ROGUE_SCRIPT_KNOB:=1}\n"
                  "echo $MV2T_RANK $MV2T_CC\n")       # internal: exempt
    md = tmp_path / "README.md"
    md.write_text("Set MV2T_ROGUE_DOC_KNOB=1 to win. MV2T_PEER_TIMEOUT "
                  "and MV2T_ALLREDUCE_ALGO are fine.\n")
    mods, _ = core.scan_paths([os.path.join(REPO, "mvapich2_tpu")])
    fs = RegistryPass(doc_sources=[str(c), str(sh), str(md)]).run(mods)
    drift = [f for f in fs if "ROGUE" in f.msg]
    assert len(drift) == 3, [f.msg for f in fs]
    assert not any("MV2T_CC" in f.msg or "MV2T_RANK" in f.msg
                   or "PEER_TIMEOUT" in f.msg
                   or "ALLREDUCE_ALGO" in f.msg for f in fs)


def test_env_drift_doctor_committed_surfaces_clean():
    """native getenv reads, bin/ scripts and the README all resolve
    against the registry — the three genuine seed findings
    (MV2T_CPLANE_DEBUG, MV2T_BENCH_INIT_BUDGET_MS, MV2T_DEVICE_WIN)
    are fixed by declaration, not exempted."""
    from mvapich2_tpu.analysis.registry import RegistryPass
    mods, _ = core.scan_paths([os.path.join(REPO, "mvapich2_tpu")])
    fs = [f for f in RegistryPass().run(mods)
          if "getenv" in f.msg or "mention" in f.msg]
    assert fs == [], [f.render() for f in fs]
    # the fixes are declarations (enumerable via mpiname/MPI_T), not
    # INTERNAL_ENV exemptions
    from mvapich2_tpu.analysis.registry import INTERNAL_ENV
    for env in ("MV2T_CPLANE_DEBUG", "MV2T_BENCH_INIT_BUDGET_MS",
                "MV2T_DEVICE_WIN"):
        assert env not in INTERNAL_ENV


def test_runtests_modelcheck_lane_wired():
    """bin/runtests grew the --modelcheck lane (the exhaustive
    long-horizon model configs) next to --lint/--tsan/--chaos."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "runtests"),
         "--help"], capture_output=True, text=True, timeout=60)
    assert "--modelcheck" in r.stdout


# -- the proto pass: control-plane verification (ISSUE 13) ---------------

_PKG_MODULES_CACHE = []


def _pkg_modules():
    # parsed once per session: SourceModules are read-only for passes,
    # and the ~130-file parse would otherwise repeat per mutation test
    if not _PKG_MODULES_CACHE:
        from mvapich2_tpu.analysis import core as acore
        mods, errs = acore.scan_paths(
            [os.path.join(REPO, "mvapich2_tpu")])
        assert not errs
        _PKG_MODULES_CACHE.append(mods)
    return list(_PKG_MODULES_CACHE[0])


def _mutated_pkg_modules(rel_suffix, transform):
    """The whole-package module set with ONE module's source mutated —
    the reintroduce-the-class harness (key flow is cross-module, so
    the mutation must be judged against the full tree)."""
    from mvapich2_tpu.analysis import core as acore
    out = []
    hit = False
    for m in _pkg_modules():
        if m.relpath.endswith(rel_suffix):
            src = transform(m.text)
            assert src != m.text, f"mutation did not apply to {rel_suffix}"
            out.append(acore.SourceModule(m.path, src))
            hit = True
        else:
            out.append(m)
    assert hit, rel_suffix
    return out


def test_proto_pass_fixture():
    """Seeded control-plane fixture: exact finding count and
    locations, one per invariant class — write-only key, drift pair
    (subsuming its orphans), never-written key, unbounded KVS retry
    loop, non-total wire state, version-skew consumer."""
    fs = _lint("bad_proto.py")
    assert _locs(fs, "proto") == [
        ("proto", 9),    # fixture-orphan-<*> written, never read
        ("proto", 11),   # boot-card-<*> vs boot_card-<*> drift
        ("proto", 16),   # fixture-ghost-<*> read, never written
        ("proto", 26),   # peek_many retry loop without a deadline
        ("proto", 42),   # wire stage 2 entered, never handled
        ("proto", 46),   # FIXTURE_MANIFEST_VERSION skew (no v2 handler)
    ]
    assert len(fs) == 6
    msgs = "\n".join(f.msg for f in fs)
    assert "fixture-orphan-<*>" in msgs and "never read" in msgs
    assert "boot-card-<*> vs boot_card-<*>" in msgs
    assert "fixture-ghost-<*>" in msgs and "blocks forever" in msgs
    assert "unbounded KVS wait" in msgs and "bounded-by" in msgs
    assert "not total" in msgs
    assert "fixture_manifest-v2" in msgs


def test_clean_proto_fixture_zero_findings():
    assert _lint("clean_proto.py") == []


def test_proto_pass_in_default_gate():
    """The tier-1 strict gate runs 10 passes including proto and the
    event-coverage doctor — a new unbaselined control-plane finding
    fails tier-1 through test_repo_strict_clean."""
    ids = [p.id for p in core.all_passes()]
    assert "proto" in ids and "events" in ids and len(ids) == 10


def test_proto_baseline_ratchet_stays_empty():
    """Strict mode for the new pass: the committed baseline carries NO
    proto entries — every genuine finding was fixed by change, and new
    ones cannot be baselined away silently."""
    bl = core.load_baseline()
    assert [e for e in bl.entries if e.get("pass") == "proto"] == []


# -- pass: events (trace event-coverage doctor) --------------------------

def test_events_pass_fixture():
    """Three seeded record sites outside the conformance grammar: a
    literal name, an f-string prefix (mystery_*), and a wrapper whose
    name parameter resolves through its call sites (the _trace_rma
    idiom). The covered literals / prefixes / wildcard-mpi sites stay
    silent, so the counts are exact."""
    fs = _lint("bad_events.py")
    assert _locs(fs, "events") == [("events", 10), ("events", 16),
                                   ("events", 18)]
    assert len(fs) == 3
    msgs = "\n".join(f.msg for f in fs)
    assert "bogus_wait" in msgs and "bogus_pulse" in msgs
    assert "mystery_*" in msgs


def test_events_pass_hist_and_nte_checks():
    """The _MET_HISTS / _NT_EVENTS halves key on trace/native.py being
    among the scanned modules: with the real one alongside the fixture,
    the unknown latency-sample name is a finding, the known one is
    silent, and the repo's own NTE->region map is fully covered by the
    cplane conformance grammar (zero NTE findings)."""
    from mvapich2_tpu.analysis.events import EventCoveragePass
    native = os.path.join(REPO, "mvapich2_tpu", "trace", "native.py")
    mods, errs = core.scan_paths(
        [os.path.join(FIXTURES, "bad_events.py"), native])
    assert not errs
    fs = EventCoveragePass().run(mods)
    assert [(f.line, "lat_bogus_thing" in f.msg) for f in fs
            if "_MET_HISTS" in f.msg] == [(27, True)]
    assert not any("NTE event" in f.msg for f in fs)


def test_events_grammar_exports():
    """The doctor consumes conform.event_grammars()/grammar_covers —
    the same tables the runtime checker matches against, so the static
    and dynamic views cannot drift apart."""
    from mvapich2_tpu.analysis import conform
    grams = conform.event_grammars()
    for layer in ("mpi", "protocol", "channel", "progress", "nbc",
                  "device", "cplane", "metrics"):
        assert layer in grams, layer
    assert conform.grammar_covers("device", "rma_lock")
    assert conform.grammar_covers("nbc", "sched_start")
    assert not conform.grammar_covers("device", "bogus_pulse")
    assert not conform.grammar_covers("nolayer", "anything")


def test_proto_pass_committed_tree_clean():
    """The committed control plane is clean under the proto pass —
    every genuine seed finding (write-only __agent_up_/__agent_exit_
    keys, timeout-less failure-watcher loops, unannotated wire states,
    the missing manifest-v1 handler annotation) is FIXED, not
    baselined."""
    from mvapich2_tpu.analysis.proto import ProtoPass
    assert ProtoPass().run(_pkg_modules()) == []


def test_proto_catches_agent_key_orphan_mutation():
    """Reintroduce the seed class: drop launch_tree's agent-protocol
    consumption and the __agent_up_/__agent_exit_ families go
    write-only again."""
    from mvapich2_tpu.analysis.proto import ProtoPass
    mods = _mutated_pkg_modules(
        "runtime/launcher.py",
        lambda s: s.replace('srv.peek(f"__agent_up_{node}")', "None")
                   .replace('srv.peek(f"__agent_exit_{node}")', "None"))
    fs = ProtoPass().run(mods)
    msgs = "\n".join(f.msg for f in fs)
    assert "'__agent_up_<*>' is written" in msgs, msgs
    assert "'__agent_exit_<*>' is written" in msgs


def test_proto_catches_key_family_drift_mutation():
    """THE motivating class: drift the verdict card's spelling
    (shm-cabi- -> shm_cabi-) on the write side only — the pass names
    both spellings instead of letting np=4 hang silently."""
    from mvapich2_tpu.analysis.proto import ProtoPass
    mods = _mutated_pkg_modules(
        "transport/shm.py",
        lambda s: s.replace('f"shm-cabi-{self.my_rank}": "1" if my_cabi',
                            'f"shm_cabi-{self.my_rank}": "1" if my_cabi'))
    fs = ProtoPass().run(mods)
    assert any("drift" in f.msg and "shm-cabi-<*>" in f.msg
               and "shm_cabi-<*>" in f.msg for f in fs), \
        [f.msg for f in fs]


def test_proto_catches_unbounded_watcher_mutation():
    """Strip the failure watcher's bounded-by annotation: the
    timeout-less retry loop is a finding again."""
    from mvapich2_tpu.analysis.proto import ProtoPass
    mods = _mutated_pkg_modules(
        "runtime/boot.py",
        lambda s: s.replace(
            "# proto: bounded-by(kvs-connection-lifetime)", "", 1))
    fs = ProtoPass().run(mods)
    assert any("unbounded KVS wait" in f.msg
               and f.path.endswith("runtime/boot.py") for f in fs), \
        [f.render() for f in fs]


def test_proto_catches_wire_state_mutations():
    """Strip a wire-state annotation AND add an unreachable stage:
    both the annotation discipline and totality bite."""
    from mvapich2_tpu.analysis.proto import ProtoPass
    mods = _mutated_pkg_modules(
        "transport/shm.py",
        lambda s: s.replace("if self._wire_stage == 1:   # state: wire:1",
                            "if self._wire_stage == 1:")
                   .replace("self._wire_stage = 1\n",
                            "self._wire_stage = 3\n"))
    fs = ProtoPass().run(mods)
    msgs = "\n".join(f.msg for f in fs)
    assert "'# state: wire:1' annotation" in msgs, msgs
    assert "wire state 3 is entered" in msgs


def test_proto_catches_manifest_version_mutations():
    """Bump MANIFEST_VERSION without a v3 handler annotation, and
    strip the existing v1/v2 ones — all are version-skew findings."""
    from mvapich2_tpu.analysis.proto import ProtoPass
    mods = _mutated_pkg_modules(
        "runtime/daemon.py",
        lambda s: s.replace("MANIFEST_VERSION = 3", "MANIFEST_VERSION = 4"))
    fs = ProtoPass().run(mods)
    assert any("manifest-v3" in f.msg for f in fs), [f.msg for f in fs]
    for stripped in ("# proto: manifest-v1", "# proto: manifest-v2"):
        mods = _mutated_pkg_modules(
            "runtime/daemon.py",
            lambda s, stripped=stripped: s.replace(stripped, ""))
        fs = ProtoPass().run(mods)
        want = stripped.split()[-1]
        assert any(want in f.msg for f in fs), [f.msg for f in fs]


def test_proto_state_map():
    """The exported control-plane map (shared_field_map /
    device_lane_map analog): key families with write/read sites, the
    annotated wire states, the version constants."""
    from mvapich2_tpu.analysis.proto import proto_state_map
    m = proto_state_map(refresh=True)
    keys = m["keys"]
    assert keys["shm-cabi-<*>"]["writes"] >= 2
    assert keys["shm-cabi-<*>"]["reads"] >= 1
    assert keys["__failure_ev_<*>"]["writes"] >= 2
    assert keys["tcp-addr-<*>"]["reads"] == 1
    assert set(m["wire_states"]) == {0, 1}
    assert all(v["annotated"] for v in m["wire_states"].values())
    assert m["versions"]["MANIFEST_VERSION"] >= 2
    assert m["versions"]["BOOT_PROTO_VERSION"] >= 1
    assert m["waits"] > 10


def test_watchdog_proto_map_lines():
    """PR 7/12 parity: the stall report and mpistat share one
    control-plane protocol map section."""
    from mvapich2_tpu.trace import watchdog
    lines = watchdog.proto_map_lines()
    text = "\n".join(lines)
    assert "control-plane protocol map" in text
    assert "wire states: 0 @" in text
    assert "MANIFEST_VERSION" in text
    assert "shm-cabi-<*>" in text


def test_watchdog_control_report_section():
    """The live half: per-peer wiring stage + bells + the in-flight
    wire deadline, from a channel-shaped object."""
    from mvapich2_tpu.trace import watchdog

    class FakeChan:
        my_rank = 0
        local_ranks = [0, 1, 2]
        cabi_ranks = {2}
        _wired = False
        _wire_stage = 1
        _peer_bells = {1: "/x"}
        _wire_deadline = 0.0
    import time as _t
    ch = FakeChan()
    ch._wire_deadline = _t.monotonic() + 42.0
    lines = watchdog._control_report(ch)
    text = "\n".join(lines)
    assert "wired=False, wire stage=1" in text
    assert "peer 1: bell set" in text
    assert "peer 2: bell UNSET [C-ABI]" in text
    assert "wire gate, deadline in" in text


def test_mpistat_proto_map_flag(capsys):
    from mvapich2_tpu.trace.mpistat import main as mpistat_main
    assert mpistat_main(["--proto-map"]) == 0
    out = capsys.readouterr().out
    assert "wire states" in out and "shm-cabi-<*>" in out


def test_mpistat_daemon_lines(tmp_path):
    """The daemon claim-cycle section reads one manifest.json — claim
    state, epoch, owner, version."""
    import json as _json

    from mvapich2_tpu.trace.mpistat import daemon_lines
    (tmp_path / "manifest.json").write_text(_json.dumps({
        "version": 2, "daemon_pid": 0,
        "sets": {"n2-r4194304-p268435456": {
            "state": "busy", "epoch": 7, "owner_pid": 12345}}}))
    lines = daemon_lines(str(tmp_path))
    text = "\n".join(lines)
    assert "manifest v2" in text
    assert "n2-r4194304-p268435456: busy epoch=7 owner=12345" in text
    assert daemon_lines(str(tmp_path / "nonexistent")) == []
    # the multi-tenant (v3) rows: occupancy vs quota, queue depth,
    # exec-cache size
    (tmp_path / "manifest.json").write_text(_json.dumps({
        "version": 3, "daemon_pid": 0, "exec_epoch": 2, "qseq": 3,
        "queue": [{"pid": 999, "geokey": "n2-x", "seq": 3}],
        "sets": {"n2-r4194304-p268435456-i0": {
            "geokey": "n2-r4194304-p268435456",
            "state": "busy", "epoch": 7, "owner_pid": 12345}}}))
    text = "\n".join(daemon_lines(str(tmp_path)))
    assert "occupancy: 1 busy / 1 provisioned" in text
    assert "queue depth 1" in text
    assert "exec-cache: 0 executable(s)" in text


def test_proto_cli_routes_runtime_paths():
    """mv2tlint accepts control-plane paths on the command line and
    the proto doctors run on them (fixture mode) — the 'lint the
    module you are editing' workflow."""
    assert lint_main([os.path.join(FIXTURES, "bad_proto.py"),
                      "--no-baseline"]) == 1
    assert lint_main([os.path.join(FIXTURES, "clean_proto.py"),
                      "--no-baseline"]) == 0
    # the committed control-plane modules pass standalone too (their
    # cross-module key peers ride along via the package default gate,
    # so standalone runs only the module-local doctors)
    assert lint_main([os.path.join(REPO, "mvapich2_tpu", "runtime",
                                   "daemon.py"), "--no-baseline"]) == 0


def test_ntrace_layout_mirrors_header():
    """The python mirror of the trace-ring geometry + NTE event table
    (trace/native.py) matches native/shm_layout.h — and a drifted
    mirror IS caught (the layout doctor bites on NTE names)."""
    from mvapich2_tpu.analysis import native as native_mod
    fs = [f for f in native_mod.NativeSourcePass().run([])
          if "NTE" in f.msg or "NTR" in f.msg]
    assert fs == []
    # drifted event table: swap two names in a synthetic mirror
    from mvapich2_tpu.analysis.native import _nte_to_name
    assert _nte_to_name("NTE_FLAT_FANIN") == "flat_fanin"
    assert _nte_to_name("NTE_BELL_RING") == "bell_ring"


# -- ISSUE 17: the metrics subsystem under the lint ratchet ---------------

def test_metrics_modules_under_lint_ratchet():
    """ISSUE 17 satellite: the telemetry modules (metrics package,
    sampler-bearing shm channel, exporter) ride the same passes as the
    datapath — in the scanned set, clean under the pvars + traceguard
    passes — and ONE seeded violation of each python class in a
    metrics-shaped module is caught (the ratchet actually bites)."""
    import mvapich2_tpu
    from mvapich2_tpu.analysis import core as acore

    pkg = os.path.dirname(mvapich2_tpu.__file__)
    modules, errors = acore.scan_paths([pkg])
    assert not errors
    names = {os.path.relpath(m.path, pkg) for m in modules}
    for need in ("metrics/__init__.py", "metrics/hist.py",
                 "metrics/ring.py", "metrics/sampler.py",
                 "metrics/export.py"):
        assert need in names, need
    from mvapich2_tpu.analysis.registry import RegistryPass
    from mvapich2_tpu.analysis.traceguard import TraceGuardPass
    met_paths = {m.path for m in modules
                 if os.path.relpath(m.path, pkg).startswith("metrics/")
                 or os.path.relpath(m.path, pkg) in
                 ("mpit.py", "transport/shm.py", "trace/mpistat.py")}
    fs = RegistryPass().run(modules)   # pvar decls are cross-module
    assert [f for f in fs if f.path in met_paths] == []
    assert [f for f in TraceGuardPass().run(
        [m for m in modules if m.path in met_paths])] == []
    # seeded: a histogram fetched by a name nothing ever declares
    # (RegistryPass) + an unguarded tracer.record beside it
    # (TraceGuardPass) in a sampler-shaped module
    bad = acore.SourceModule("metrics/bad_sampler_fixture.py", (
        "from .. import mpit\n"
        "def tick(tracer):\n"
        "    mpit.pvar('lat_hist_never_declared').rec(3)\n"
        "    tracer.record('channel', 'metrics_tick', 'i')\n"))
    assert len(RegistryPass().run(modules + [bad])) == 1
    assert len(TraceGuardPass().run([bad])) == 1


def test_metrics_layout_drift_detected(tmp_path):
    """The MV2T_MET_* segment geometry is pinned by the layout doctor:
    drifting the header's ring-row count (or any derived stride input)
    away from the trace/native.py mirror is a mechanical finding."""
    real = open(os.path.join(REPO, "native", "shm_layout.h")).read()
    hdr = tmp_path / "shm_layout.h"
    hdr.write_text(real.replace("#define MV2T_MET_RING_ROWS 256",
                                "#define MV2T_MET_RING_ROWS 255"))
    fs = native_mod.NativeSourcePass([], layout=True,
                                     layout_header=str(hdr)).run([])
    assert any("MV2T_MET_RING_ROWS" in f.msg and "disagree" in f.msg
               for f in fs), [f.msg for f in fs]
    # the committed header + mirror agree (no standing finding)
    fs = [f for f in native_mod.NativeSourcePass().run([])
          if "MV2T_MET" in f.msg]
    assert fs == []
