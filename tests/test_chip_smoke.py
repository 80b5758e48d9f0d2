"""CPU rehearsal of chip_smoke.py's phases at tiny sizes.

The script itself refuses to run without a TPU; these tests import its
phases and drive them on the virtual CPU mesh (kernels interpreted), so
a wrong path, argument, reference or pvar count is found here and not on
the chip.
"""

import os
import sys

import jax
import ml_dtypes
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

from mvapich2_tpu import mpit  # noqa: E402
from mvapich2_tpu.utils.config import get_config  # noqa: E402


def test_library_door_on_one_device_slot_channel():
    from mvapich2_tpu.parallel.mesh import make_mesh
    chip0 = mpit.pvar("coll_level_chip").read()
    calls = chip_smoke.library_door(
        seed=7, big=8 * 1024, mid=8 * 256, fft=8 * 512,
        device_mesh=make_mesh((1,), ("x",), jax.devices()[:1]),
        expect_kernel=False)
    # ten phases and the alltoall once more at the benchmark cell's size
    assert calls == 11 * (1 + chip_smoke.STEADY_CALLS)
    assert mpit.pvar("coll_level_chip").read() - chip0 == \
        chip_smoke.NRANKS * calls
    assert "MV2T_ALLREDUCE_ALGO" not in os.environ


def test_launcher_door_counts_the_size_table(monkeypatch, capsys):
    # run the launcher in-process on the 8 virtual CPU devices (what its
    # re-exec'd child does), so the pvars are this process' own
    monkeypatch.setenv("MV2T_VPOD_CHILD", "1")
    ici0 = mpit.pvar("coll_level_ici").read()
    fb0 = chip_smoke.fallback_pvars()
    calls, stats = chip_smoke.launcher_door(
        osu_args=["-m", "4096", "-i", "3", "-x", "1"])
    assert (calls, stats) == (11 * 4 + 1, 11 * 3)
    assert mpit.pvar("coll_level_ici").read() - ici0 == \
        chip_smoke.NRANKS * calls
    # the port's float64 statistics are turned away and counted: what
    # one_chip's proof expects of this door
    turned_away = "dev_coll_fallback_host_dtype"
    assert chip_smoke.fallback_pvars()[turned_away] - fb0[turned_away] == \
        chip_smoke.NRANKS * stats
    assert "No Errors" in capsys.readouterr().out


def test_four_chip_phase_under_the_interpreter(monkeypatch):
    cfg = get_config()
    monkeypatch.setenv("MV2T_ICI_INTERPRET", "1")
    monkeypatch.setenv("MV2T_DEV_TIER_VMEM_MAX", "8192")
    # the committed CPU profile sends large shards back to XLA
    monkeypatch.setenv("MV2T_DEV_TIER_XLA_MIN", "-1")
    cfg.reload()
    try:
        chip_smoke.four_chips(seed=3, scale=2048)
    finally:
        monkeypatch.undo()
        cfg.reload()


def test_fold_phase_under_the_interpreter(monkeypatch, capsys):
    """Two ranks a chip: the fold channel's five collectives on
    device-resident buffers, each bit-equal to numpy, both levels
    counted."""
    cfg = get_config()
    monkeypatch.setenv("MV2T_ICI_INTERPRET", "1")
    monkeypatch.setenv("MV2T_DEV_TIER_VMEM_MAX", "8192")
    monkeypatch.setenv("MV2T_DEV_TIER_XLA_MIN", "-1")
    cfg.reload()
    stacked0 = mpit.pvar("dev_fold_stacked").read()
    operands0 = mpit.pvar("dev_fold_operands").read()
    fused0 = mpit.pvar("dev_fold_fused").read()
    in_ring0 = mpit.pvar("dev_fold_in_ring").read()
    try:
        chip_smoke.fold_phase(seed=3, nbytes=16 * 1024)
    finally:
        monkeypatch.undo()
        cfg.reload()
    out = capsys.readouterr().out
    assert out.count("bit-equal to numpy on 8 ranks over 4 chips") == 6
    # the deposits are device arrays on their chips: the reduce family
    # folds them as they lie (ISSUE 41), inside the mesh program, one
    # launch a call (ISSUE 44), and at 16 KiB, past this test's VMEM
    # edge, inside the ring kernel's fold rounds (ISSUE 49); allgather
    # alone still makes a planar copy a chip. The phase asserts the four
    # itself, and says them
    # ... and the allreduce once more on a dup of the world (ISSUE 55)
    # folds as the world's did
    assert mpit.pvar("dev_fold_stacked").read() - stacked0 == 4 * 1
    assert mpit.pvar("dev_fold_operands").read() - operands0 == 5
    assert mpit.pvar("dev_fold_fused").read() - fused0 == 5
    assert mpit.pvar("dev_fold_in_ring").read() - in_ring0 == 5
    assert "'dev_fold_fused': 5" in out
    assert "'dev_fold_in_ring': 5" in out
    assert "'dev_coll_derived': 8" in out
    assert "allreduce on comm.dup()" in out


def test_derived_comms_step_on_one_device(capsys):
    """The one-chip run's step: eight ranks on one device, a dup, the
    rows, the columns and the reversed world, six collectives each (a
    split's groups reduce in turn here: the interpreter)."""
    from mvapich2_tpu.parallel.mesh import make_mesh
    derived0 = mpit.pvar("dev_coll_derived").read()
    calls = chip_smoke.derived_comms(
        seed=11, nbytes=8 * 512 * 4, at_once=False,
        device_mesh=make_mesh((1,), ("x",), jax.devices()[:1]))
    assert calls == 4 * 6
    assert mpit.pvar("dev_coll_derived").read() - derived0 == \
        chip_smoke.NRANKS * calls
    out = capsys.readouterr().out
    assert out.count("bit-equal to the plain reference") == 4
    assert "columns   (4 group(s) of 2)" in out


@pytest.mark.parametrize("chips", [1, 4])
def test_pt2pt_lane_phase(chips, capsys):
    """Two ranks on one device (the one-chip run) and on two (what
    ``device_mesh=True`` binds where jax has devices to spare: the
    four-chip run): the senders delete, the results hold."""
    from mvapich2_tpu.parallel.mesh import make_mesh
    if chips == 1:
        chip_smoke.pt2pt_lane(seed=5, nbytes=16 * 1024, device_mesh=make_mesh(
            (1,), ("x",), jax.devices()[:1]))
    else:
        chip_smoke.pt2pt_lane(seed=5, nbytes=16 * 1024, d2d=2)
    out = capsys.readouterr().out
    assert f"on {1 if chips == 1 else 2} device(s)" in out
    with pytest.raises(AssertionError):     # the count is held, not said
        chip_smoke.pt2pt_lane(seed=5, nbytes=4096, d2d=1)


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out and "no TPU" in out


def test_inputs_tell_a_bfloat16_sum_from_an_f32_one():
    """Bit-equality with numpy means what ``chipbench``'s ``correct``
    means only if a sum carried in a narrower type would miss it."""
    xs = [chip_smoke.rank_data(7, 1, r, 1024)
          for r in range(chip_smoke.NRANKS)]
    f32 = np.sum(xs, axis=0)
    assert np.array_equal(f32, np.sum(np.asarray(xs, np.float64), axis=0))
    bf16 = np.zeros(1024, ml_dtypes.bfloat16)
    for x in xs:
        bf16 = bf16 + x.astype(ml_dtypes.bfloat16)
    assert not np.array_equal(bf16.astype(np.float32), f32)
