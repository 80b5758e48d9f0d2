"""``comm.reduce_scatter_block`` on the 1:1 mesh channel (ISSUE 42): the
ring's fold rounds alone on the chunked HBM streamer
(``mv2t_hbm_reduce_scatter``, interpreted on four CPU devices), bit-equal
to the plain reference (tests/plain_reference.py) for both element
types, three ops and whole-tile and ragged blocks; the call counts
itself (``dev_coll_tier_hbm``, ``dev_rs_wire_bytes``, the ``dev_rs_wire``
instant), a planned call as a deciding one; and a call the kernel cannot
take, or a platform it cannot run on, is counted where it was silent.
"""

import functools

import jax
import ml_dtypes
import numpy as np
import pytest

import plain_reference as ref
from mvapich2_tpu import mpit
from mvapich2_tpu.core import op as opmod
from mvapich2_tpu.ops import pallas_ici
from mvapich2_tpu.parallel.mesh import make_mesh
from mvapich2_tpu.runtime.universe import run_ranks
from mvapich2_tpu.utils.config import get_config

BF16 = np.dtype(ml_dtypes.bfloat16)
F32 = np.dtype(np.float32)
P4 = 4
OPS = {"sum": opmod.SUM, "max": opmod.MAX, "min": opmod.MIN}
WATCH = ("coll_level_chip", "coll_level_ici", "dev_coll_tier_hbm",
         "dev_coll_tier_vmem", "dev_rs_wire_bytes", "dev_call_plan_hit",
         "dev_call_plan_filed")


def _env(monkeypatch, **env):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    get_config().reload()


@pytest.fixture
def interpreted(monkeypatch):
    """The ring kernels under the interpreter, no XLA crossover. The
    VMEM edge at 8 KiB puts the small cases in the ``vmem`` bin, which
    has no reduce-scatter entry and streams all the same."""
    _env(monkeypatch, MV2T_ICI_INTERPRET="1", MV2T_DEV_TIER_VMEM_MAX="8192",
         MV2T_DEV_TIER_XLA_MIN="-1")
    yield
    monkeypatch.undo()
    get_config().reload()


def _fallbacks():
    names = (mpit.pvar_get_info(i)["name"]
             for i in range(mpit.pvar_get_num()))
    return {n: mpit.pvar(n).read() for n in names
            if n.startswith("dev_coll_fallback_")}


def _data(seed, n, dtype, ranks=P4):
    """Whole numbers whose sums over the ranks are exact in ``dtype``."""
    bound = 2 ** 20 if dtype == F32 else 16
    return [np.random.default_rng([seed, r]).integers(
        -bound, bound, size=n, endpoint=True).astype(dtype)
        for r in range(ranks)]


def _drive(inputs, op="sum", calls=2, traced=False,
           channel="DeviceCollChannel"):
    """One rank an input over four devices (one a device, or two on the
    fold channel), each calling ``calls`` times on its own
    device-resident flat array. Returns the last results on the host,
    what the watched pvars and the fallback family rose by and, traced,
    every rank's ``device``-lane events."""
    before = {n: mpit.pvar(n).read() for n in WATCH}
    fb0 = _fallbacks()
    ranks = len(inputs)
    got, lanes = [None] * ranks, [None] * ranks

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == channel
        x = jax.device_put(inputs[comm.rank], ch.device)
        for _ in range(calls):
            out = jax.block_until_ready(
                comm.reduce_scatter_block(x, op=OPS[op]))
        assert out.devices() == {ch.device} and out.ndim == 1
        got[comm.rank] = np.asarray(out)
        if traced:
            lanes[comm.rank] = [e for e in comm.u.engine.tracer.events
                                if e[1] == "device"]

    run_ranks(ranks, app, device_mesh=make_mesh((P4,), ("x",),
                                                jax.devices()[:P4]))
    rose = {n: mpit.pvar(n).read() - before[n] for n in WATCH}
    fb = {n: v - fb0[n] for n, v in _fallbacks().items() if v != fb0[n]}
    return got, rose, fb, lanes


def _bit_equal(got, want):
    for r, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == w.dtype and g.shape == w.shape, (r, g.shape)
        bits = np.dtype(f"u{w.dtype.itemsize}")
        assert np.array_equal(g.view(bits), w.view(bits)), r


# a float32 tile is (8, 128) = 1024 elements, a bfloat16 one (16, 128) =
# 2048: a block of 4096 is whole tiles of both, one of 1000 of neither
@pytest.mark.parametrize("block", [4096, 1000])
@pytest.mark.parametrize("op", list(OPS))
@pytest.mark.parametrize("dtype", [F32, BF16], ids=str)
def test_the_ring_is_the_plain_reference_and_counts_itself(interpreted,
                                                           dtype, op, block):
    inputs = _data(4201, P4 * block, dtype)
    got, rose, fb, _ = _drive(inputs, op, calls=2)
    _bit_equal(got, ref.reduce_scatter_block(inputs, op))
    # the tier the program took, a rank a call; the second call ran the
    # plan the first filed and counted as it did
    assert rose["coll_level_ici"] == rose["dev_coll_tier_hbm"] == P4 * 2
    assert rose["dev_coll_tier_vmem"] == 0 and fb == {}
    assert rose["dev_call_plan_filed"] == rose["dev_call_plan_hit"] == P4
    wire = pallas_ici.reduce_scatter_wire_bytes(P4 * block, dtype, P4)
    assert rose["dev_rs_wire_bytes"] == P4 * 2 * wire
    tile = 1024 if dtype == F32 else 2048
    assert wire == 3 * -(-block // tile) * tile * dtype.itemsize


def test_the_wire_instant_on_the_first_call_and_on_a_planned_one(
        interpreted, monkeypatch):
    """One ``dev_rs_wire`` instant a call in the device lane, under the
    call's own ``seq``, with the count the pvar sums: call 1 decides,
    calls 2 and 3 run its plan."""
    _env(monkeypatch, MV2T_TRACE="1")
    inputs = _data(4202, P4 * 1000, F32)
    got, rose, fb, lanes = _drive(inputs, calls=3, traced=True)
    _bit_equal(got, ref.reduce_scatter_block(inputs))
    wire = 3 * 1024 * 4
    assert rose["dev_rs_wire_bytes"] == P4 * 3 * wire and fb == {}
    assert rose["dev_call_plan_hit"] == P4 * 2
    for lane in lanes:
        wires = [(a["seq"], a["coll"], a["wire_bytes"])
                 for _t, _l, name, ph, a in lane
                 if name == "dev_rs_wire" and ph == "i"]
        assert wires == [(s, "reduce_scatter_block", wire) for s in (1, 2, 3)]
        begun = [(a["seq"], a["tier"], a["planned"])
                 for _t, _l, name, ph, a in lane
                 if name == "dev_reduce_scatter_block" and ph == "B"]
        assert begun == [(1, "hbm", False), (2, "hbm", True),
                         (3, "hbm", True)]


def test_wire_bytes_by_hand():
    rs = pallas_ici.reduce_scatter_wire_bytes
    # the cell: 128 MiB of float32 a rank over four chips, 32 MiB blocks
    # of 65 536 whole rows: three of them leave every chip
    assert rs(33_554_432, F32, 4) == 3 * 65_536 * 128 * 4 == 100_663_296
    # Moonlight's own layer: 7 799 952 elements a block, 60 937.125 rows,
    # sent as 60 944 (whole (8, 128) tiles)
    assert rs(31_199_808, F32, 4) == 3 * 60_944 * 128 * 4
    # a block of 1000: one float32 tile, or one bfloat16 tile of twice
    # the rows at half the bytes
    assert rs(4000, F32, 4) == rs(4000, BF16, 4) == 3 * 4096
    # p not dividing n: the block is the ceiling (the kernel pads)
    assert rs(4001, F32, 4) == 3 * 4096 and rs(4097, F32, 4) == 3 * 8192
    assert rs(1024, F32, 2) == 4096 and rs(8 * 1024, F32, 8) == 7 * 4096


def test_one_rule_says_the_tier_for_dispatcher_and_channel(interpreted):
    """``planned_tier`` folds the bins that have no reduce-scatter
    entry into the streamer; the XLA takes keep their reasons."""
    tier = functools.partial(pallas_ici.planned_tier, "reduce_scatter_block")
    assert tier(4096, F32, "sum") == ("hbm", None)          # the vmem bin
    assert tier(128 << 20, F32, "max") == ("hbm", None)
    assert tier(4096, F32, "band") == ("xla", "dtype")
    assert tier(4096, np.dtype(bool), "sum") == ("xla", "dtype")
    assert tier(0, F32, "sum") == ("xla", "shape")
    assert tier(4096, F32, "sum", interpret=False) == ("xla", "platform")


def test_an_op_the_kernel_cannot_take_is_counted_and_right(interpreted,
                                                           monkeypatch):
    """The rule turns ``max`` away (as it would an op the streamer had
    no reducer for): channel and dispatcher both hear it, the call
    counts ``dev_coll_fallback_dtype`` and no kernel tier, and XLA's
    allreduce-then-slice hands back the same bits."""
    monkeypatch.setattr(pallas_ici, "_SUPPORTED_OPS", ("sum",))
    inputs = _data(4203, P4 * 1000, F32)
    got, rose, fb, _ = _drive(inputs, "max", calls=2)
    _bit_equal(got, ref.reduce_scatter_block(inputs, "max"))
    assert rose["dev_coll_tier_hbm"] == rose["dev_rs_wire_bytes"] == 0
    assert rose["coll_level_ici"] == P4 * 2
    # a rank a call from the channel, and once more for each time the
    # dispatcher is traced
    assert set(fb) == {"dev_coll_fallback_dtype"}
    assert fb["dev_coll_fallback_dtype"] >= P4 * 2


def test_a_cpu_that_does_not_interpret_says_so():
    """Off the TPU and without MV2T_ICI_INTERPRET the call takes XLA's
    lowering, as it always did there, and is now counted as every other
    collective's: ``dev_coll_fallback_platform``."""
    assert not get_config()["ICI_INTERPRET"]
    inputs = _data(4204, P4 * 1000, BF16)
    got, rose, fb, _ = _drive(inputs, "sum", calls=2)
    _bit_equal(got, ref.reduce_scatter_block(inputs))
    assert rose["dev_coll_tier_hbm"] == rose["dev_rs_wire_bytes"] == 0
    assert set(fb) == {"dev_coll_fallback_platform"}
    assert fb["dev_coll_fallback_platform"] >= P4 * 2


def test_two_ranks_a_chip_run_the_ring_at_level_two_and_say_so(interpreted):
    """The fold channel's level 2 is the same 1-D program over the four
    chips' folds, so it runs the ring too and counts its tier; the wire
    count stays the 1:1 binding's (a chip's fold is not a rank's
    deposit), and the hand-out is still one eager slice a rank."""
    ranks = 8
    inputs = _data(4205, ranks * 1024, F32, ranks)
    got, rose, fb, _ = _drive(inputs, calls=2, channel="DeviceFoldChannel")
    _bit_equal(got, ref.reduce_scatter_block(inputs))
    assert rose["coll_level_chip"] == rose["coll_level_ici"] == ranks * 2
    assert rose["dev_coll_tier_hbm"] == ranks * 2
    assert rose["dev_rs_wire_bytes"] == 0 and fb == {}
