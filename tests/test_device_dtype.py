"""The device path by element type (ISSUE 28): a bfloat16 buffer rides
the mesh, slot and fold channels like a float32 one, every collective
bit-equal to the plain numpy reference (tests/plain_reference.py), and
a call that *is* kept off the device for its dtype is counted and leaves
an instant in the trace.

Mesh programs run the Pallas kernels under the TPU interpreter on a
4-device sub-mesh (as tests/test_chip_smoke.py does); the slot channel
binds its ranks to one device. bfloat16 data are whole numbers with
|v| <= 16, so every partial sum over 8 ranks is exact in bfloat16 and
the order of a reduction cannot show.
"""

import jax
import ml_dtypes
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import plain_reference as ref
from mvapich2_tpu import mpit
from mvapich2_tpu.ops import pallas_alltoall, pallas_ici
from mvapich2_tpu.parallel.mesh import make_mesh
from mvapich2_tpu.runtime.universe import run_ranks
from mvapich2_tpu.utils.config import get_config

BF16 = np.dtype(ml_dtypes.bfloat16)
F32 = np.dtype(np.float32)

# channel -> (ranks, devices bound, class, the level pvars a call bumps)
CHANNELS = {"mesh": (4, 4, "DeviceCollChannel", ("coll_level_ici",)),
            "slot": (8, 1, "HBMSlotChannel", ("coll_level_chip",)),
            "fold": (8, 4, "DeviceFoldChannel",
                     ("coll_level_chip", "coll_level_ici"))}
TURNED_AWAY = "dev_coll_fallback_host_dtype"


@pytest.fixture
def interpreted(monkeypatch):
    """Ring and alltoall kernels under the interpreter, the streaming
    (HBM) tier from 8 KiB up, no XLA crossover."""
    cfg = get_config()
    monkeypatch.setenv("MV2T_ICI_INTERPRET", "1")
    monkeypatch.setenv("MV2T_DEV_TIER_VMEM_MAX", "8192")
    monkeypatch.setenv("MV2T_DEV_TIER_XLA_MIN", "-1")
    cfg.reload()
    yield
    monkeypatch.undo()
    cfg.reload()


def _mesh(channel):
    ndev = CHANNELS[channel][1]
    return make_mesh((ndev,), ("x",), jax.devices()[:ndev])


def _data(seed, rank, n, dtype, bound):
    rng = np.random.default_rng([seed, rank])
    return rng.integers(-bound, bound, size=n, endpoint=True).astype(dtype)


def _fallbacks():
    names = (mpit.pvar_get_info(i)["name"]
             for i in range(mpit.pvar_get_num()))
    return {n: mpit.pvar(n).read() for n in names
            if n.startswith("dev_coll_fallback_")}


def _drive(channel, inputs, call, calls=1, on_device=True):
    """Every rank puts its input on its own device (or keeps it on the
    host) and makes ``call`` ``calls`` times. Returns the last results
    on the host and the pvar rises; asserts the device path carried
    every call: the channel's class, its level pvars up by ranks x
    calls, no ``dev_coll_fallback_*`` up at all, device results on the
    rank's own device."""
    ranks, _ndev, klass, levels = CHANNELS[channel]
    assert len(inputs) == ranks
    watch = levels + ("dev_coll_tier_hbm", "dev_a2a_wire_bytes",
                      "dev_ag_wire_bytes")
    before = {n: mpit.pvar(n).read() for n in watch}
    fb0 = _fallbacks()
    got, on_own = [None] * ranks, [None] * ranks

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == klass
        x = inputs[comm.rank]
        if on_device:
            x = jax.device_put(x, ch.device)
        for _ in range(calls):
            out = call(comm, x)
        if out is not None:
            if on_device:
                on_own[comm.rank] = out.devices() == {ch.device}
            got[comm.rank] = np.asarray(out)

    run_ranks(ranks, app, device_mesh=_mesh(channel))
    rose = {n: mpit.pvar(n).read() - before[n] for n in watch}
    for lv in levels:
        assert rose[lv] == ranks * calls, (lv, rose)
    fb_rose = {n: v - fb0[n] for n, v in _fallbacks().items()
               if v != fb0[n]}
    assert TURNED_AWAY in fb0 and fb_rose == {}, fb_rose
    assert all(o is not False for o in on_own), on_own
    return got, rose


def _bit_equal(got, want):
    assert len(got) == len(want)
    for r, (g, w) in enumerate(zip(got, want)):
        if w is None:
            assert g is None, r
            continue
        assert g is not None and g.dtype == w.dtype and g.shape == w.shape, \
            (r, None if g is None else (g.dtype, g.shape), w.dtype, w.shape)
        bits = np.dtype(f"u{w.dtype.itemsize}")
        assert np.array_equal(g.view(bits), w.view(bits)), \
            (r, int(np.count_nonzero(g.view(bits) != w.view(bits))))


# -- alltoall: both element types, whole and ragged tiles, two channels --
# a bfloat16 tile is (16, 128) = 2048 elements, a float32 one (8, 128) =
# 1024: 4096 elements a pair are whole tiles of both, 1000 of neither

@pytest.mark.parametrize("block", [4096, 1000])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=str)
@pytest.mark.parametrize("channel", ["mesh", "slot"])
def test_alltoall_is_the_plain_reference(interpreted, channel, dtype, block):
    ranks = CHANNELS[channel][0]
    inputs = [_data(2801, r, ranks * block, dtype, 2 ** 20 if dtype == F32
                    else 16) for r in range(ranks)]
    got, rose = _drive(channel, inputs, lambda comm, x: comm.alltoall(x),
                       calls=2)
    _bit_equal(got, ref.alltoall(inputs))
    if channel == "mesh":
        # the streaming kernel carried it, and reckoned its wire: p - 1
        # blocks a rank a call, each rounded up to whole tiles
        assert rose["dev_coll_tier_hbm"] == ranks * 2
        tile = 2048 if dtype == BF16 else 1024
        padded = -(-block // tile) * tile
        assert rose["dev_a2a_wire_bytes"] == \
            ranks * 2 * (ranks - 1) * padded * dtype.itemsize
    else:
        assert rose["dev_a2a_wire_bytes"] == 0


def test_alltoall_wire_bytes_by_hand():
    # the benchmark cell: 48 MiB of bfloat16 a pair is 12288 whole tiles
    assert pallas_alltoall.alltoall_wire_bytes(100663296, BF16, 4) == \
        3 * 50331648
    # 1000 bfloat16 a pair travel as one (16, 128) tile, 4096 B
    assert pallas_alltoall.alltoall_wire_bytes(4000, BF16, 4) == 3 * 4096
    assert pallas_alltoall.alltoall_wire_bytes(4000, F32, 4) == 3 * 4096
    # a skewed alltoallv pads every step to its largest pair (A10)
    assert pallas_alltoall.wire_bytes((8, 16, 0, 24), F32) == 40 * 128 * 4


# -- allgather (ISSUE 34): both element types, a whole-tile shard and an
# FSDP-ragged one, two channels. One expert layer of Moonlight-16B-A3B
# outside its routed experts is 31 199 808 parameters, 7 799 952 a rank
# over four: 60 937.125 rows of 128. Cut to what the interpreter holds,
# 7 799 952 // 2**10 = 7 617 elements are 59.5 rows: no whole row, let
# alone a whole tile; 8 192 are whole tiles of both types.
FSDP_SHARD = 7_799_952
RAGGED = FSDP_SHARD >> 10       # 7 617


@pytest.mark.parametrize("n", [8192, RAGGED])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=str)
@pytest.mark.parametrize("channel", ["mesh", "slot"])
def test_allgather_is_the_plain_reference(interpreted, channel, dtype, n):
    ranks = CHANNELS[channel][0]
    inputs = [_data(3401, r, n, dtype, 2 ** 20 if dtype == F32 else 16)
              for r in range(ranks)]
    got, rose = _drive(channel, inputs, lambda comm, x: comm.allgather(x),
                       calls=2)
    _bit_equal(got, ref.allgather(inputs))
    if channel == "mesh":
        # the streaming ring carried it, and reckoned its wire: p - 1
        # blocks a rank a call, each the shard rounded up to whole tiles
        assert rose["dev_coll_tier_hbm"] == ranks * 2
        tile = 2048 if dtype == BF16 else 1024
        padded = -(-n // tile) * tile
        assert rose["dev_ag_wire_bytes"] == \
            ranks * 2 * (ranks - 1) * padded * dtype.itemsize
    else:
        assert rose["dev_ag_wire_bytes"] == 0
    assert rose["dev_a2a_wire_bytes"] == 0


@pytest.mark.parametrize("n,dtype,p,want", [
    # the benchmark cell: 16 MiB of bfloat16 a rank is 4 096 whole tiles
    (8388608, BF16, 4, 3 * 16777216),
    # Moonlight's own shard: 60 937.125 rows travel as 3 809 (16, 128)
    # tiles, 60 944 rows
    (FSDP_SHARD, BF16, 4, 3 * 60944 * 128 * 2),
    # 7 617 elements: four (16, 128) tiles of bfloat16, eight (8, 128)
    # of float32, 8 192 elements either way
    (RAGGED, BF16, 4, 3 * 8192 * 2),
    (RAGGED, F32, 4, 3 * 8192 * 4),
    (8192, F32, 8, 7 * 8192 * 4),
], ids=["cell", "moonlight", "ragged-bf16", "ragged-f32", "whole-f32"])
def test_all_gather_wire_bytes_by_hand(n, dtype, p, want):
    assert pallas_ici.all_gather_wire_bytes(n, dtype, p) == want


# tokens device i sends expert j, 64 a device on four devices, as the MoE
# step harness routed them: every expert alike; a zipf-like share
# rotated per device; half of every device's tokens on expert 0
MOE_ROUTING = {
    "uniform": ([16, 16, 16, 16],) * 4,
    "skew": ([32, 15, 10, 7], [15, 12, 7, 30], [10, 7, 32, 15],
             [7, 30, 15, 12]),
    "hot": ([40, 8, 8, 8],) * 4,
}


@pytest.mark.parametrize("shape,payload", [
    ("uniform", 1536), ("skew", 1664), ("hot", 1792)])
def test_alltoallv_wire_counts(monkeypatch, shape, payload):
    """ROADMAP A10's three counts, 64 tokens of 8 f32 a device on four
    devices (2 048 B a shard). ``payload`` is what the routing needs to
    move: the busiest rank's off-device elements, 4 B each, the figure
    the CPU-era record this test replaces held under ``wire_bytes``
    (1 536 / 1 664 / 1 792). What the kernel sends is counted in
    ``pallas_alltoall.wire_bytes``' own unit, which is not the record's:
    each permutation step padded to its largest pair and rounded up to
    whole (8, 128) f32 tiles, on the step schedule ``hbm_alltoallv``
    hands its kernel. At this width every pair, the hot expert's 1 280 B
    too, fits one 4 096 B tile: 12 288 B whatever the routing, seven to
    eight times the payload. Pad-to-max proper needs B2's shapes."""
    p, dmodel = 4, 8
    counts = [[c * dmodel for c in row] for row in MOE_ROUTING[shape]]
    assert 4 * max(sum(c for j, c in enumerate(row) if j != i)
                   for i, row in enumerate(counts)) == payload
    seen = []

    def record(blocks, axis_name, p_, step_rows, *rest):
        seen.append(tuple(step_rows))
        return blocks
    monkeypatch.setattr(pallas_alltoall, "_a2a_call", record)
    in_len = pallas_alltoall.packed_displs(counts)[2]
    jax.eval_shape(
        jax.shard_map(lambda v: pallas_alltoall.hbm_alltoallv(
            v, "x", p, counts), mesh=_mesh("mesh"), in_specs=(P("x"),),
            out_specs=P("x"), check_vma=False),
        jax.ShapeDtypeStruct((p * in_len,), F32))
    assert len(seen) == 1
    assert pallas_alltoall.wire_bytes(seen[0], F32) == 3 * 4096


# -- every other collective the gate lets through on bfloat16 ------------

N = 4096        # elements a rank; 8 KiB of bfloat16: the streaming tier


def _allreduce(op):
    from mvapich2_tpu.core import op as opmod
    mpi_op = {"sum": opmod.SUM, "max": opmod.MAX}[op]
    return (lambda comm, x: comm.allreduce(x, op=mpi_op),
            lambda inputs: ref.allreduce(inputs, op))


COLLECTIVES = {
    "allreduce_sum": _allreduce("sum"),
    "allreduce_max": _allreduce("max"),
    "reduce": (lambda comm, x: comm.reduce(x, root=1),
               lambda inputs: ref.reduce(inputs, 1)),
    "bcast": (lambda comm, x: comm.bcast(x, root=2),
              lambda inputs: ref.bcast(inputs, 2)),
    "allgather": (lambda comm, x: comm.allgather(x), ref.allgather),
    "reduce_scatter_block": (lambda comm, x: comm.reduce_scatter_block(x),
                             ref.reduce_scatter_block),
}


@pytest.mark.parametrize("coll", list(COLLECTIVES))
@pytest.mark.parametrize("channel", list(CHANNELS))
def test_bfloat16_collective_is_the_plain_reference(interpreted, channel,
                                                    coll):
    ranks = CHANNELS[channel][0]
    call, reference = COLLECTIVES[coll]
    inputs = [_data(2802, r, N, BF16, 16) for r in range(ranks)]
    got, _rose = _drive(channel, inputs, call)
    _bit_equal(got, reference(inputs))


@pytest.mark.parametrize("coll", ["allreduce_sum", "bcast", "allgather"])
@pytest.mark.parametrize("channel", list(CHANNELS))
def test_bfloat16_host_buffers_are_staged_in_their_own_type(
        interpreted, monkeypatch, channel, coll):
    """Host numpy buffers of a dtype numpy knows only through ml_dtypes,
    over the crossover: each leader's staging branch (``np.asarray``,
    ``np.stack``, the fold channel's ``np.zeros`` rows) keeps the type,
    and the result is written back into the caller's array."""
    monkeypatch.setenv("MV2T_DEVICE_COLL_MIN_BYTES", "1")
    get_config().reload()
    ranks = CHANNELS[channel][0]
    call, reference = COLLECTIVES[coll]
    inputs = [_data(2806, r, N, BF16, 16) for r in range(ranks)]
    got, _rose = _drive(channel, [x.copy() for x in inputs], call,
                        on_device=False)
    _bit_equal(got, reference(inputs))


def test_bfloat16_alltoallv_is_the_plain_reference(interpreted):
    """Skewed counts with a zero pair, dense displacements, host
    buffers (``comm.alltoallv`` hands back its recvbuf), on the mesh
    channel, the only one with a device alltoallv: the leader stages
    each rank's payload into a zero-padded bfloat16 row."""
    counts = ((100, 700, 0, 5), (3, 128, 2048, 9), (0, 0, 1, 4000),
              (777, 12, 12, 12))
    inputs = [_data(2803, r, sum(counts[r]), BF16, 16) for r in range(4)]

    def dense(cs):
        return [int(d) for d in np.cumsum([0] + list(cs[:-1]))]

    def call(comm, x):
        scounts = list(counts[comm.rank])
        rcounts = [counts[s][comm.rank] for s in range(4)]
        recv = np.zeros(sum(rcounts), BF16)
        return comm.alltoallv(x, scounts, dense(scounts), recv, rcounts,
                              dense(rcounts))
    got, rose = _drive("mesh", inputs, call, on_device=False)
    _bit_equal(got, ref.alltoallv(inputs, counts))
    assert rose["dev_coll_tier_hbm"] == 4


# -- a call that is turned away for its dtype is not silent --------------

@pytest.mark.parametrize("channel", ["mesh", "slot"])
def test_a_dtype_turned_away_is_counted_and_traced(monkeypatch, channel):
    """complex64 is no kind the device path carries: the host arm
    moves it (still MPI_Alltoall's answer, back on the rank's device),
    the transport-level count rises once per rank per call, no level
    pvar does, and each call leaves an instant in the trace."""
    monkeypatch.setenv("MV2T_TRACE", "1")
    get_config().reload()
    ranks, _ndev, _klass, levels = CHANNELS[channel]
    inputs = [_data(2804, r, ranks * 64, np.dtype(np.complex64), 16)
              for r in range(ranks)]
    before = {n: mpit.pvar(n).read() for n in levels + (TURNED_AWAY,)}
    got, instants = [None] * ranks, [None] * ranks

    def app(comm):
        x = jax.device_put(inputs[comm.rank], comm.device_channel.device)
        for _ in range(3):
            out = comm.alltoall(x)
        assert out.devices() == {comm.device_channel.device}
        got[comm.rank] = np.asarray(out)
        instants[comm.rank] = [
            a for _t, lane, name, ph, a in comm.u.engine.tracer.events
            if (lane, name, ph) == ("channel", "dev_coll_fallback", "i")]

    try:
        run_ranks(ranks, app, device_mesh=_mesh(channel))
    finally:
        monkeypatch.undo()
        get_config().reload()
    _bit_equal(got, ref.alltoall(inputs))
    rose = {n: mpit.pvar(n).read() - v for n, v in before.items()}
    assert rose[TURNED_AWAY] == ranks * 3
    assert all(rose[lv] == 0 for lv in levels), rose
    for seen in instants:
        assert [(a["coll"], a["reason"], a["dtype"]) for a in seen] == \
            [("alltoall", "host_dtype", "complex64")] * 3


def test_a_host_buffer_under_the_crossover_is_not_turned_away():
    """The count is of calls the dtype *alone* kept off the device: a
    small float64 host buffer goes to the host arm for its size."""
    before = mpit.pvar(TURNED_AWAY).read()

    def app(comm):
        out = comm.allreduce(np.full(8, float(comm.rank), np.float64))
        assert out[0] == sum(range(comm.size))

    run_ranks(4, app, device_mesh=_mesh("mesh"))
    assert mpit.pvar(TURNED_AWAY).read() == before


def test_the_alltoall_call_carries_its_wire_bytes_in_the_trace(
        interpreted, monkeypatch):
    """One ``dev_a2a_wire`` instant a call in the device lane, under the
    call's own ``seq``, with the count the pvar sums."""
    monkeypatch.setenv("MV2T_TRACE", "1")
    get_config().reload()
    inputs = [_data(2805, r, 4 * 1000, BF16, 16) for r in range(4)]
    lanes = [None] * 4

    def app(comm):
        x = jax.device_put(inputs[comm.rank], comm.device_channel.device)
        comm.allreduce(x)               # seq 1: no wire instant
        comm.alltoall(x)                # seq 2
        # waited for: a kernel left in flight keeps the interpreter's
        # process-wide state into the next test
        jax.block_until_ready(comm.alltoall(x))     # seq 3
        lanes[comm.rank] = [e for e in comm.u.engine.tracer.events
                            if e[1] == "device"]

    run_ranks(4, app, device_mesh=_mesh("mesh"))
    for lane in lanes:
        wires = [(a["seq"], a["coll"], a["wire_bytes"])
                 for _t, _l, name, ph, a in lane
                 if name == "dev_a2a_wire" and ph == "i"]
        assert wires == [(2, "alltoall", 3 * 4096), (3, "alltoall", 3 * 4096)]
        begun = [a["seq"] for _t, _l, name, ph, a in lane
                 if name == "dev_alltoall" and ph == "B"]
        assert begun == [2, 3]


def test_the_allgather_call_carries_its_wire_bytes_in_the_trace(
        interpreted, monkeypatch):
    """One ``dev_ag_wire`` instant a call in the device lane, under the
    call's own ``seq``, with the count the pvar sums; an alltoall
    between two of them leaves none of this name."""
    monkeypatch.setenv("MV2T_TRACE", "1")
    get_config().reload()
    inputs = [_data(3402, r, RAGGED, BF16, 16) for r in range(4)]
    lanes = [None] * 4
    before = mpit.pvar("dev_ag_wire_bytes").read()

    def app(comm):
        x = jax.device_put(inputs[comm.rank], comm.device_channel.device)
        comm.allgather(x)               # seq 1
        comm.alltoall(x[:4096])         # seq 2: the other kernel's wire
        jax.block_until_ready(comm.allgather(x))    # seq 3 (waited for)
        lanes[comm.rank] = [e for e in comm.u.engine.tracer.events
                            if e[1] == "device"]

    run_ranks(4, app, device_mesh=_mesh("mesh"))
    wire = 3 * 8192 * 2
    assert mpit.pvar("dev_ag_wire_bytes").read() - before == 4 * 2 * wire
    for lane in lanes:
        wires = [(a["seq"], a["coll"], a["wire_bytes"])
                 for _t, _l, name, ph, a in lane
                 if name == "dev_ag_wire" and ph == "i"]
        assert wires == [(1, "allgather", wire), (3, "allgather", wire)]
        begun = [a["seq"] for _t, _l, name, ph, a in lane
                 if name == "dev_allgather" and ph == "B"]
        assert begun == [1, 3]
