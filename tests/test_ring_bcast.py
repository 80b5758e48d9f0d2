"""The streaming broadcast (ops/pallas_ici ``hbm_ring_bcast``,
``mv2t_hbm_bcast``) under the TPU interpreter on virtual CPU devices,
held bit for bit to the plain numpy reference: every root, three
dtypes, whole tiles, a ragged length and a single tile, two shards and
four. Every operand but the root's is a sentinel (NaN, or the type's
lowest value), so a read of one shows in the result. Then the chain's
static schedule by hand, what the kernel says it puts on the wire, and
the tier rule's answers for a broadcast.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from mvapich2_tpu.ops import pallas_ici  # noqa: E402
from mvapich2_tpu.parallel import MeshComm, make_mesh  # noqa: E402
from mvapich2_tpu.utils.config import get_config  # noqa: E402

from plain_reference import bcast as reference  # noqa: E402

ROW = 128
KiB, MiB = 1 << 10, 1 << 20


@pytest.fixture(scope="module")
def comms():
    return {p: MeshComm(make_mesh((p,), ("x",), jax.devices()[:p]))
            for p in (2, 4)}


def _elements(length, dtype):
    """Whole tiles: several chunks a lane; ragged: the last tile part filled; one tile: the second lane
    stays empty."""
    tile = pallas_ici._sublanes(dtype) * ROW
    return {"whole": 12 * tile, "ragged": 5 * tile - 37, "tile": tile}[length]


def _inputs(p, root, m, dtype):
    """One flat array a shard: the root's the payload, every other a
    sentinel no payload element equals."""
    dt = np.dtype(dtype)
    sentinel = (np.nan if jnp.issubdtype(dt, jnp.floating)
                else np.iinfo(dt).min)
    xs = [np.full(m, sentinel, dt) for _ in range(p)]
    xs[root] = (np.random.default_rng([p, root, m]).integers(
        -1000, 1000, m)).astype(dt)
    return xs


@pytest.mark.parametrize("length", ["whole", "ragged", "tile"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32", "int32"])
@pytest.mark.parametrize("p,root", [(2, 0), (2, 1), (4, 0), (4, 1),
                                    (4, 2), (4, 3)])
def test_bcast_bit_equal_and_reads_no_other_operand(comms, p, root, dtype,
                                                    length):
    dt = jnp.dtype(dtype)
    m = _elements(length, dt)
    xs = _inputs(p, root, m, dt)
    # a 2-tile chunk: 3 chunks a lane of the whole-tile payload, past
    # the pipeline's depth, every step traced as it is
    chunk = 2 * pallas_ici._sublanes(dt) * ROW * dt.itemsize
    out = comms[p].run(lambda s: pallas_ici.hbm_ring_bcast(
        s, "x", p, root, chunk_bytes=chunk, interpret=True),
        jnp.asarray(np.concatenate(xs)), out_specs=P("x"))
    got = np.asarray(out).reshape(p, m)
    bits = np.dtype(f"u{dt.itemsize}")
    for r, want in enumerate(reference(xs, root)):
        np.testing.assert_array_equal(got[r].view(bits), want.view(bits))


@pytest.mark.parametrize("p,root,depth,tiles", [
    (4, 1, 2, 17), (4, 0, 3, 23), (4, 3, 2, 12), (2, 1, 2, 9)],
    ids=["odd-lanes", "depth3", "whole-groups", "two-shards"])
def test_bcast_long_enough_to_loop(comms, p, root, depth, tiles):
    """One-tile chunks, many a lane: the first ``depth`` steps traced
    as they are, the whole groups after them as the loop's body (three
    or more: the lanes have 8 chunks or more), and the rest after it:
    a lane's odd chunk where the lanes differ by one, and the last
    tile part filled."""
    tile = 8 * ROW
    m = tiles * tile - 5
    xs = _inputs(p, root, m, "float32")
    out = comms[p].run(lambda s: pallas_ici.hbm_ring_bcast(
        s, "x", p, root, chunk_bytes=tile * 4, depth=depth,
        interpret=True), jnp.asarray(np.concatenate(xs)), out_specs=P("x"))
    for row in np.asarray(out).reshape(p, m):
        np.testing.assert_array_equal(row, xs[root])


class _Log:
    """A streamer that only writes down what ``_chain`` asks of it."""

    def __init__(self, depth=2):
        self.depth, self.pending_send, self.calls = depth, {}, []

    def take(self, d, dst, off, sz):
        self.calls.append(("take", d, dst, off, sz))

    def issue(self, d, src, off, sz, acc, red=None):
        assert acc is None and red is None and len(src) == 1
        self.calls.append(("send", d, src[0], off, sz))


def test_the_chain_by_hand():
    """Who sends and who receives what, in which order, for four chips
    and two lanes of three chunks: the root only sends, from x; the
    chip opposite takes a chunk and passes it on, lane by lane; a
    lane's last chip only takes on it, after the lane it passes on and
    two chunks behind it (it lies two hops farther along that lane:
    without the lag it would wait every step for a chunk that comes
    round the whole ring). What a chip sends on a lane, in order, is
    what it took there, and what the next chip takes."""
    lanes = [[(0, 8), (8, 8), (16, 4)], [(24, 8), (32, 8), (40, 4)]]

    def run(src, dst, sends, skew):
        st = _Log()
        pallas_ici._chain(st, lanes, src, dst, sends, skew)
        return st.calls

    root = run("x", None, [True, True], [0, 0])
    assert root == [("send", d, "x", *lanes[d][j])
                    for j in range(3) for d in (0, 1)]
    mid = run("o", "o", (True, True), (0, 0))
    assert mid == [(what, d, "o", *lanes[d][j]) for j in range(3)
                   for d in (0, 1) for what in ("take", "send")]
    near, far = lambda d, j: [("take", d, "o", *lanes[d][j]),
                              ("send", d, "o", *lanes[d][j])], \
        lambda d, j: [("take", d, "o", *lanes[d][j])]
    cw_end = run("o", "o", (False, True), (2, 0))       # lane 0 ends here
    assert cw_end == (near(1, 0) + near(1, 1) + near(1, 2) + far(0, 0)
                      + far(0, 1) + far(0, 2))
    ccw_end = run("o", "o", (True, False), (0, 1))      # a lag of one
    assert ccw_end == (near(0, 0) + near(0, 1) + far(1, 0) + near(0, 2)
                       + far(1, 1) + far(1, 2))
    # one lane (two shards, or one way round): the last chip only takes
    pallas_ici._chain(end := _Log(), lanes[:1], "o", "o", (False,), (0,))
    assert end.calls == far(0, 0) + far(0, 1) + far(0, 2)
    # a one-tile payload leaves the second lane empty
    pallas_ici._chain(one := _Log(), [[(0, 8)], []], "o", "o", (True, True),
                      (0, 2))
    assert one.calls == [("take", 0, "o", 0, 8), ("send", 0, "o", 0, 8)]


def _kernel_ops(fn, p, n, dtype):
    """The primitives of the traced kernel of ``fn`` on ``[n]`` a
    shard, counted by name."""
    import collections

    from test_pallas_ici import _all_eqns, _eqns
    comm = MeshComm(make_mesh((p,), ("x",), jax.devices()[:p]))
    traced = jax.make_jaxpr(lambda x: comm.run(fn, x, out_specs=P("x")))(
        jax.ShapeDtypeStruct((p * n,), dtype))
    (call,) = _eqns(traced.jaxpr, "pallas_call")
    return collections.Counter(
        e.primitive.name for e in _all_eqns(call.params["jaxpr"]))


@pytest.mark.parametrize("p", [2, 4])
@pytest.mark.parametrize("nbytes", [8 * MiB, 64 * MiB, 256 * MiB])
def test_the_traced_program_does_not_grow_with_the_payload(p, nbytes):
    """The chain's steps are a loop's body, not unrolled: the kernel at
    the cell's 64 MiB traces what it traces at 8 MiB and at 256 MiB, one
    short schedule and its loop for each distance from the root. Until
    ISSUE 54 that was under a third of the operations of the all-gather
    that hands every shard as many bytes, whose rounds were unrolled (a
    first call of 11-16 s on the chip at 64 MiB, PERF.md section 6,
    PR 51); its rounds are loops of the same helper now, so from the
    cell's size on its kernel does not grow either: a loop a round."""
    dt = jnp.dtype("bfloat16")
    n = nbytes // dt.itemsize
    chain = _kernel_ops(lambda s: pallas_ici.hbm_ring_bcast(
        s, "x", p, 1, interpret=True), p, n, dt)
    small = _kernel_ops(lambda s: pallas_ici.hbm_ring_bcast(
        s, "x", p, 1, interpret=True), p, 8 * MiB // dt.itemsize, dt)
    assert chain == small
    assert chain["cond"] == p and chain["scan"] == p    # one a distance
    if nbytes >= 64 * MiB:      # from the cell's size on
        gather, twice = (_kernel_ops(
            lambda s: pallas_ici.hbm_ring_all_gather(
                s, "x", p, interpret=True), p, m, dt)
            for m in (n // p, 2 * n // p))
        assert gather == twice and gather["scan"] == p - 1


def test_wire_bytes_are_the_payload_in_whole_tiles():
    bf16, f32 = jnp.dtype("bfloat16"), jnp.dtype("float32")
    # the cell: 64 MiB of bfloat16 are whole tiles; nothing is added
    assert pallas_ici.bcast_wire_bytes(32 * MiB, bf16, 4) == 64 * MiB
    assert pallas_ici.bcast_wire_bytes(32 * MiB, bf16, 2) == 64 * MiB
    # Moonlight's layer as it is: 31 199 808 bfloat16 are 15 234.28 tiles
    assert pallas_ici.bcast_wire_bytes(31_199_808, bf16, 4) == \
        15235 * 16 * ROW * 2
    assert pallas_ici.bcast_wire_bytes(1, f32, 4) == 8 * ROW * 4


@pytest.fixture
def tier_edges(monkeypatch):
    monkeypatch.setenv("MV2T_DEV_TIER_VMEM_MAX", str(4 * MiB))
    monkeypatch.setenv("MV2T_DEV_TIER_XLA_MIN", str(512 * MiB))
    monkeypatch.setenv("MV2T_ICI_INTERPRET", "1")
    get_config().reload()
    yield monkeypatch
    monkeypatch.undo()
    get_config().reload()


@pytest.mark.parametrize("nbytes,multi_axis,want", [
    (4 * KiB, False, ("xla", None)),        # the vmem bin: no engine yet
    (4 * MiB, False, ("xla", None)),
    (4 * MiB + 2, False, ("hbm", None)),    # the streaming bin
    (64 * MiB, False, ("hbm", None)),
    (64 * MiB, True, ("xla", None)),        # a multi-axis mesh: none either
    (512 * MiB, False, ("xla", "size")),    # past the XLA crossover
    (0, False, ("xla", "shape"))],
    ids=["vmem", "vmem-edge", "hbm-edge", "hbm", "multi-axis", "size",
         "shape"])
def test_planned_tier_of_a_bcast_in_each_bin(tier_edges, nbytes, multi_axis,
                                             want):
    """One rule, asked by ``ici_bcast`` and by ``_decide_tier`` alike
    (with the op either gives: None, or the call's default)."""
    if multi_axis:      # the interpreter's multi-axis case is XLA's too
        tier_edges.setattr(pallas_ici, "on_tpu", lambda: True)
    for op in (None, "sum"):
        assert pallas_ici.planned_tier(
            "bcast", nbytes, np.dtype("float32"), op, num_devices=4,
            multi_axis=multi_axis) == want


def test_planned_tier_of_a_bcast_its_fallbacks(tier_edges):
    assert pallas_ici.planned_tier("bcast", 64 * MiB, np.dtype("complex64"),
                                   None) == ("xla", "dtype")
    # a quant bin moves bits too: the chain, not the quantized wire
    tier_edges.setenv("MV2T_QUANT_COLL", "q8:1e-2")
    tier_edges.setenv("MV2T_DEV_TIER_QUANT_MIN", str(MiB))
    get_config().reload()
    assert pallas_ici.planned_tier("bcast", 64 * MiB, np.dtype("float32"),
                                   None, num_devices=4) == ("hbm", None)
    assert pallas_ici.planned_tier("bcast", 64 * MiB, np.dtype("float32"),
                                   None, num_devices=4,
                                   multi_axis=True) == ("xla", None)
    # nothing to run the kernels on: the platform bucket, as the others
    tier_edges.setenv("MV2T_ICI_INTERPRET", "0")
    get_config().reload()
    assert pallas_ici.planned_tier("bcast", 64 * MiB, np.dtype("float32"),
                                   None) == ("xla", "platform")
