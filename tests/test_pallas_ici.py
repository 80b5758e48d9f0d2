"""HBM-streaming ICI collective engine (ops/pallas_ici) — interpret-mode
correctness sweep on the 8-device virtual CPU mesh.

The chunked remote-DMA kernels must bit-agree with the XLA lowering for
every op x dtype x chunk-boundary shape (integer-valued data makes
float sums order-independent, so "bit-agreement" is exact, not rtol);
the double-buffer schedule must be invariant under pipeline depth; the
tier dispatcher must route by the measured boundaries and count every
XLA fallback.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from mvapich2_tpu import mpit  # noqa: E402
from mvapich2_tpu.ops import pallas_ici, pallas_ring  # noqa: E402
from mvapich2_tpu.parallel import MeshComm, make_mesh  # noqa: E402
from mvapich2_tpu.utils.config import get_config  # noqa: E402

NP = 8


@pytest.fixture(scope="module")
def comm8():
    return MeshComm(make_mesh((NP,), ("x",)))


def _reload(**env):
    for k, v in env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    get_config().reload()


@pytest.fixture(autouse=True)
def _clean_env():
    yield
    _reload(MV2T_ICI_INTERPRET=None, MV2T_DEV_TIER_VMEM_MAX=None,
            MV2T_DEV_TIER_XLA_MIN=None, MV2T_ICI_CHUNK_BYTES=None,
            MV2T_ICI_PIPELINE_DEPTH=None, MV2T_ICI_BIDIR=None)


def _expect(xv, op):
    blocks = np.asarray(xv, np.float64).reshape(NP, -1)
    return {"sum": blocks.sum(0), "max": blocks.max(0),
            "min": blocks.min(0), "prod": blocks.prod(0)}[op]


def _run_ar(comm8, xv, op="sum", **kw):
    out = comm8.run(lambda s: pallas_ici.hbm_ring_all_reduce(
        s, "x", NP, op=op, interpret=True, **kw), jnp.asarray(xv))
    return np.asarray(out).reshape(NP, -1)


# ---------------------------------------------------------------------------
# chunk-boundary shapes (shard x chunk remainders, degenerate 1-chunk)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shard,chunk_bytes", [
    (8, 16),          # shard divides p, chunks divide the block exactly
    (13, 16),         # shard % p != 0: identity-padded tail
    (37, 64),         # non-divisible block/chunk remainder (last short)
    (5, 1 << 20),     # 1-chunk degenerate: chunk covers the whole block
])
def test_allreduce_chunk_boundaries_bitwise(comm8, shard, chunk_bytes):
    xv = (np.arange(NP * shard) % 7).astype(np.float32)
    got = _run_ar(comm8, xv, chunk_bytes=chunk_bytes)
    exp = _expect(xv, "sum")
    for row in got:
        np.testing.assert_array_equal(row, exp)


# ---------------------------------------------------------------------------
# tile-scale streams: the layout moves whole (rows, 128) tiles, so a
# multi-chunk pipeline needs blocks of many rows — the tiny shards above
# are one tile per block and a single chunk. These drive, per lane,
# several chunks, slot reuse past the pipeline depth, a short last
# chunk, uneven lanes and an identity-padded tail. They run on a 4-shard
# sub-mesh: the interpreter parks one host thread per shard in every
# blocking wait, and a multi-chunk ring over all 8 virtual devices of an
# 8-thread host starves its pool (7 shards pass, 8 hang; 8 of 16 pass).
# ---------------------------------------------------------------------------

P4 = 4
ROW = 128
_TILE_STREAMS = [
    # (elements per ring block, chunk_bytes, depth)
    (48 * ROW, 4096, 2),       # 3 chunks per lane: slots reused past depth
    (40 * ROW, 4096, 2),       # uneven lanes: 24 rows cw, 16 ccw
    (44 * ROW - 5, 4096, 2),   # block not a whole tile: identity pad
    (48 * ROW, 8192, 2),       # 24-row lane / 16-row chunk: short last
    (64 * ROW, 4096, 3),       # deeper pipeline, 4 chunks per lane
]


@pytest.fixture(scope="module")
def comm4():
    return MeshComm(make_mesh((P4,), ("x",), jax.devices()[:P4]))


@pytest.mark.parametrize("blk,chunk_bytes,depth", _TILE_STREAMS)
def test_allreduce_tile_streams_bitwise(comm4, blk, chunk_bytes, depth):
    xv = (np.arange(P4 * P4 * blk) % 7).astype(np.float32)
    out = comm4.run(lambda s: pallas_ici.hbm_ring_all_reduce(
        s, "x", P4, interpret=True, chunk_bytes=chunk_bytes,
        depth=depth), jnp.asarray(xv))
    exp = xv.reshape(P4, -1).sum(0)
    for row in np.asarray(out).reshape(P4, -1):
        np.testing.assert_array_equal(row, exp)


def test_allreduce_tile_streams_bf16_max(comm4):
    """16-row bf16 tiles, the non-sum reducer, multi-chunk."""
    blk = 96 * ROW      # 48-row lanes = 3 chunks of 16 rows
    xv = (np.arange(P4 * P4 * blk) % 11 - 5).astype(np.float32)
    out = comm4.run(lambda s: pallas_ici.hbm_ring_all_reduce(
        s, "x", P4, op="max", interpret=True, chunk_bytes=4096),
        jnp.asarray(xv, dtype=jnp.bfloat16))
    exp = xv.reshape(P4, -1).max(0)
    for row in np.asarray(out.astype(jnp.float32)).reshape(P4, -1):
        np.testing.assert_array_equal(row, exp)


@pytest.mark.parametrize("m", [48 * ROW, 44 * ROW - 5])
def test_all_gather_tile_streams_bitwise(comm4, m):
    xv = (np.arange(P4 * m) % 13).astype(np.float32)
    out = comm4.run(lambda s: pallas_ici.hbm_ring_all_gather(
        s, "x", P4, chunk_bytes=4096, interpret=True), jnp.asarray(xv),
        out_specs=P("x"))
    for row in np.asarray(out).reshape(P4, -1):
        np.testing.assert_array_equal(row, xv)


@pytest.mark.parametrize("blk", [48 * ROW, 44 * ROW - 5])
def test_reduce_scatter_tile_streams_bitwise(comm4, blk):
    n = P4 * blk - 3            # p does not divide n: padded tail block
    xv = (np.arange(P4 * n) % 7).astype(np.float32)
    out = comm4.run(lambda s: pallas_ici.hbm_ring_reduce_scatter(
        s, "x", P4, chunk_bytes=4096, interpret=True), jnp.asarray(xv),
        out_specs=P("x"))
    full = np.zeros(P4 * blk, np.float32)
    full[:n] = xv.reshape(P4, n).sum(0)
    np.testing.assert_array_equal(np.asarray(out).reshape(-1), full)


@pytest.mark.parametrize("c", [48 * ROW, 20 * ROW - 7])
def test_alltoall_tile_streams_bitwise(comm4, c):
    """The pairwise-permutation streamer, multi-chunk on both lanes."""
    from mvapich2_tpu.ops import pallas_alltoall
    xv = np.arange(P4 * P4 * c, dtype=np.float32) % 1021
    out = comm4.run(lambda s: pallas_alltoall.hbm_alltoall(
        s, "x", P4, chunk_bytes=4096, interpret=True), jnp.asarray(xv),
        out_specs=P("x"))
    got = np.asarray(out).reshape(P4, P4, c)
    sent = xv.reshape(P4, P4, c)
    np.testing.assert_array_equal(got, sent.transpose(1, 0, 2))


@pytest.mark.parametrize("op,dtype", [
    ("max", np.int32),
    ("min", np.int32),
    ("prod", np.float32),
])
def test_allreduce_ops_bitwise(comm8, op, dtype):
    n = NP * 16
    xv = ((np.arange(n) % 2 + 1) if op == "prod"
          else (np.arange(n) % 11 - 5)).astype(dtype)
    got = _run_ar(comm8, xv, op=op, chunk_bytes=32)
    exp = _expect(xv, op).astype(dtype)
    for row in got:
        np.testing.assert_array_equal(row.astype(dtype), exp)


def test_allreduce_bf16_bitwise(comm8):
    # integer values small enough that every partial is bf16-exact
    xv = (np.arange(NP * 8) % 5).astype(np.float32)
    out = comm8.run(lambda s: pallas_ici.hbm_ring_all_reduce(
        s, "x", NP, interpret=True, chunk_bytes=16),
        jnp.asarray(xv, dtype=jnp.bfloat16))
    got = np.asarray(out.astype(jnp.float32)).reshape(NP, -1)
    exp = _expect(xv, "sum")
    for row in got:
        np.testing.assert_array_equal(row, exp)


def test_allreduce_agrees_with_xla_lowering(comm8):
    """The acceptance identity: chunked kernel == lax.psum, bitwise
    (integer-valued f32 makes the sum order-free)."""
    xv = (np.arange(NP * 24) % 13).astype(np.float32)
    got = _run_ar(comm8, xv, chunk_bytes=32)
    from mvapich2_tpu import ops
    ref = comm8.run(lambda s: ops.allreduce(s, "x"), jnp.asarray(xv))
    np.testing.assert_array_equal(got,
                                  np.asarray(ref).reshape(NP, -1))


def test_allreduce_unidirectional(comm8):
    xv = (np.arange(NP * 12) % 9).astype(np.float32)
    got = _run_ar(comm8, xv, chunk_bytes=16, bidirectional=False)
    exp = _expect(xv, "sum")
    for row in got:
        np.testing.assert_array_equal(row, exp)


# ---------------------------------------------------------------------------
# pipelining depth (the double-buffer schedule)
# ---------------------------------------------------------------------------

def test_pipeline_depth_invariance(comm8):
    """Deeper pipelines reorder DMA issue, never results."""
    xv = (np.arange(NP * 37) % 7).astype(np.float32)
    exp = _expect(xv, "sum")
    for depth in (3, 4):
        got = _run_ar(comm8, xv, chunk_bytes=64, depth=depth)
        for row in got:
            np.testing.assert_array_equal(row, exp)


def test_chunk_schedule_unit():
    """Static schedule invariants: chunks tile the span exactly, the
    remainder rides the last chunk, and the global-counter slot
    sequence never lands a write in a slot still inside the
    outstanding window (the credit-correctness precondition)."""
    for lo, hi, chunk in [(0, 64, 16), (0, 37, 16), (19, 37, 8),
                          (0, 5, 1 << 20)]:
        cl = pallas_ici._chunks(lo, hi, chunk)
        assert cl[0][0] == lo
        assert sum(sz for _, sz in cl) == hi - lo
        offs = [off for off, _ in cl]
        assert offs == sorted(offs)
        assert all(sz == chunk for _, sz in cl[:-1])
    for depth in (2, 3, 4):
        for total in (1, 3, 7, 8):
            slots = [k % depth for k in range(total)]
            for k in range(total):
                window = slots[k + 1:k + depth]   # outstanding writes
                if k + depth < total:
                    assert slots[k + depth] not in window
                    assert slots[k + depth] == slots[k]


def test_scratch_scales_with_depth_and_chunk():
    a = pallas_ici._scratch_shapes(2, 2, 64, jnp.float32)
    b = pallas_ici._scratch_shapes(2, 4, 64, jnp.float32)
    # three data buffers lead; VMEM bytes double with depth
    assert a[0].shape == (2, 2, 64, 128) and \
        b[0].shape == (2, 4, 64, 128)
    assert len(a) == len(b)


# ---------------------------------------------------------------------------
# the operand is read where it lies: no whole-operand copy in front of
# the rounds, the last fold lands in the reduce-scatter's output, and the
# caller's send buffer is only ever read
# ---------------------------------------------------------------------------

_RING_FNS = {
    "all_reduce": pallas_ici.hbm_ring_all_reduce,
    "reduce_scatter": pallas_ici.hbm_ring_reduce_scatter,
    "all_gather": pallas_ici.hbm_ring_all_gather,
}
# as ``pallas_ici.ring_steps`` and the ``ici_<coll>`` instants name them
_STEP_NAMES = {"all_reduce": "allreduce", "all_gather": "allgather",
               "reduce_scatter": "reduce_scatter"}


def _ring_expect(coll, xv, p):
    """numpy's answer for every shard, stacked: [p, ...]."""
    rows = xv.reshape(p, -1)
    if coll == "all_gather":
        return np.tile(xv, (p, 1))
    tot = rows.sum(0)
    if coll == "all_reduce":
        return np.tile(tot, (p, 1))
    nblk = -(-tot.size // p)
    full = np.zeros(p * nblk, xv.dtype)
    full[:tot.size] = tot
    return full.reshape(p, nblk)


_LEFT_AS_IT_WAS = [
    # (coll, k operands, p, bidir, shard, chunk_bytes)
    (coll, 1, *shape) for coll in sorted(_RING_FNS) for shape in [
        (2, None, 44 * ROW - 5, 4096),   # one lane (p = 2), ragged, 6 chunks
        (4, False, 24 * ROW, 4096),      # one lane, 3 chunks a block
        (4, True, 44 * ROW - 5, 4096),   # two lanes, ragged shard, uneven
        (8, False, 37, 64),              # one tile a block (see the note above)
        (8, True, 16 * ROW + 3, 4096),   # two lanes, ragged, one chunk each
    ]] + [
    # the fold rounds on k operands (ISSUE 49): each only ever read
    (coll, k, *shape) for coll in ("all_reduce", "reduce_scatter")
    for k, shape in [
        (2, (2, None, 44 * ROW - 5, 4096)),
        (2, (4, True, 48 * ROW, 8192)),  # 24-row lanes, a short last chunk
        (3, (4, False, 24 * ROW, 4096)),
    ]]


@pytest.mark.parametrize("coll,k,p,bidir,shard,chunk_bytes", _LEFT_AS_IT_WAS)
def test_operand_is_left_as_it_was(coll, k, p, bidir, shard, chunk_bytes):
    """The kernels read the send buffer where it lies, so what the
    caller handed in is bit for bit what it was after the call, and the
    answer is numpy's: of one operand, or of the ``k`` whose sum is the
    shard's contribution."""
    comm = MeshComm(make_mesh((p,), ("x",), jax.devices()[:p]))
    # an odd shard leaves p not dividing the reduce-scatter's input
    n = shard if coll == "all_gather" else p * shard - (shard % 2)
    xvs = [((np.arange(p * n) * 7 + 3 + 5 * i) % 11).astype(np.float32)
           for i in range(k)]
    xjs = [jnp.asarray(xv) for xv in xvs]
    fn = _RING_FNS[coll]

    def body(*s):
        return fn(s if k > 1 else s[0], "x", p, chunk_bytes=chunk_bytes,
                  bidirectional=bidir, interpret=True), s

    out, seen = comm.run(body, *xjs, out_specs=(P("x"), (P("x"),) * k))
    for xv, xj, sj in zip(xvs, xjs, seen):
        np.testing.assert_array_equal(np.asarray(xj), xv)
        np.testing.assert_array_equal(np.asarray(sj), xv)
    np.testing.assert_array_equal(np.asarray(out).reshape(p, -1),
                                  _ring_expect(coll, sum(xvs), p))


def _ring_order_fold(blocks, red):
    """The fold order of the ring for one block on one lane: ``blocks``
    in the order the partial visits their ranks; each rank folds the
    arrival into its own contribution as ``red(own, arrival)``."""
    acc = blocks[0]
    for own in blocks[1:]:
        acc = red(own, acc)
    return acc


@pytest.mark.parametrize("op,dtype", [
    ("sum", "float32"), ("prod", "float32"), ("sum", "bfloat16"),
    ("max", "bfloat16")])
@pytest.mark.parametrize("coll", ["all_reduce", "reduce_scatter"])
def test_fold_order_is_the_rings(comm4, coll, op, dtype):
    """Same chunks, same operands of the same reducer in the same
    order, whichever buffer a round reads them from: on data whose
    float sums and products depend on the order, block b's result is
    bit for bit the ring's fold (rank b+1 first, rank b last on the
    clockwise lane's rows; rank b-1 first on the other lane's)."""
    blk = 48 * ROW
    rng = np.random.default_rng(43)
    xv = rng.uniform(0.5, 1.5, P4 * P4 * blk).astype(np.float32)
    xj = jnp.asarray(xv, dtype=dtype)
    out = comm4.run(lambda s: _RING_FNS[coll](
        s, "x", P4, op, chunk_bytes=4096, interpret=True), xj,
        out_specs=P("x"))
    got = np.asarray(out.astype(jnp.float32)).reshape(P4, -1)
    x = xj.reshape(P4, P4, blk)          # [rank, block, element]
    red = pallas_ici._reducer(op)
    half = pallas_ici._block_spans(
        blk // ROW, 2, pallas_ici._sublanes(dtype))[0][1] * ROW
    exp = []
    for b in range(P4):
        cw = _ring_order_fold([x[(b + 1 + i) % P4, b, :half]
                               for i in range(P4)], red)
        ccw = _ring_order_fold([x[(b - 1 - i) % P4, b, half:]
                                for i in range(P4)], red)
        exp.append(np.asarray(jnp.concatenate([cw, ccw])
                              .astype(jnp.float32)))
    exp = np.concatenate(exp)
    if coll == "all_reduce":
        for row in got:
            np.testing.assert_array_equal(row, exp)
    else:
        np.testing.assert_array_equal(got.reshape(-1), exp)


def _fold_then_ring(fn, red):
    """``fn`` on the operands' fold, made in front of it, first operand
    first: what the fold channel's program was until ISSUE 49."""
    import functools
    return lambda xs, *a, **kw: fn(functools.reduce(red, xs), *a, **kw)


_ONE_LANE, _TWO_LANES = (False, 4096), (True, 8192)    # the latter leaves
_K_OPERANDS = [                                        # a short last chunk
    # (k, op, dtype, (bidirectional, chunk_bytes))
    (2, "sum", "float32", _ONE_LANE), (2, "max", "float32", _TWO_LANES),
    (2, "min", "bfloat16", _TWO_LANES), (2, "sum", "bfloat16", _ONE_LANE),
    (3, "sum", "float32", _TWO_LANES), (3, "max", "bfloat16", _ONE_LANE),
]


@pytest.mark.parametrize("k,op,dtype,lanes", _K_OPERANDS)
@pytest.mark.parametrize("coll", ["all_reduce", "reduce_scatter"])
def test_k_operands_fold_in_the_rounds(comm4, coll, k, op, dtype, lanes):
    """The fold rounds on ``k`` operands a shard (two ranks a chip:
    their deposits): a chunk read from the operands is read from all
    ``k`` and folded in VMEM, ``red(red(x0, x1, ...), arrival)``. Every
    other element is a value whose sums depend on the order, the ones
    between small whole numbers that any order sums exactly in both
    types: the result is bit for bit fold-then-ring's on all of them and
    the plain reference's on the whole numbers; one and two lanes,
    16-row bfloat16 tiles, a short last chunk."""
    blk = 48 * ROW      # 24-row lanes: 8192 B chunks leave 16 + 8 rows
    rng = np.random.default_rng([49, k])
    n = P4 * P4 * blk
    xvs = [np.where(np.arange(n) % 2, rng.uniform(0.5, 1.5, n),
                    rng.integers(-9, 9, n)).astype(np.float32)
           for _ in range(k)]
    xjs = [jnp.asarray(xv, dtype=dtype) for xv in xvs]
    fn = _RING_FNS[coll]
    kw = dict(chunk_bytes=lanes[1], bidirectional=lanes[0], interpret=True)
    got = comm4.run(lambda *s: fn(s, "x", P4, op, **kw), *xjs,
                    out_specs=P("x"))
    apart = comm4.run(
        lambda *s: _fold_then_ring(fn, pallas_ici._reducer(op))(
            s, "x", P4, op, **kw), *xjs, out_specs=P("x"))
    assert got.dtype == apart.dtype == xjs[0].dtype
    assert np.asarray(got).tobytes() == np.asarray(apart).tobytes()
    want = getattr(np, op)(np.stack(xvs).reshape(k * P4, -1), axis=0)
    rows = np.asarray(got.astype(jnp.float32))
    for row in (rows.reshape(P4, -1) if coll == "all_reduce"
                else rows.reshape(1, -1)):
        np.testing.assert_array_equal(row[::2], want[::2])


def _sub_jaxprs(params):
    for v in params.values():
        for sub in (v if isinstance(v, (list, tuple)) else [v]):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _all_eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in _sub_jaxprs(e.params):
            yield from _all_eqns(sub)


def _eqns(jaxpr, name):
    return (e for e in _all_eqns(jaxpr) if e.primitive.name == name)


def _run_eqns(jaxpr, times=1):
    """Every equation as often as the chip runs it: one inside a loop
    (the ``scan`` a ``fori_loop`` of a known trip count is) counts once
    a trip. Since ISSUE 54 a long ring round is a few steps and a loop,
    so what a kernel does in all is read off a step at a time."""
    for e in jaxpr.eqns:
        yield from [e] * times
        trips = e.params["length"] if e.primitive.name == "scan" else 1
        for sub in _sub_jaxprs(e.params):
            yield from _run_eqns(sub, times * trips)


def _dma(e):
    """``(source, source is indexed, destination, destination is
    indexed, semaphore, device id)`` of a ``dma_start`` / ``dma_wait``
    equation; the device id is ``None`` for a local DMA."""
    from jax import tree_util
    (src, src_tf, dst, dst_tf, sem, _sem_tf, _ssem, _ssem_tf,
     device) = tree_util.tree_unflatten(e.params["tree"], e.invars)
    return src, bool(src_tf), dst, bool(dst_tf), sem, device


def _local_dmas(coll, p, n, hbm_names, **kw):
    """Every local DMA the traced kernel of ``coll`` starts, as
    ``(source, source is whole, destination, destination is whole)``:
    the HBM operands by the names given (kernel argument order), any
    scratch buffer as ``vmem``; whole = no index on the ref."""
    comm = MeshComm(make_mesh((p,), ("x",), jax.devices()[:p]))
    traced = jax.make_jaxpr(lambda x: comm.run(
        lambda s: _RING_FNS[coll](s, "x", p, interpret=True, **kw), x,
        out_specs=P("x")))(jnp.zeros(p * n, jnp.float32))
    (call,) = _eqns(traced.jaxpr, "pallas_call")
    assert not call.params["input_output_aliases"]
    kernel = call.params["jaxpr"]
    names = dict(zip(kernel.invars, hbm_names))
    dmas = []
    loop = {}       # a loop body's name for the kernel's operand
    for e in _run_eqns(kernel):
        if e.primitive.name == "scan":
            loop.update(zip(e.params["jaxpr"].jaxpr.invars, e.invars))
        if e.primitive.name != "dma_start":
            continue
        src, src_tf, dst, dst_tf, _sem, device = _dma(e)
        if device is None:
            src, dst = loop.get(src, src), loop.get(dst, dst)
            dmas.append((names.get(src, "vmem"), not src_tf,
                         names.get(dst, "vmem"), not dst_tf))
    return dmas


@pytest.mark.parametrize("coll,hbm", [
    ("all_reduce", ("x", "o")), ("reduce_scatter", ("x", "w", "o")),
    ("all_gather", ("x", "o"))])
@pytest.mark.parametrize("lane", [3, 8], ids=["unrolled", "looped"])
def test_no_whole_operand_copy_in_front_of_the_rounds(coll, hbm, lane):
    """Read off the ``pallas_call``'s jaxpr (p = 4, two lanes, 3 chunks
    a lane, every step written out, and 8, a round a loop: each DMA
    counted as often as it runs): no DMA moves a whole operand into a
    whole working or output buffer, none writes the operand, and HBM
    meets HBM only in the all-gather's copy of the shard into its own
    block. The fold rounds load every accumulator chunk and round 0's
    send chunks from the operand; the reduce-scatter's last fold stores
    into its output."""
    chunks, rounds = 2 * lane, P4 - 1
    n = 16 * lane * ROW * (1 if coll == "all_gather" else P4)
    assert bool(pallas_ici.ring_steps(
        _STEP_NAMES[coll], n, np.float32, P4,
        chunk_bytes=4096)["steps_looped"]) == (lane == 8)
    dmas = _local_dmas(coll, P4, n, hbm, chunk_bytes=4096)
    assert not [d for d in dmas if d[2] == "x"], "the operand is written"
    hbm2hbm = [d for d in dmas if d[0] != "vmem" and d[2] != "vmem"]
    assert not [d for d in hbm2hbm if d[1] and d[3]], hbm2hbm
    loads = [d[0] for d in dmas if d[2] == "vmem"]
    stores = [d[2] for d in dmas if d[0] == "vmem"]
    if coll == "all_gather":
        assert hbm2hbm == [("x", True, "o", False)]
        assert loads.count("x") == chunks
        assert loads.count("o") == (rounds - 1) * chunks
        assert stores.count("o") == rounds * chunks
        return
    assert not hbm2hbm, hbm2hbm
    # fold round 0 sends from the operand, every round folds into it
    assert loads.count("x") == (1 + rounds) * chunks
    if coll == "reduce_scatter":
        assert loads.count("w") == (rounds - 1) * chunks
        assert stores.count("w") == (rounds - 1) * chunks
        assert stores.count("o") == chunks
    else:
        assert loads.count("o") == (rounds - 1 + rounds) * chunks
        assert stores.count("o") == 2 * rounds * chunks


def _traced_kernel(fn, n, dtype, k=1, p=P4):
    """The ``pallas_call``'s kernel jaxpr of ``fn(shards)`` on ``k``
    shards of ``[n]`` a chip: traced on shapes, nothing runs."""
    comm = MeshComm(make_mesh((p,), ("x",), jax.devices()[:p]))
    traced = jax.make_jaxpr(lambda *xs: comm.run(
        lambda *s: fn(s), *xs, out_specs=P("x")))(
        *[jax.ShapeDtypeStruct((p * n,), dtype)] * k)
    (call,) = _eqns(traced.jaxpr, "pallas_call")
    return call.params["jaxpr"]


def _kernel_ops(coll, k, p, n, **kw):
    """What the traced kernel of ``coll`` on ``k`` operands holds: its
    primitives counted by name, each as often as it runs, and ``vpu``,
    those among them that compute a whole chunk (the reducer's; loads
    and stores of VMEM are ``get`` and ``swap``)."""
    import collections
    kernel = _traced_kernel(
        lambda s: _RING_FNS[coll](s if k > 1 else s[0], "x", p,
                                  interpret=True, **kw),
        n, jnp.float32, k, p)
    ops = collections.Counter()
    for e in _run_eqns(kernel):
        ops[e.primitive.name] += 1
        if e.primitive.name not in ("get", "swap") and any(
                len(getattr(v.aval, "shape", ())) >= 2 for v in e.outvars):
            ops["vpu"] += 1
    return ops


# the parent of ISSUE 49 (commit c492591), p = 4, two lanes, 3 chunks a
# lane, read off its traced kernels: three other cells run these
_PARENT_OPS = {
    "all_reduce": dict(dma_start=126, dma_wait=162, get=36, swap=18, vpu=18,
                       semaphore_signal=40, semaphore_wait=39),
    "reduce_scatter": dict(dma_start=72, dma_wait=90, get=36, swap=18,
                           vpu=18, semaphore_signal=22, semaphore_wait=21),
    "all_gather": dict(dma_start=55, dma_wait=73, get=0, swap=0, vpu=0,
                       semaphore_signal=22, semaphore_wait=21),
}


def _unrolled_stream_step(self, spans_chunks, src, acc, dst, red):
    """``_RingStreamer.stream_step`` as it was until ISSUE 54, every
    chunk step written out: the reference schedule."""
    ndir = self.ndir
    cmax = max(len(c) for c in spans_chunks)
    live = [[None] * len(spans_chunks[d]) for d in range(ndir)]
    for c in range(cmax + 1):
        for d in range(ndir):
            if c < len(spans_chunks[d]):
                off, sz = spans_chunks[d][c]
                live[d][c] = self.issue(
                    d, src[d], off, sz, acc[d] if acc else None, red)
        for d in range(ndir):
            if 1 <= c and c - 1 < len(spans_chunks[d]):
                off, sz = spans_chunks[d][c - 1]
                self.drain(d, live[d][c - 1], dst[d], off, sz, red)
    self.drain_stores()


@pytest.mark.parametrize("coll,k", [
    ("all_reduce", 1), ("all_reduce", 2), ("reduce_scatter", 2),
    ("all_gather", 1)])
def test_a_looped_kernel_runs_what_the_unrolled_one_holds(monkeypatch,
                                                          coll, k):
    """The counts below, restated for rounds that loop (8 chunks a
    lane): each primitive as often as the chip runs it is what the
    kernel with every step written out holds: the same DMA starts and
    waits, VMEM reads and writes, chunk-wide VPU ops and semaphore ops;
    the loops add scalar arithmetic and nothing else."""
    n = 128 * ROW if coll == "all_gather" else P4 * 128 * ROW
    names = ("dma_start", "dma_wait", "get", "swap", "vpu",
             "semaphore_signal", "semaphore_wait")
    looped = _kernel_ops(coll, k, P4, n, chunk_bytes=4096)
    assert looped["scan"] == (P4 - 1) * (2 if coll == "all_reduce" else 1)
    monkeypatch.setattr(pallas_ici._RingStreamer, "stream_step",
                        _unrolled_stream_step)
    unrolled = _kernel_ops(coll, k, P4, n, chunk_bytes=4096)
    assert not unrolled["scan"]
    assert {m: looped[m] for m in names} == {m: unrolled[m] for m in names}


@pytest.mark.parametrize("coll,k", [
    ("all_reduce", 1), ("reduce_scatter", 1), ("all_gather", 1),
    ("all_reduce", 2), ("reduce_scatter", 2), ("reduce_scatter", 3)])
def test_one_operand_is_the_parents_kernel_and_k_add_a_load_a_chunk(coll, k):
    """At ``k = 1`` the traced kernel holds the DMA starts and waits,
    the VMEM reads and writes, the chunk-wide VPU ops and the semaphore
    ops it held before the fold rounds took ``k`` operands, count for
    count. Each further operand adds, a chunk of a fold round, one load
    (a start and a wait), one VMEM read and one VPU op for the
    accumulator, and in round 0 the same again for the send chunk,
    which is read and written back once; not one store, remote DMA or
    semaphore op more."""
    chunks, rounds = 2 * 3, P4 - 1
    n = 48 * ROW if coll == "all_gather" else P4 * 48 * ROW
    ops = _kernel_ops(coll, k, P4, n, chunk_bytes=4096)
    want = dict(_PARENT_OPS[coll])
    more = (k - 1) * (rounds + 1) * chunks      # loads of the others
    for name in ("dma_start", "dma_wait", "get", "vpu"):
        want[name] += more
    if k > 1:       # round 0's send chunks, read and written back folded
        want["get"] += chunks
        want["swap"] += chunks
    assert {name: ops[name] for name in want} == want


# ---------------------------------------------------------------------------
# alltoall(v): the own block's copy runs under the waves (ISSUE 52)
# ---------------------------------------------------------------------------

def _ragged_counts(p, own=True):
    """A skewed count matrix: no two pairs alike, past two shards a
    permutation step that nobody has anything for, the own blocks
    non-empty or all empty."""
    counts = [[(17 * ROW + 37 * r + 1211 * j) % (23 * ROW) + 1
               for j in range(p)] for r in range(p)]
    for r in range(p):
        counts[r][r] = 9 * ROW + 5 + r if own else 0
        if p > 2:
            counts[r][(r + p - 1) % p] = 0
    return counts


_A2A_CASES = {
    # name: (p, bidirectional, elements a pair or the count matrix)
    "p2": (2, None, 24 * ROW),               # one lane, one wave, 3 chunks
    "p4": (4, True, 24 * ROW),               # two lanes, two waves
    "p4_one_lane": (4, False, 20 * ROW - 7),     # ragged tiles, three waves
    "p8": (8, True, 24 * ROW),               # two lanes, four waves
    "v4": (4, True, _ragged_counts(4)),
    "v2": (2, None, _ragged_counts(2)),
    "v4_no_own": (4, True, _ragged_counts(4, own=False)),
}


def _a2a_fn(p, bidir, what):
    """``(per-shard function, elements a shard takes, elements a shard
    returns)`` of one case: ``hbm_alltoall`` on ``what`` elements a
    pair, ``hbm_alltoallv`` on the count matrix ``what``."""
    from mvapich2_tpu.ops import pallas_alltoall
    kw = dict(chunk_bytes=4096, bidirectional=bidir, interpret=True)
    if isinstance(what, int):
        return (lambda s: pallas_alltoall.hbm_alltoall(s, "x", p, **kw),
                p * what, p * what)
    _sd, _rd, in_len, out_len = pallas_alltoall.packed_displs(what)
    return (lambda s: pallas_alltoall.hbm_alltoallv(s, "x", p, what, **kw),
            in_len, out_len)


def _a2a_dma_order(p, bidir, what):
    """The traced alltoall(v) kernel's DMAs in program order, as
    ``(primitive, kind, semaphore)``: ``own`` is a local copy from the
    HBM input to the HBM output, ``remote`` one with a device id."""
    comm = MeshComm(make_mesh((p,), ("x",), jax.devices()[:p]))
    fn, in_len, _out = _a2a_fn(p, bidir, what)
    traced = jax.make_jaxpr(lambda x: comm.run(fn, x, out_specs=P("x")))(
        jnp.zeros(p * in_len, jnp.float32))
    (call,) = _eqns(traced.jaxpr, "pallas_call")
    assert not call.params["input_output_aliases"]
    kernel = call.params["jaxpr"]
    x_hbm, o_hbm = kernel.invars[:2]
    order = []
    for e in _all_eqns(kernel):
        if e.primitive.name not in ("dma_start", "dma_wait"):
            continue
        src, _src_tf, dst, _dst_tf, sem, device = _dma(e)
        kind = ("remote" if device is not None else
                "own" if (src, dst) == (x_hbm, o_hbm) else "local")
        order.append((e.primitive.name, kind, sem))
    return order, kernel.invars[-1]


@pytest.mark.parametrize("case", sorted(_A2A_CASES))
def test_alltoall_own_block_copy_runs_under_the_waves(case):
    """Read off the ``pallas_call``'s jaxpr: the local HBM-to-HBM DMAs,
    ``x[my] -> o[my]`` a chunk's rows at a time, are as many as the own
    block has chunks, each started in front of the remote DMAs of a
    chunk step of its own (the first in front of the first remote DMA;
    never two with no remote DMA between them while a wave has chunks
    left), and nothing waits on their semaphore, the kernel's last
    scratch, which no wave touches, until the last remote DMA has been
    started; where nobody keeps anything for itself there is no such
    DMA."""
    p, bidir, what = _A2A_CASES[case]
    order, own_sem = _a2a_dma_order(p, bidir, what)
    starts = [i for i, (name, kind, _s) in enumerate(order)
              if (name, kind) == ("dma_start", "own")]
    on_own_sem = [i for i, (_n, _k, sem) in enumerate(order)
                  if sem is own_sem]
    remote = [i for i, (name, kind, _s) in enumerate(order)
              if (name, kind) == ("dma_start", "remote")]
    assert len(remote) > 2, "several chunks a wave"
    if case.endswith("no_own"):
        assert not starts and not on_own_sem
        return
    own_elems = what if isinstance(what, int) else max(
        row[r] for r, row in enumerate(what))
    pieces = -(-pallas_ici._tile_rows(own_elems, np.float32)
               // pallas_ici._cfg_chunk_rows(np.float32, 4096))
    assert len(starts) == pieces > 1
    waits = [i for i in on_own_sem if i not in starts]
    assert len(waits) == pieces
    assert all(order[i][:2] == ("dma_wait", "own") for i in waits)
    assert all(order[i][2] is own_sem for i in starts)
    assert starts[0] < remote[0], "no piece in front of the waves"
    for a, b in zip(starts, starts[1:]):
        assert b > remote[-1] or any(a < r < b for r in remote), \
            "two pieces started in one chunk step"
    assert min(waits) > remote[-1], \
        "a piece is waited for before the waves end"


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_A2A_CASES))
def test_alltoall_is_the_plain_reference_and_leaves_its_operand(case, dtype):
    """The same cases run (the interpreter's DMAs are asynchronous too:
    a copy that raced a wave would show): every shard holds, bit for
    bit, what ``tests/plain_reference.py`` says, and the send buffer is
    what it was, the own block the copy reads too."""
    import plain_reference as ref
    p, bidir, what = _A2A_CASES[case]
    comm = MeshComm(make_mesh((p,), ("x",), jax.devices()[:p]))
    fn, in_len, out_len = _a2a_fn(p, bidir, what)
    # whole numbers under 256: bfloat16 holds each exactly
    xv = ((np.arange(p * in_len) * 7 + 3) % 251).astype(np.float32)
    xj = jnp.asarray(xv, dtype=dtype)
    out, seen = comm.run(lambda s: (fn(s), s), xj,
                         out_specs=(P("x"), P("x")))
    assert out.dtype == xj.dtype
    for kept in (xj, seen):
        np.testing.assert_array_equal(
            np.asarray(kept.astype(jnp.float32)), xv)
    got = np.asarray(out.astype(jnp.float32)).reshape(p, out_len)
    inputs = list(xv.reshape(p, in_len))
    if isinstance(what, int):
        want = ref.alltoall(inputs)
    else:
        want = ref.alltoallv([x[:sum(row)] for x, row in zip(inputs, what)],
                             what)
    for r in range(p):
        np.testing.assert_array_equal(got[r, :want[r].size], want[r])


# ---------------------------------------------------------------------------
# all-gather + the pt2pt lane
# ---------------------------------------------------------------------------

def test_hbm_all_gather_bitwise(comm8):
    xv = np.arange(NP * 13, dtype=np.int32)
    out = comm8.run(lambda s: pallas_ici.hbm_ring_all_gather(
        s, "x", NP, chunk_bytes=16, interpret=True), jnp.asarray(xv),
        out_specs=P("x"))
    got = np.asarray(out).reshape(NP, NP * 13)
    for row in got:
        np.testing.assert_array_equal(row, xv)


def test_remote_sendrecv_exchange(comm8):
    xv = np.arange(NP * 4, dtype=np.float32)
    out = comm8.run(lambda s: pallas_ici.remote_sendrecv(
        s, "x", NP, src=2, dst=5, interpret=True), jnp.asarray(xv),
        out_specs=P("x"))
    got = np.asarray(out).reshape(NP, 4)
    exp = xv.reshape(NP, 4).copy()
    exp[[2, 5]] = exp[[5, 2]]        # src<->dst swap; others identity
    np.testing.assert_array_equal(got, exp)


# ---------------------------------------------------------------------------
# tier dispatch + fallback observability
# ---------------------------------------------------------------------------

def test_planned_tier_reasons():
    _reload(MV2T_ICI_INTERPRET="1", MV2T_DEV_TIER_VMEM_MAX="64",
            MV2T_DEV_TIER_XLA_MIN="4096")
    assert pallas_ici.planned_tier("allreduce", 64, np.float32,
                                   "sum") == ("vmem", None)
    assert pallas_ici.planned_tier("allreduce", 100, np.float32,
                                   "sum") == ("hbm", None)
    assert pallas_ici.planned_tier("allreduce", 8192, np.float32,
                                   "sum") == ("xla", "size")
    assert pallas_ici.planned_tier("allreduce", 100, np.float32,
                                   "land") == ("xla", "dtype")
    assert pallas_ici.planned_tier("allreduce", 100, np.complex64,
                                   "sum") == ("xla", "dtype")
    assert pallas_ici.planned_tier("allreduce", 0, np.float32,
                                   "sum") == ("xla", "shape")
    _reload(MV2T_ICI_INTERPRET=None)
    if jax.devices()[0].platform != "tpu":
        assert pallas_ici.planned_tier(
            "allreduce", 100, np.float32, "sum") == ("xla", "platform")


def test_default_tier_edges_cover_the_old_cliff():
    """The acceptance bound: with compiled-in defaults (no profile
    override), buffers past the 4 MiB VMEM cap plan the HBM-streaming
    tier — never a silent XLA fallback."""
    from mvapich2_tpu.coll import tuning
    _reload(MV2T_DEV_TIER_VMEM_MAX=None, MV2T_DEV_TIER_XLA_MIN=None)
    saved = dict(tuning._DEVICE_CROSSOVERS)
    tuning._DEVICE_CROSSOVERS.clear()
    try:
        assert tuning.device_tier("allreduce", 4 * 1024 * 1024) == "vmem"
        assert tuning.device_tier("allreduce", 4 * 1024 * 1024 + 1) \
            == "hbm"
        assert tuning.device_tier("allreduce", 1 << 30) == "hbm"
        # a measured profile re-enters XLA above its crossover
        tuning._DEVICE_CROSSOVERS["dev_tier_xla_min"] = 1 << 26
        assert tuning.device_tier("allreduce", 1 << 27) == "xla"
        # an explicit cvar outranks the measurement
        _reload(MV2T_DEV_TIER_XLA_MIN="-1")
        assert tuning.device_tier("allreduce", 1 << 27) == "hbm"
    finally:
        tuning._DEVICE_CROSSOVERS.clear()
        tuning._DEVICE_CROSSOVERS.update(saved)


def test_dispatcher_routes_hbm(comm8):
    _reload(MV2T_ICI_INTERPRET="1", MV2T_DEV_TIER_VMEM_MAX="16",
            MV2T_ICI_CHUNK_BYTES="32")
    xv = (np.arange(NP * 16) % 7).astype(np.float32)   # shard 64 B > 16
    out = comm8.run(lambda s: pallas_ici.ici_all_reduce(s, "x", NP),
                    jnp.asarray(xv))
    got = np.asarray(out).reshape(NP, -1)
    exp = _expect(xv, "sum")
    for row in got:
        np.testing.assert_array_equal(row, exp)


def test_vmem_reject_counts_fallback_pvar(comm8):
    """The once-silent pallas_ring rejection now bumps the pvar family
    (per traced shape)."""
    before = mpit.pvar("dev_coll_fallback_size").read()
    n = pallas_ring.VMEM_LIMIT_BYTES // 4 + 128   # one shard past the cap
    xv = (np.arange(NP * n) % 5).astype(np.float32)
    out = comm8.run(lambda s: pallas_ring.ring_all_reduce(s, "x", NP),
                    jnp.asarray(xv))
    exp = _expect(xv, "sum")
    np.testing.assert_array_equal(np.asarray(out).reshape(NP, -1)[0], exp)
    assert mpit.pvar("dev_coll_fallback_size").read() >= before + 1
