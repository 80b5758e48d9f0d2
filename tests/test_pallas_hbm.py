"""HBM slot-segment collectives: ops/pallas_hbm.py kernels and the
HBMSlotChannel co-residence path (more ranks than devices — the
mpirun-on-one-chip model). On CPU the kernels run in pallas interpret
mode and the channel binds a 1-device mesh explicitly."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mvapich2_tpu import run_ranks
from mvapich2_tpu.ops import pallas_hbm as ph
from mvapich2_tpu.utils.config import get_config


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["planar", "interleaved"])
@pytest.mark.parametrize("mean", [False, True])
def test_fused_reduce_to_slot(layout, mean):
    R, M, L = 4, 8, 128
    key = jax.random.PRNGKey(0)
    if layout == "planar":
        x = jax.random.normal(key, (R, M, L), jnp.float32)
        ref = np.asarray(x).sum(axis=0)
    else:
        x = jax.random.normal(key, (M, R, L), jnp.float32)
        ref = np.asarray(x).sum(axis=1)
    if mean:
        ref = ref / R
    out = ph.fused_reduce_to_slot(x, layout=layout, mean=mean, block_m=4)
    assert out.shape == (M, L)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5,
                               atol=1e-4)


@pytest.mark.parametrize("donate", [False, True])
def test_fused_allreduce_broadcast(donate):
    R, M, L = 8, 16, 128
    x = jax.random.normal(jax.random.PRNGKey(1), (M, R, L), jnp.float32)
    ref = np.broadcast_to(
        np.asarray(x).sum(axis=1, keepdims=True), (M, R, L))
    out = ph.fused_allreduce(x, block_m=8, donate=donate)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5,
                               atol=1e-4)


def test_hbm_slot_allreduce_ragged():
    # n not a multiple of 128: the pad must not leak into the result
    R, n = 3, 1000
    bufs = jnp.asarray(np.random.default_rng(2).normal(size=(R, n)),
                       jnp.float32)
    out = ph.hbm_slot_allreduce(bufs)
    assert out.shape == (n,)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(bufs).sum(axis=0), rtol=1e-5,
                               atol=1e-4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mean", [False, True])
@pytest.mark.parametrize("n", [1024, 128 * 24, 128 * 8 * 1024])
@pytest.mark.parametrize("R", [2, 4, 8])
def test_hbm_slot_allreduce_operands(R, n, mean, dtype):
    """R separate (n,) buffers as R operands of the slot kernel. Whole
    numbers small enough that every partial sum (and the mean over a
    power of two) is exact in the dtype, so numpy must agree bit for
    bit whatever order the kernel adds in."""
    top = 1 << 20 if dtype == "float32" else 16
    host = np.random.default_rng([R, n]).integers(
        -top, top, size=(R, n), endpoint=True).astype(np.float32)
    bufs = [jnp.asarray(h, dtype) for h in host]
    out = jax.jit(lambda *xs: ph.hbm_slot_allreduce_operands(
        xs, mean=mean))(*bufs)
    assert out.shape == (n,) and out.dtype == jnp.dtype(dtype)
    ref = host.sum(axis=0) / (R if mean else 1)
    np.testing.assert_array_equal(np.asarray(out, np.float32), ref)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("R", [3, 8])
def test_slot_operands_and_stacked_bit_equal(R, dtype):
    # rounding data: the two entries share one kernel body and one
    # order of addition, so they agree in every bit
    n = 128 * 40
    host = np.random.default_rng(R).normal(size=(R, n)) * 1e3
    stacked = jnp.asarray(host, dtype)
    a = ph.hbm_slot_allreduce(stacked)
    b = ph.hbm_slot_allreduce_operands(list(stacked))
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))
    np.testing.assert_allclose(
        np.asarray(a, np.float32),
        np.asarray(stacked, np.float32).sum(axis=0),
        rtol=1e-5 if dtype == "float32" else 1e-2, atol=1e-2)


def test_slot_operands_refuse_ragged():
    with pytest.raises(ValueError, match="128"):
        ph.hbm_slot_allreduce_operands([jnp.zeros(1000)] * 2)


def test_pack_unpack_roundtrip():
    R, n = 4, 512
    bufs = jnp.arange(R * n, dtype=jnp.float32).reshape(R, n)
    np.testing.assert_array_equal(
        np.asarray(ph.unpack_interleaved(ph.pack_interleaved(bufs))),
        np.asarray(bufs))


def test_bench_candidates_cover_both_kernels():
    cands = ph.bench_candidates(M=2048, R=8)
    names = [c[0] for c in cands]
    assert any(n.startswith("hbm_slot_reduce") for n in names)
    assert any(n.startswith("hbm_fused_bcast") for n in names)
    m = 2048 * 128 * 4
    for name, _, traffic, chains in cands:
        assert traffic == (9 * m if "slot" in name else 16 * m)
        # only shape-preserving ops may be timed as a chain
        assert chains == name.startswith("hbm_fused")


# ---------------------------------------------------------------------------
# the co-residence channel (ranks > devices)
# ---------------------------------------------------------------------------

def _one_device_mesh():
    from mvapich2_tpu.parallel.mesh import make_mesh
    return make_mesh((1,), ("x",), jax.devices()[:1])


def _force_device(names):
    cfg = get_config()
    for n in names:
        cfg.set(f"{n}_ALGO", "device")


def _unforce(names):
    cfg = get_config()
    for n in names:
        cfg.set(f"{n}_ALGO", "")


@pytest.mark.parametrize("nranks", [4, 5])
def test_slot_channel_allreduce(nranks):
    _force_device(["ALLREDUCE"])

    def fn(comm):
        assert type(comm.device_channel).__name__ == "HBMSlotChannel"
        sb = (np.arange(300, dtype=np.float32) + comm.rank)
        rb = comm.allreduce(sb)
        expected = (np.arange(300, dtype=np.float32) * comm.size
                    + sum(range(comm.size)))
        np.testing.assert_allclose(rb, expected, rtol=1e-6)
        # max (the non-pallas reduction path)
        from mvapich2_tpu.core import op as opmod
        mx = comm.allreduce(np.full(16, comm.rank, np.float32),
                            op=opmod.MAX)
        np.testing.assert_array_equal(mx, comm.size - 1)
    try:
        run_ranks(nranks, fn, device_mesh=_one_device_mesh())
    finally:
        _unforce(["ALLREDUCE"])


def test_slot_channel_bcast_allgather_alltoall_rsb():
    names = ["BCAST", "ALLGATHER", "ALLTOALL", "REDUCE_SCATTER"]
    _force_device(names)

    def fn(comm):
        p = comm.size
        # bcast from a nonzero root
        buf = (np.arange(130, dtype=np.float32) * 3 if comm.rank == 2
               else np.zeros(130, np.float32))
        comm.bcast(buf, root=2)
        np.testing.assert_allclose(buf,
                                   np.arange(130, dtype=np.float32) * 3)
        # allgather
        sb = np.full(7, comm.rank, np.float32)
        rb = np.zeros(7 * p, np.float32)
        comm.allgather(sb, rb)
        np.testing.assert_array_equal(
            rb, np.repeat(np.arange(p, dtype=np.float32), 7))
        # alltoall
        sb = np.arange(p * 3, dtype=np.float32) + 100 * comm.rank
        rb = np.zeros(p * 3, np.float32)
        comm.alltoall(sb, rb)
        expected = np.concatenate(
            [np.arange(comm.rank * 3, comm.rank * 3 + 3) + 100 * src
             for src in range(p)]).astype(np.float32)
        np.testing.assert_array_equal(rb, expected)
        # reduce_scatter_block
        sb = np.arange(p * 5, dtype=np.float32) + comm.rank
        rb = comm.reduce_scatter_block(sb, count=5)
        base = np.arange(comm.rank * 5, (comm.rank + 1) * 5,
                         dtype=np.float32)
        np.testing.assert_allclose(rb, base * p + sum(range(p)))
    try:
        run_ranks(4, fn, device_mesh=_one_device_mesh())
    finally:
        _unforce(names)


def test_slot_channel_device_resident_zero_copy():
    """Device-resident buffers: every rank's allreduce result is the
    SAME device array (the zero-copy shared slot)."""
    _force_device(["ALLREDUCE"])
    got = {}

    def fn(comm):
        sb = jnp.asarray(np.full(256, float(comm.rank + 1), np.float32))
        out = comm.allreduce(sb, recvbuf=None)
        got[comm.rank] = out
        np.testing.assert_allclose(
            np.asarray(out),
            np.full(256, sum(range(1, comm.size + 1)), np.float32))
    try:
        run_ranks(3, fn, device_mesh=_one_device_mesh())
    finally:
        _unforce(["ALLREDUCE"])
    assert got[0] is got[1] is got[2]
