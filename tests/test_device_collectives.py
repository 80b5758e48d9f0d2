"""Device-path (ICI channel) tests on the 8-device virtual CPU mesh —
the XLA-native collective layer that replaces the reference's transport."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from mvapich2_tpu import ops  # noqa: E402
from mvapich2_tpu.parallel import MeshComm, make_mesh, mesh_shape_for  # noqa: E402


@pytest.fixture(scope="module")
def comm8():
    return MeshComm(make_mesh((8,), ("x",)))


def test_mesh_shape_for():
    assert mesh_shape_for(8, 2) == (2, 4)
    assert mesh_shape_for(16, 2) == (4, 4)
    assert mesh_shape_for(7, 2) == (1, 7)
    assert mesh_shape_for(8, 1) == (8,)


def test_allreduce_psum(comm8):
    x = jnp.arange(32, dtype=jnp.float32)
    out = comm8.run(lambda s: comm8.allreduce(s), x)
    # each shard of 4 elems summed over... psum sums the *shards*; with
    # out_specs P('x') each shard holds the sum of all 8 shards' values
    expected = x.reshape(8, 4).sum(axis=0)
    got = np.asarray(out).reshape(8, 4)
    for blk in got:
        np.testing.assert_allclose(blk, expected)


def test_allreduce_max(comm8):
    x = jnp.arange(8, dtype=jnp.float32)
    out = comm8.run(lambda s: comm8.allreduce(s, op="max"), x)
    assert np.asarray(out).max() == 7.0
    assert (np.asarray(out) == 7.0).all()


def test_bcast_from_root(comm8):
    x = jnp.arange(8, dtype=jnp.float32) * 10
    out = comm8.run(lambda s: comm8.bcast(s, root=3), x)
    np.testing.assert_allclose(np.asarray(out), 30.0)


def test_all_gather(comm8):
    x = jnp.arange(8, dtype=jnp.int32)
    out = comm8.run(lambda s: comm8.all_gather(s, tiled=True), x,
                    out_specs=P("x"))
    # every shard gathers the full vector; tiled output is [8*8] globally
    got = np.asarray(out).reshape(8, 8)
    for row in got:
        np.testing.assert_array_equal(row, np.arange(8))


def test_reduce_scatter(comm8):
    # each shard holds [8] -> psum_scatter leaves each shard sum-block
    x = jnp.tile(jnp.arange(8, dtype=jnp.float32), (8,))
    out = comm8.run(lambda s: comm8.reduce_scatter(s), x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8) * 8)


def test_all_to_all(comm8):
    # shard i holds blocks destined to each peer: value i*8+j for peer j
    x = jnp.arange(64, dtype=jnp.int32)
    out = comm8.run(lambda s: comm8.all_to_all(s), x)
    got = np.asarray(out).reshape(8, 8)
    expected = np.arange(64).reshape(8, 8).T
    np.testing.assert_array_equal(got, expected)


def test_ring_shift(comm8):
    x = jnp.arange(8, dtype=jnp.int32)
    out = comm8.run(lambda s: comm8.ring_shift(s, 1), x)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.roll(np.arange(8), 1))


def test_halo_exchange_periodic(comm8):
    # global [32] split into 8 shards of 4; halo width 1
    x = jnp.arange(32, dtype=jnp.float32)
    out = comm8.run(lambda s: comm8.halo_exchange(s, halo=1), x,
                    out_specs=P("x"))
    got = np.asarray(out).reshape(8, 6)
    g = np.arange(32, dtype=np.float32).reshape(8, 4)
    for i in range(8):
        np.testing.assert_allclose(got[i, 0], g[(i - 1) % 8, -1])
        np.testing.assert_allclose(got[i, 1:-1], g[i])
        np.testing.assert_allclose(got[i, -1], g[(i + 1) % 8, 0])


def test_halo_exchange_nonperiodic(comm8):
    x = jnp.arange(32, dtype=jnp.float32)
    out = comm8.run(lambda s: comm8.halo_exchange(s, halo=1,
                                                  periodic=False), x,
                    out_specs=P("x"))
    got = np.asarray(out).reshape(8, 6)
    assert got[0, 0] == 0.0          # no left neighbor
    assert got[7, -1] == 0.0         # no right neighbor


def test_scan_axis(comm8):
    x = jnp.ones(8, dtype=jnp.float32)
    out = comm8.run(lambda s: comm8.scan(s), x, out_specs=P("x"))
    np.testing.assert_allclose(np.asarray(out), np.arange(1, 9))


@pytest.mark.slow
def test_ring_allreduce_manual_matches_psum(comm8):
    x = jnp.arange(80, dtype=jnp.float32).reshape(8, 10)

    def fused(s):
        return ops.allreduce(s, "x")

    def manual(s):
        return ops.ring_allreduce_manual(s, "x")

    a = comm8.run(fused, x.reshape(-1), out_specs=P("x"))
    b = comm8.run(manual, x.reshape(-1), out_specs=P("x"))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_two_axis_hierarchy():
    """2-level analog: reduce over intra-'host' axis then inter axis
    equals flat psum over both (the shmem+leader identity)."""
    mesh = make_mesh((2, 4), ("dcn", "host"))
    comm = MeshComm(mesh, "host")
    x = jnp.arange(16, dtype=jnp.float32)

    def two_level(s):
        intra = ops.allreduce(s, "host")
        return ops.allreduce(intra, "dcn")

    def flat(s):
        return ops.allreduce(s, ("dcn", "host"))

    a = comm.run(two_level, x, in_specs=(P(("dcn", "host")),),
                 out_specs=P(("dcn", "host")))
    b = comm.run(flat, x, in_specs=(P(("dcn", "host")),),
                 out_specs=P(("dcn", "host")))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_moe_shuffle_roundtrip(comm8):
    x = jnp.arange(64, dtype=jnp.float32)

    def roundtrip(s):
        return ops.moe_shuffle(ops.moe_shuffle(s, "x"), "x")

    out = comm8.run(roundtrip, x, out_specs=P("x"))
    np.testing.assert_allclose(np.asarray(out), np.arange(64))


def test_under_jit_compiles_once(comm8):
    x = jnp.arange(8, dtype=jnp.float32)

    @jax.jit
    def step(v):
        return comm8.run(lambda s: comm8.allreduce(s * 2.0), v)

    out = step(x)
    np.testing.assert_allclose(np.asarray(out)[0], np.arange(8).sum() * 2)


# ---------------------------------------------------------------------------
# pallas ring kernels (TPU interpret mode with race detection)
# ---------------------------------------------------------------------------

def _interp():
    # race-detecting interpreter when this jax has it, plain interpret
    # otherwise (ops/_compat owns the version seam)
    from mvapich2_tpu.ops._compat import interpret_params
    return interpret_params(detect_races=True)


def test_pallas_ring_all_gather(comm8):
    from mvapich2_tpu.ops import pallas_ring
    x = jnp.arange(64, dtype=jnp.float32)
    ip = _interp()
    out = comm8.run(lambda s: pallas_ring.ring_all_gather(s, "x", 8,
                                                          interpret=ip),
                    x, out_specs=P("x"))
    got = np.asarray(out).reshape(8, 64)
    for row in got:
        np.testing.assert_array_equal(row, np.arange(64))


def test_pallas_ring_all_reduce(comm8):
    from mvapich2_tpu.ops import pallas_ring
    x = jnp.arange(64, dtype=jnp.float32)
    ip = _interp()
    out = comm8.run(lambda s: pallas_ring.ring_all_reduce(s, "x", 8,
                                                          interpret=ip),
                    x, out_specs=P("x"))
    got = np.asarray(out).reshape(8, 8)
    expected = np.arange(64, dtype=np.float32).reshape(8, 8).sum(axis=0)
    for row in got:
        np.testing.assert_allclose(row, expected)


def test_pallas_ring_all_reduce_2d(comm8):
    from mvapich2_tpu.ops import pallas_ring
    x = jnp.arange(8 * 16 * 4, dtype=jnp.float32).reshape(8 * 16, 4)
    ip = _interp()
    out = comm8.run(lambda s: pallas_ring.ring_all_reduce(s, "x", 8,
                                                          interpret=ip),
                    x, out_specs=P("x"))
    got = np.asarray(out).reshape(8, 16, 4)
    expected = np.arange(8 * 16 * 4, dtype=np.float32).reshape(8, 16, 4) \
        .sum(axis=0)
    for blk in got:
        np.testing.assert_allclose(blk, expected)


def test_pallas_ring_nondivisible_pads(comm8):
    """Non-divisible shapes are zero-padded to whole-tile ring blocks
    and run the kernel (no lax.psum fallback any more)."""
    from mvapich2_tpu.ops import pallas_ring
    x = jnp.arange(8 * 5, dtype=jnp.float32)  # shard 5 elems, 5 % 8 != 0
    ip = _interp()
    out = comm8.run(lambda s: pallas_ring.ring_all_reduce(s, "x", 8,
                                                          interpret=ip),
                    x)
    expected = np.arange(40, dtype=np.float32).reshape(8, 5).sum(axis=0)
    np.testing.assert_allclose(np.asarray(out).reshape(8, 5)[0], expected)
