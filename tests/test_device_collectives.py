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


# ---------------------------------------------------------------------------
# comm.bcast on the 1:1 mesh channel (ISSUE 51): the streaming chain
# (``mv2t_hbm_bcast``, interpreted on four CPU devices) from a device
# buffer, held to the plain reference; the call counts the tier its
# program holds and what the root puts on the wire; the vmem bin keeps
# XLA's lowering and counts no fallback; ibcast's segments take whatever
# the one rule says of a segment's size.
# ---------------------------------------------------------------------------

P4 = 4
_BCAST_WATCH = ("coll_level_ici", "dev_coll_tier_hbm", "dev_coll_tier_vmem",
                "dev_bc_wire_bytes", "dev_call_plan_hit",
                "dev_call_plan_filed", "dev_deposit_as_is")


@pytest.fixture
def interpreted_chain(monkeypatch):
    """The ring kernels under the interpreter; the vmem bin up to 8 KiB,
    no XLA crossover; the recorder on."""
    from mvapich2_tpu.utils.config import get_config
    for k, v in (("MV2T_ICI_INTERPRET", "1"), ("MV2T_TRACE", "1"),
                 ("MV2T_DEV_TIER_VMEM_MAX", "8192"),
                 ("MV2T_DEV_TIER_XLA_MIN", "-1")):
        monkeypatch.setenv(k, v)
    get_config().reload()
    yield monkeypatch
    monkeypatch.undo()
    get_config().reload()


def _fallback_reads():
    from mvapich2_tpu import mpit
    names = (mpit.pvar_get_info(i)["name"]
             for i in range(mpit.pvar_get_num()))
    return {n: mpit.pvar(n).read() for n in names
            if n.startswith("dev_coll_fallback_")}


def _bcast_inputs(n, dtype, seed):
    """Other whole numbers on every rank: a rank handed its own buffer
    back, or another non-root's, shows."""
    return [np.random.default_rng([seed, r]).integers(
        -1 << 20, 1 << 20, n).astype(np.float32).astype(dtype)
        for r in range(P4)]


def _drive_bcast(inputs, root, calls):
    """Four ranks on four devices, each calling ``comm.bcast`` on its
    own device-resident flat array ``calls`` times and deleting the
    array it sent afterwards. Returns the last results on the host,
    what the watched pvars and the fallback family rose by, and every
    rank's ``device``-lane events."""
    from mvapich2_tpu import mpit, run_ranks
    before = {n: mpit.pvar(n).read() for n in _BCAST_WATCH}
    fb0 = _fallback_reads()
    got, lanes = [None] * P4, [None] * P4

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == "DeviceCollChannel"
        x = jax.device_put(inputs[comm.rank], ch.device)
        for _ in range(calls):
            out = jax.block_until_ready(comm.bcast(x, root=root))
        assert out.devices() == {ch.device} and out.ndim == 1
        assert out is not x     # the root's result is a copy of its own
        comm.barrier()
        x.delete()              # the senders' buffers go; the result holds
        got[comm.rank] = np.asarray(out)
        lanes[comm.rank] = [e for e in comm.u.engine.tracer.events
                            if e[1] == "device"]

    run_ranks(P4, app, device_mesh=make_mesh((P4,), ("x",),
                                             jax.devices()[:P4]))
    rose = {n: mpit.pvar(n).read() - before[n] for n in _BCAST_WATCH}
    fb = {n: v - fb0[n] for n, v in _fallback_reads().items() if v != fb0[n]}
    return got, rose, fb, lanes


def _bits_equal(got, want):
    for r, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == w.dtype and g.shape == w.shape, (r, g.shape)
        bits = np.dtype(f"u{w.dtype.itemsize}")
        assert np.array_equal(g.view(bits), w.view(bits)), r


@pytest.mark.parametrize("root", [0, 2])
def test_comm_bcast_streams_and_counts_itself(interpreted_chain, root):
    import ml_dtypes
    from plain_reference import bcast as reference
    from mvapich2_tpu.ops import pallas_ici
    dt = np.dtype(ml_dtypes.bfloat16)
    n = 5 * 2048                    # 20 KiB: the streaming bin, 5 tiles
    inputs = _bcast_inputs(n, dt, 5100 + root)
    got, rose, fb, lanes = _drive_bcast(inputs, root, calls=3)
    _bits_equal(got, reference(inputs, root))
    # the tier the program holds, a rank a call; call 1 decides and
    # files, calls 2 and 3 run its plan and count as it did
    assert rose["coll_level_ici"] == rose["dev_coll_tier_hbm"] == P4 * 3
    assert rose["dev_coll_tier_vmem"] == 0 and fb == {}
    assert rose["dev_call_plan_filed"] == P4
    assert rose["dev_call_plan_hit"] == P4 * 2
    assert rose["dev_deposit_as_is"] == P4 * 3
    wire = pallas_ici.bcast_wire_bytes(n, dt, P4)
    assert wire == n * 2 and rose["dev_bc_wire_bytes"] == P4 * 3 * wire
    for lane in lanes:
        wires = [(a["seq"], a["coll"], a["wire_bytes"])
                 for _t, _l, name, ph, a in lane
                 if name == "dev_bc_wire" and ph == "i"]
        assert wires == [(s, "bcast", wire) for s in (1, 2, 3)]
        begun = [(a["seq"], a["tier"], a["planned"], a["as_is"])
                 for _t, _l, name, ph, a in lane
                 if name == "dev_bcast" and ph == "B"]
        assert begun == [(1, "hbm", False, True), (2, "hbm", True, True),
                         (3, "hbm", True, True)]
    # the lowering asked the same rule, once for the one signature
    lowered = [a for lane in lanes for _t, _l, name, _ph, a in lane
               if name == "ici_bcast"]
    assert lowered and all(a["tier"] == "hbm" and a["root"] == root
                           for a in lowered)
    # and says how the chain it lowered to is written (ISSUE 54)
    steps = pallas_ici.ring_steps("bcast", n, dt, P4)
    assert steps["steps_traced"] > 0
    assert all({k: a[k] for k in steps} == steps for a in lowered)


def test_comm_bcast_in_the_vmem_bin_is_xla_and_no_fallback(
        interpreted_chain):
    from plain_reference import bcast as reference
    inputs = _bcast_inputs(1024, np.dtype(np.float32), 5103)   # 4 KiB
    got, rose, fb, lanes = _drive_bcast(inputs, 1, calls=2)
    _bits_equal(got, reference(inputs, 1))
    assert rose["coll_level_ici"] == P4 * 2 and fb == {}
    assert rose["dev_coll_tier_hbm"] == rose["dev_coll_tier_vmem"] == 0
    assert rose["dev_bc_wire_bytes"] == 0
    assert rose["dev_call_plan_filed"] == rose["dev_call_plan_hit"] == P4
    for lane in lanes:
        assert [a["tier"] for _t, _l, name, ph, a in lane
                if name == "dev_bcast" and ph == "B"] == ["xla", "xla"]
        assert not [1 for _t, _l, name, _ph, _a in lane
                    if name == "dev_bc_wire"]


@pytest.mark.parametrize("seg_bytes,tier", [(12288, "hbm"), (4096, "xla")])
def test_ibcast_segments_take_the_rule_of_their_own_size(interpreted_chain,
                                                         seg_bytes, tier):
    """``ibcast`` of 24 KiB cut into segments either side of the 8 KiB
    edge: two of 12 KiB, each the chain, or six of 4 KiB, each XLA's
    lowering; the bits are the root's either way. (The buffer is the
    host's: a jax array cannot be written at ``wait()``, so ``ibcast``
    never took one, on any path.)"""
    from mvapich2_tpu import run_ranks
    from mvapich2_tpu.utils.config import get_config
    interpreted_chain.setenv("MV2T_DEVICE_COLL_MIN_BYTES", "1")
    interpreted_chain.setenv("MV2T_DEVICE_NBC_SEG_BYTES", str(seg_bytes))
    get_config().reload()
    n, root = 6144, 3
    inputs = _bcast_inputs(n, np.dtype(np.float32), 5104)
    routed, tiers = [], []

    def app(comm):
        buf = inputs[comm.rank].copy()
        req = comm.ibcast(buf, root=root)
        routed.append(getattr(req, "device_nbc", False))
        req.wait()
        np.testing.assert_array_equal(buf, inputs[root])
        comm.barrier()      # whichever rank's poll launched a segment
        tiers.extend(a["tier"] for _t, lane, name, _ph, a
                     in comm.u.engine.tracer.events
                     if lane == "device" and name == "ici_bcast")

    run_ranks(P4, app, device_mesh=make_mesh((P4,), ("x",),
                                             jax.devices()[:P4]))
    assert routed and all(routed)
    assert tiers and set(tiers) == {tier}
