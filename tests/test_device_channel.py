"""ICI device-collective channel: the coll_fns seam carries XLA collectives.

The VERDICT-driving contract: a mesh-bound Comm's allreduce/bcast/
allgather/alltoall dispatch to the XLA ops when selected, MV2T_*_ALGO can
force either path, and both paths produce identical results.
"""

import os

import numpy as np
import pytest

from mvapich2_tpu.runtime.universe import run_ranks
from mvapich2_tpu.utils.config import get_config

N_RANKS = 8
BIG = 16384  # >= default device crossover in elements*4 terms


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
    _reload(MV2T_ALLREDUCE_ALGO=None, MV2T_BCAST_ALGO=None,
            MV2T_USE_DEVICE_COLL=None, MV2T_DEVICE_COLL_MIN_BYTES=None)


def test_device_path_taken_and_matches_host():
    """Large f32 allreduce goes device; result == host-path result."""
    taken = {}

    def app(comm):
        x = np.full(BIG, float(comm.rank + 1), np.float32)
        out_dev = comm.allreduce(x)
        # force host and compare (env flips are process-global: barrier so
        # no rank is mid-collective under the other selection)
        comm.barrier()
        if comm.rank == 0:
            _reload(MV2T_ALLREDUCE_ALGO="ring")
        comm.barrier()
        out_host = comm.allreduce(x)
        comm.barrier()
        if comm.rank == 0:
            _reload(MV2T_ALLREDUCE_ALGO=None)
        comm.barrier()
        if comm.rank == 0:
            taken["dispatch"] = comm.coll_fns["allreduce"].__qualname__
        np.testing.assert_array_equal(out_dev, out_host)
        expect = sum(range(1, comm.size + 1))
        assert out_dev[0] == expect

    run_ranks(N_RANKS, app, device_mesh=True)
    # the installed entry is the device-channel wrapper, not the host api fn
    assert "wrap" in taken["dispatch"] or "entry" in taken["dispatch"]


def test_force_device_small_message():
    """MV2T_ALLREDUCE_ALGO=device forces the ICI path below crossover."""
    _reload(MV2T_ALLREDUCE_ALGO="device")

    def app(comm):
        x = np.full(4, float(comm.rank), np.float32)
        out = comm.allreduce(x)
        assert out[0] == sum(range(comm.size))

    run_ranks(N_RANKS, app, device_mesh=True)


def test_force_host_named_algo():
    """A named host algorithm keeps large messages on the host path."""
    _reload(MV2T_ALLREDUCE_ALGO="rsa")

    def app(comm):
        x = np.full(BIG, float(comm.rank), np.float32)
        out = comm.allreduce(x)
        assert out[0] == sum(range(comm.size))

    run_ranks(N_RANKS, app, device_mesh=True)


def test_all_device_collectives_match_host():
    """bcast/allgather/alltoall/reduce_scatter_block/reduce device results
    equal the host algorithms'."""
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")  # everything goes device

    def app(comm):
        p = comm.size
        r = comm.rank
        # bcast
        b = np.arange(64, dtype=np.float32) if r == 2 \
            else np.zeros(64, np.float32)
        comm.bcast(b, root=2)
        np.testing.assert_array_equal(b, np.arange(64, dtype=np.float32))
        # allgather
        mine = np.full(16, float(r), np.float32)
        got = comm.allgather(mine)
        expect = np.repeat(np.arange(p, dtype=np.float32), 16)
        np.testing.assert_array_equal(got, expect)
        # alltoall: rank r sends value r*p+j to rank j
        send = np.array([r * p + j for j in range(p)],
                        np.float32).repeat(4)
        got = comm.alltoall(send)
        expect = np.array([s * p + r for s in range(p)],
                          np.float32).repeat(4)
        np.testing.assert_array_equal(got, expect)
        # reduce_scatter_block
        send = np.arange(p * 8, dtype=np.float32) + r
        got = comm.reduce_scatter_block(send)
        base = np.arange(r * 8, (r + 1) * 8, dtype=np.float32)
        expect = base * p + sum(range(p))
        np.testing.assert_array_equal(got, expect)
        # reduce (max)
        from mvapich2_tpu.core import op as opmod
        got = comm.reduce(np.full(8, float(r), np.float32), op=opmod.MAX,
                          root=1)
        if r == 1:
            np.testing.assert_array_equal(
                got, np.full(8, float(p - 1), np.float32))

    run_ranks(N_RANKS, app, device_mesh=True)


def test_device_resident_buffers_round_trip():
    """jax-array buffers stay on device: result is a device array."""
    import jax.numpy as jnp

    def app(comm):
        x = jnp.full((256,), float(comm.rank + 1), jnp.float32)
        out = comm.allreduce(x)
        from mvapich2_tpu.coll.device import is_device_array
        assert is_device_array(out), type(out)
        assert float(out[0]) == sum(range(1, comm.size + 1))

    run_ranks(N_RANKS, app, device_mesh=True)


def test_f64_stays_on_host_path():
    """With jax x64 disabled, float64 must not be silently downcast —
    the selection keeps it on the host path and values stay exact."""
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")

    def app(comm):
        # a value that loses precision in f32
        x = np.full(64, 1.0 + 2.0**-40, np.float64)
        out = comm.allreduce(x)
        assert out[0] == comm.size * (1.0 + 2.0**-40)

    run_ranks(N_RANKS, app, device_mesh=True)


def test_unbound_comm_unaffected():
    """Without device_mesh, everything rides the host path as before."""
    def app(comm):
        x = np.full(BIG, float(comm.rank), np.float32)
        out = comm.allreduce(x)
        assert out[0] == sum(range(comm.size))
        assert comm.device_channel is None

    run_ranks(N_RANKS, app)


def test_rsb_nonsum_op_and_exact_prod():
    """reduce_scatter_block honors non-sum ops on the device path, and
    PROD is exact (zeros/negatives/ints — no log/exp trickery)."""
    from mvapich2_tpu.core import op as opmod
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")

    def app(comm):
        p, r = comm.size, comm.rank
        send = np.arange(p * 4, dtype=np.float32) + r
        got = comm.reduce_scatter_block(send, op=opmod.MAX)
        base = np.arange(r * 4, (r + 1) * 4, dtype=np.float32)
        np.testing.assert_array_equal(got, base + (p - 1))
        # prod with a negative and a zero contributor
        x = np.full(8, -1.0 if r == 0 else (0.0 if r == 1 else 2.0),
                    np.float32)
        got = comm.allreduce(x, op=opmod.PROD)
        np.testing.assert_array_equal(got, np.zeros(8, np.float32))
        x = np.full(8, -1.0 if r == 0 else 2.0, np.float32)
        got = comm.allreduce(x, op=opmod.PROD)
        np.testing.assert_array_equal(
            got, np.full(8, -(2.0 ** (comm.size - 1)), np.float32))

    run_ranks(N_RANKS, app, device_mesh=True)


def test_device_buffers_on_forced_host_path():
    """Device-array buffers still work when a host algorithm is forced —
    staged through the host, result back on device."""
    import jax.numpy as jnp
    _reload(MV2T_ALLREDUCE_ALGO="ring")

    def app(comm):
        from mvapich2_tpu.coll.device import is_device_array
        x = jnp.full((512,), float(comm.rank + 1), jnp.float32)
        out = comm.allreduce(x)
        assert is_device_array(out)
        assert float(out[0]) == sum(range(1, comm.size + 1))

    run_ranks(N_RANKS, app, device_mesh=True)


def test_device_buffer_on_unbound_comm_host_staged():
    """A device sendbuf on an unbound comm is staged through the host
    (numpy result) instead of crashing."""
    import jax.numpy as jnp

    def app(comm):
        x = jnp.full((64,), float(comm.rank), jnp.float32)
        out = comm.allreduce(x)
        assert isinstance(out, np.ndarray)
        assert out[0] == sum(range(comm.size))

    run_ranks(N_RANKS, app)


def test_rank_death_breaks_rendezvous():
    """A rank dying outside a device collective aborts the rendezvous
    barrier: peers see an error instead of deadlocking."""
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")

    def app(comm):
        if comm.rank == 3:
            raise RuntimeError("boom")
        comm.allreduce(np.ones(64, np.float32))

    with pytest.raises(RuntimeError):
        run_ranks(N_RANKS, app, device_mesh=True, timeout=60)


def test_nonsum_ops_and_in_place():
    from mvapich2_tpu.coll.api import IN_PLACE
    from mvapich2_tpu.core import op as opmod
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")

    def app(comm):
        x = np.full(32, float(comm.rank + 1), np.float32)
        out = comm.allreduce(x, op=opmod.MAX)
        assert out[0] == comm.size
        out = comm.allreduce(x, op=opmod.MIN)
        assert out[0] == 1.0
        # MPI_IN_PLACE
        buf = np.full(32, float(comm.rank + 1), np.float32)
        comm.allreduce(IN_PLACE, buf)
        assert buf[0] == sum(range(1, comm.size + 1))

    run_ranks(N_RANKS, app, device_mesh=True)


def test_hbm_streaming_tier_end_to_end():
    """ISSUE 8 acceptance shape: a buffer past the (here, forced-tiny)
    VMEM boundary runs the HBM-streaming chunked kernel through the
    full MPI channel — interpret mode on the CPU mesh — lands the right
    answer, and the per-call tier pvar counts it (never a silent XLA
    fallback)."""
    from mvapich2_tpu import mpit
    _reload(MV2T_ICI_INTERPRET="1", MV2T_DEV_TIER_VMEM_MAX="64",
            MV2T_ICI_CHUNK_BYTES="128", MV2T_DEVICE_COLL_MIN_BYTES="1")
    before = mpit.pvar("dev_coll_tier_hbm").read()
    try:
        def app(comm):
            x = np.full(256, float(comm.rank + 1), np.float32)
            out = comm.allreduce(x)     # 1 KiB shard > 64 B vmem cap
            expect = sum(range(1, comm.size + 1))
            np.testing.assert_array_equal(out, np.full(256, expect,
                                                       np.float32))

        run_ranks(N_RANKS, app, device_mesh=True)
        assert mpit.pvar("dev_coll_tier_hbm").read() >= before + N_RANKS
    finally:
        _reload(MV2T_ICI_INTERPRET=None, MV2T_DEV_TIER_VMEM_MAX=None,
                MV2T_ICI_CHUNK_BYTES=None, MV2T_DEVICE_COLL_MIN_BYTES=None)


# -- device-lane observability (ISSUE 10) --------------------------------

def test_device_dispatch_spans_and_phases(monkeypatch):
    """A traced device collective drops a B/E span in the 'device' lane
    whose B carries tier/op/bytes (its length is its two stamps'), and
    inside it the phase spans of the rendezvous
    (tests/test_device_phases.py holds their order and nesting per
    channel)."""
    monkeypatch.setenv("MV2T_TRACE", "1")
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")
    tiers = ("vmem", "hbm", "xla", "slot")
    device_lane = {}

    def app(comm):
        out = comm.allreduce(np.ones(BIG, np.float32))
        assert out[0] == comm.size
        rec = comm.u.engine.tracer
        assert rec is not None
        device_lane[comm.rank] = [e for e in rec.events
                                  if e[1] == "device"]

    run_ranks(N_RANKS, app, device_mesh=True)
    spans = [e for r in device_lane for e in device_lane[r]
             if e[2] == "dev_allreduce"]
    bs = [e for e in spans if e[3] == "B"]
    es = [e for e in spans if e[3] == "E"]
    assert bs and es
    args = bs[0][4]
    assert args["tier"] in tiers
    assert args["op"] == "sum" and args["bytes"] > 0
    assert es[0][4] == {"seq": 1, "coll": "allreduce", "ctx": 1}   # the world's ctx_coll
    for r, events in device_lane.items():
        names = [e[2] for e in events if e[3] == "B"]
        assert names[0] == "dev_allreduce"
        assert {"dev_arrive", "dev_release", "dev_deliver"} <= set(names)
        leader_only = {"dev_stage", "dev_dispatch", "dev_collect"}
        assert (leader_only <= set(names)) == (r == 0), (r, names)
        assert {e[4]["seq"] for e in events if e[3] in "BE"} == {1}


def test_jax_profile_hook_brackets_device_region(monkeypatch, tmp_path):
    """MV2T_JAX_PROFILE=<dir>: the first device collective starts a
    jax.profiler trace there (stopped at exit); the directory gains
    profile artifacts."""
    import mvapich2_tpu.coll.device as devmod
    monkeypatch.setattr(devmod, "_jax_profile_started", False)
    prof_dir = str(tmp_path / "xprof")
    _reload(MV2T_JAX_PROFILE=prof_dir, MV2T_DEVICE_COLL_MIN_BYTES="1")
    try:
        def app(comm):
            comm.allreduce(np.ones(BIG, np.float32))

        run_ranks(N_RANKS, app, device_mesh=True)
        assert devmod._jax_profile_started
        devmod._stop_jax_profile()
        files = [os.path.join(dp, f)
                 for dp, _dn, fn in os.walk(prof_dir) for f in fn]
        assert files, "jax.profiler produced no artifacts"
    finally:
        _reload(MV2T_JAX_PROFILE=None)
        monkeypatch.setattr(devmod, "_jax_profile_started", True)


# -- the slot channel's two operand forms (ISSUE 27) ----------------------
# Eight ranks on ONE device: device-resident deposits are the program's
# eight operands as they lie (dev_slot_operands counts the leader calls
# that did so); host deposits are stacked on the host and staged as one
# (R, n) operand. Same answers either way, one cached program per form.

def _slot_mesh():
    import jax

    from mvapich2_tpu.parallel.mesh import make_mesh
    return make_mesh((1,), ("x",), jax.devices()[:1])


def _slot_data(c):
    """Every rank's n = 8c whole numbers in [-2^20, 2^20] as f32: any
    order of f32 addition over eight ranks is exact."""
    return [np.random.default_rng([c, r]).integers(
        -2**20, 2**20, size=N_RANKS * c, endpoint=True).astype(np.float32)
        for r in range(N_RANKS)]


@pytest.mark.parametrize("c", [128, 125], ids=["rows128", "odd"])
@pytest.mark.parametrize("resident", ["device", "host"])
def test_slot_channel_every_collective_both_forms(resident, c):
    """allreduce sum/max, reduce, allgather, alltoall,
    reduce_scatter_block and bcast through the MPI calls, bit-equal to
    numpy, at n a multiple of 128 (the R-operand kernel) and not (the
    traced stack and pad)."""
    import jax.numpy as jnp

    from mvapich2_tpu import mpit
    from mvapich2_tpu.core import op as opmod
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")
    data = _slot_data(c)
    total = np.sum(data, axis=0)
    buf = jnp.asarray if resident == "device" else np.array
    keys = []

    def app(comm):
        assert type(comm.device_channel).__name__ == "HBMSlotChannel"
        r, p = comm.rank, comm.size
        got = lambda out: np.asarray(out).reshape(-1)
        np.testing.assert_array_equal(
            got(comm.allreduce(buf(data[r]))), total)
        np.testing.assert_array_equal(
            got(comm.allreduce(buf(data[r]), op=opmod.MAX)),
            np.max(data, axis=0))
        out = comm.reduce(buf(data[r]), root=3)
        if r == 3:
            np.testing.assert_array_equal(got(out), total)
        np.testing.assert_array_equal(
            got(comm.allgather(buf(data[r]))), np.concatenate(data))
        np.testing.assert_array_equal(
            got(comm.alltoall(buf(data[r]))),
            np.concatenate([d[r * c:(r + 1) * c] for d in data]))
        np.testing.assert_array_equal(
            got(comm.reduce_scatter_block(buf(data[r]))),
            total[r * c:(r + 1) * c])
        np.testing.assert_array_equal(
            got(comm.bcast(buf(data[r]), root=2)), data[2])
        if r == 0:
            keys.extend(comm.device_channel._programs)

    before = mpit.pvar("dev_slot_operands").read()
    run_ranks(N_RANKS, app, device_mesh=_slot_mesh())
    rose = mpit.pvar("dev_slot_operands").read() - before
    # one leader call per collective; bcast has one operand either way
    assert rose == (7 if resident == "device" else 0)
    assert len(keys) == 7
    # the key's last field is the leader's operand count
    assert {k[5] for k in keys if k[0] != "bcast"} == \
        ({N_RANKS} if resident == "device" else {1})
    assert [k[5] for k in keys if k[0] == "bcast"] == [1]


def test_slot_program_cache_one_entry_per_form():
    """Device and host deposits of one signature never share a program:
    the cache key carries the operand form, and repeating either form
    adds nothing."""
    import jax.numpy as jnp

    from mvapich2_tpu import mpit
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")
    data = _slot_data(16)
    total = np.sum(data, axis=0)
    sizes = []

    def app(comm):
        for buf in (jnp.asarray, np.array) * 2:
            out = comm.allreduce(buf(data[comm.rank]))
            np.testing.assert_array_equal(np.asarray(out), total)
            if comm.rank == 0:
                sizes.append(len(comm.device_channel._programs))
        if comm.rank == 0:
            sizes.append(sorted(
                k[5] for k in comm.device_channel._programs))

    before = mpit.pvar("dev_slot_operands").read()
    run_ranks(N_RANKS, app, device_mesh=_slot_mesh())
    assert sizes == [1, 2, 2, 2, [1, N_RANKS]]
    assert mpit.pvar("dev_slot_operands").read() - before == 2


# -- the mesh channel's flat form, from deposit to delivery (ISSUE 29) ----
# Four ranks 1:1 on four devices: a device-resident deposit is its
# device's shard of the program's global operand as it lies (same
# buffer; dev_mesh_operands counts the leader calls in which all four
# were), the program's result is flat, and _deliver hands a
# device-resident caller the very object the leader collected.

MESH_RANKS = 4
_MESH_COLLS = ["allreduce", "reduce", "bcast", "allgather", "alltoall",
               "alltoallv", "reduce_scatter_block"]
# ibcast on a device buffer has no host recvbuf to land in and keeps the
# host schedule; reduce and reduce_scatter_block have no device i-form
_NONBLOCKING = {"allreduce", "allgather", "alltoall", "alltoallv"}
# dense alltoallv counts: every rank packs 48 elements, so none is padded
_V_COUNTS = ((12, 20, 4, 12), (0, 16, 16, 16), (24, 8, 8, 8),
             (12, 12, 12, 12))


def _mesh_call(name, comm, x, recv=None):
    """One collective on the mesh channel: blocking through the comm's
    installed entry with no recvbuf (the result stays on the device), or
    nonblocking into the host ``recv``."""
    r = comm.rank
    if name == "alltoallv":
        from mvapich2_tpu.core.comm import _resolve
        sc = list(_V_COUNTS[r])
        rc = [_V_COUNTS[s][r] for s in range(MESH_RANKS)]
        if recv is not None:
            return comm.ialltoallv(x, sc, None, recv, rc, None)
        dense = lambda cs: [int(d) for d in np.cumsum([0] + cs[:-1])]
        # Comm.alltoallv hands back its recvbuf; the entry, the result
        return comm.coll_fns["alltoallv"](
            comm, x, sc, dense(sc), None, rc, dense(rc),
            _resolve(x, None, None)[1])
    if recv is not None:
        return getattr(comm, "i" + name)(x, recv)
    if name in ("reduce", "bcast"):
        return getattr(comm, name)(x, root=2)
    return getattr(comm, name)(x)


@pytest.mark.parametrize("deposits", ["device", "one_host"])
@pytest.mark.parametrize("name", _MESH_COLLS)
def test_mesh_leader_stages_by_identity(name, deposits):
    """Every mesh-channel collective, blocking and (where the device
    tier has one) nonblocking, bit-equal to the plain reference. With
    all four deposits on their own devices the global operand's shards
    are the deposited buffers, ``_deliver`` hands back the object the
    leader collected, and dev_mesh_operands rises once per call; with
    one host deposit among them it does not rise."""
    import jax

    import plain_reference as ref
    from mvapich2_tpu import mpit
    from mvapich2_tpu.parallel.mesh import make_mesh
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")
    n = 48
    data = [np.random.default_rng([29, r]).integers(
        -2**20, 2**20, size=n, endpoint=True).astype(np.float32)
        for r in range(MESH_RANKS)]
    want = {"alltoallv": lambda: ref.alltoallv(data, _V_COUNTS),
            "reduce": lambda: ref.reduce(data, 2),
            "bcast": lambda: ref.bcast(data, 2)}.get(
                name, lambda: getattr(ref, name)(data))()
    lay = deposits == "device"
    seen = {"ptr": [None] * MESH_RANKS, "same": [None] * MESH_RANKS}

    def rose(comm, call):
        """dev_mesh_operands over one collective, fenced by barriers."""
        comm.barrier()
        before = mpit.pvar("dev_mesh_operands").read()
        comm.barrier()
        out = call()
        comm.barrier()
        return out, mpit.pvar("dev_mesh_operands").read() - before

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == "DeviceCollChannel"
        r = comm.rank
        host = not lay and r == 1
        x = data[r].copy() if host else jax.device_put(data[r], ch.device)
        if not host:
            seen["ptr"][r] = x.unsafe_buffer_pointer()
        if r == 0:      # the leader: keep what it staged and collected
            glob, per = ch._global, ch._per_rank

            def keep(key, val):     # the first call's, the blocking one
                seen.setdefault(key, list(val) if key == "res" else val)
                return val
            ch._global = lambda sh, m: keep("glob", glob(sh, m))
            ch._per_rank = lambda out: keep("res", per(out))
        out, up = rose(comm, lambda: _mesh_call(name, comm, x))
        assert up == (1 if lay else 0)
        if want[r] is None:
            assert out is None
        else:
            np.testing.assert_array_equal(np.asarray(out), want[r])
            if lay:
                assert out.ndim == 1 and out.devices() == {ch.device}
                if name != "alltoallv":     # _deliver_v cuts the pad off
                    seen["same"][r] = out is seen["res"][r]
        if name in _NONBLOCKING:
            recv = np.zeros(want[r].size, np.float32)
            _, up = rose(comm, lambda: _mesh_call(name, comm, x,
                                                  recv).wait())
            assert up == (1 if lay else 0)
            np.testing.assert_array_equal(recv, want[r])

    run_ranks(MESH_RANKS, app, timeout=30,
              device_mesh=make_mesh((MESH_RANKS,), ("x",),
                                    jax.devices()[:MESH_RANKS]))
    assert seen["glob"].shape == (MESH_RANKS * n,)
    shards = {s.device: s.data for s in seen["glob"].addressable_shards}
    for r, dev in enumerate(jax.devices()[:MESH_RANKS]):
        assert shards[dev].shape == (n,)
        if seen["ptr"][r] is not None:      # the deposit, not a copy
            assert shards[dev].unsafe_buffer_pointer() == seen["ptr"][r]
    assert all(s is not False for s in seen["same"]), seen["same"]
    if lay and name != "alltoallv":
        assert sum(s is True for s in seen["same"]) == \
            sum(w is not None for w in want)


# -- a mesh whose order is not jax.devices()'s (ISSUE 31) -----------------
# make_mesh lays a 1-D mesh of TPU chips in ICI-neighbour order; the
# channels take rank r's device from the mesh, so they follow.

def test_channels_follow_a_reordered_mesh(monkeypatch):
    """Four CPU devices laid 0, 1, 3, 2 (the ordering helper patched, as
    CPU devices carry no ``coords``): rank r lives on the mesh's r-th
    device, so rank 2 on device 3; ``comm.allreduce`` and
    ``comm.alltoall`` are bit-equal to the plain reference, every result
    on its rank's own device, four distinct devices, and
    ``dev_mesh_reordered`` rose by one."""
    import jax

    import plain_reference as ref
    from mvapich2_tpu import mpit
    from mvapich2_tpu.parallel import mesh as pmesh
    _reload(MV2T_DEVICE_COLL_MIN_BYTES="1")
    devs = jax.devices()[:MESH_RANKS]
    monkeypatch.setattr(pmesh, "_ring_order",
                        lambda given: [given[i] for i in (0, 1, 3, 2)])
    before = mpit.pvar("dev_mesh_reordered").read()
    mesh = pmesh.make_mesh((MESH_RANKS,), ("x",), devs)
    assert mpit.pvar("dev_mesh_reordered").read() - before == 1
    assert [d.id for d in mesh.devices] == [devs[i].id for i in (0, 1, 3, 2)]
    n = MESH_RANKS * 48
    data = [np.random.default_rng([31, r]).integers(
        -2**20, 2**20, size=n, endpoint=True).astype(np.float32)
        for r in range(MESH_RANKS)]
    want = {"allreduce": ref.allreduce(data), "alltoall": ref.alltoall(data)}
    lived = [None] * MESH_RANKS

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == "DeviceCollChannel"
        r = comm.rank
        assert ch.device == mesh.devices[r]
        lived[r] = ch.device
        x = jax.device_put(data[r], ch.device)
        for name in ("allreduce", "alltoall"):
            out = getattr(comm, name)(x)
            assert out.devices() == {ch.device}
            np.testing.assert_array_equal(np.asarray(out), want[name][r])

    run_ranks(MESH_RANKS, app, timeout=60, device_mesh=mesh)
    assert lived == [devs[0], devs[1], devs[3], devs[2]]
    assert len(set(lived)) == MESH_RANKS
