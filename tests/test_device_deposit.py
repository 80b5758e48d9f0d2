"""A rank deposits the array its caller handed over (ISSUE 35): a flat
device array asked for whole goes to the rendezvous as the object it is,
through no call into jax's ``reshape`` or indexing, on every channel and
through the six blocking entries and the nonblocking build alike; the
pvar ``dev_deposit_as_is`` counts it per rank per call and the
``dev_<coll>`` B record says ``as_is``. Everything else (a shaped
buffer, a count below the size, MPI_IN_PLACE at an offset, a host
buffer) takes the path it took and gives the plain reference's result
(tests/plain_reference.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import plain_reference as ref
from mvapich2_tpu import mpit
from mvapich2_tpu.coll import device as devmod
from mvapich2_tpu.parallel.mesh import make_mesh
from mvapich2_tpu.runtime.universe import run_ranks
from mvapich2_tpu.utils.config import get_config

CALLS = 2
ROOT = 2
# channel -> (ranks, devices of the mesh it binds to, class, the pvar of
# its leader's by-identity staging; the fold channel stacks per chip)
CHANNELS = {"slot": (8, 1, "HBMSlotChannel", "dev_slot_operands"),
            "mesh": (4, 4, "DeviceCollChannel", "dev_mesh_operands"),
            "fold": (8, 4, "DeviceFoldChannel", None)}
BLOCKING = ["allreduce", "reduce", "bcast", "allgather", "alltoall",
            "reduce_scatter_block"]
# the device tier's i-forms that take a device sendbuf (ibcast's one
# buffer is its host recvbuf; reduce, reduce_scatter_block have none)
NONBLOCKING = ["allreduce", "allgather", "alltoall"]
AS_IS = ([("slot", n, "blocking", "float32") for n in BLOCKING]
         + [("mesh", n, "blocking", "float32") for n in BLOCKING]
         + [("mesh", n, "nonblocking", "float32") for n in NONBLOCKING]
         + [("fold", "allreduce", "blocking", "float32"),
            ("slot", "alltoall", "blocking", "bfloat16"),
            ("mesh", "allgather", "blocking", "bfloat16")])


@pytest.fixture(autouse=True)
def device_path(monkeypatch):
    """Buffers of every size take the device path."""
    monkeypatch.setenv("MV2T_DEVICE_COLL_MIN_BYTES", "1")
    get_config().reload()
    yield
    monkeypatch.undo()
    get_config().reload()


def _mesh(ndev):
    return make_mesh((ndev,), ("x",), jax.devices()[:ndev])


def _data(ranks, n, dtype="float32"):
    """Whole numbers, other on every rank; small enough that bfloat16
    holds them and every sum over eight ranks exactly."""
    return [np.random.default_rng([35, r]).integers(
        -15, 15, size=n, endpoint=True).astype(jnp.dtype(dtype))
        for r in range(ranks)]


def _want(name, data):
    if name in ("reduce", "bcast"):
        return getattr(ref, name)(data, ROOT)
    return getattr(ref, name)(data)


def _call(name, comm, x, **kw):
    if name in ("reduce", "bcast"):
        kw["root"] = ROOT
    return getattr(comm, name)(x, **kw)


def _reads(*names):
    return {n: mpit.pvar(n).read() for n in names if n}


def _same(got, want):
    np.testing.assert_array_equal(
        np.asarray(got).astype(np.float32).reshape(-1),
        np.asarray(want).astype(np.float32).reshape(-1))


@pytest.mark.parametrize("channel,name,form,dtype", AS_IS, ids=[
    "-".join(c) for c in AS_IS])
def test_flat_whole_device_array_is_deposited_as_it_is(channel, name, form,
                                                       dtype):
    ranks, ndev, klass, operands = CHANNELS[channel]
    n = ranks * 16
    data = _data(ranks, n, dtype)
    want = _want(name, data)
    given = [[None] * ranks for _ in range(CALLS)]
    lay = [[None] * ranks for _ in range(CALLS)]

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == klass
        r = comm.rank
        x = jax.device_put(data[r], ch.device)
        call = [0]
        if form == "blocking" and r == 0:
            leader = ch._leader

            def keep(*a):       # what lies at the rendezvous when all came
                lay[call[0]][:] = ch.rv.slots
                return leader(*a)
            ch._leader = keep
        elif form == "nonblocking":
            build = ch._build_nonblocking

            def keep(comm_, name_, local, *a):
                lay[call[0]][r] = local
                return build(comm_, name_, local, *a)
            ch._build_nonblocking = keep
        for i in range(CALLS):
            call[0], given[i][r] = i, x
            if form == "blocking":
                out = _call(name, comm, x)
            else:
                out = np.empty(np.asarray(want[r]).shape, data[r].dtype)
                getattr(comm, "i" + name)(x, out).wait()
            if want[r] is not None:
                _same(out, want[r])
            comm.barrier()

    names = ("dev_deposit_as_is", operands)
    before = _reads(*names)
    run_ranks(ranks, app, device_mesh=_mesh(ndev))
    rose = {k: v - before[k] for k, v in _reads(*names).items()}
    for i in range(CALLS):
        for r in range(ranks):
            assert lay[i][r] is given[i][r], (i, r)
    assert rose["dev_deposit_as_is"] == ranks * CALLS
    if operands:
        assert rose[operands] == CALLS


def _shaped(comm, x, data, name):
    return _call(name, comm, x.reshape(2, -1)), _want(name, data)


def _partial(comm, x, data, name):
    half = x.shape[0] // 2
    return (_call(name, comm, x, count=half),
            _want(name, [d[:half] for d in data]))


def _in_place_allgather(comm, x, data, name):
    from mvapich2_tpu.coll.api import IN_PLACE
    c = x.shape[0]
    recv = jnp.zeros(comm.size * c, x.dtype).at[
        comm.rank * c:(comm.rank + 1) * c].set(x)
    return (comm.allgather(IN_PLACE, recv, count=c),
            ref.allgather(data))


def _host(comm, x, data, name):
    return _call(name, comm, np.asarray(x)), _want(name, data)


# what goes round the identity, collective, dtype
BYPASS = [(_shaped, "allreduce", "float32"),
          (_shaped, "alltoall", "float32"),
          (_partial, "allreduce", "float32"),
          (_partial, "bcast", "float32"),
          (_in_place_allgather, "allgather", "float32"),
          (_host, "allreduce", "float32"),
          (_host, "allgather", "bfloat16"),
          (_shaped, "allgather", "bfloat16")]


@pytest.mark.parametrize("channel", ["slot", "mesh"])
@pytest.mark.parametrize("how,name,dtype", BYPASS, ids=[
    f"{h.__name__.strip('_')}-{n}-{d}" for h, n, d in BYPASS])
def test_other_buffers_read_as_before_and_do_not_count(channel, how, name,
                                                       dtype):
    ranks, ndev, klass, _ = CHANNELS[channel]
    data = _data(ranks, ranks * 16, dtype)

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == klass
        x = jax.device_put(data[comm.rank], ch.device)
        out, want = how(comm, x, data, name)
        if want[comm.rank] is not None:
            _same(out, want[comm.rank])

    fallbacks = [n for n in (mpit.pvar_get_info(i)["name"]
                             for i in range(mpit.pvar_get_num()))
                 if n.startswith("dev_coll_fallback_")]
    level = "coll_level_chip" if channel == "slot" else "coll_level_ici"
    before = _reads("dev_deposit_as_is", level, *fallbacks)
    run_ranks(ranks, app, device_mesh=_mesh(ndev))
    rose = {k: v - before[k] for k, v in
            _reads("dev_deposit_as_is", level, *fallbacks).items()}
    assert rose.pop("dev_deposit_as_is") == 0
    assert rose.pop(level) == ranks       # the device carried the call
    assert not any(v for k, v in rose.items() if "host" in k), rose


def test_a_flat_device_buffer_enters_neither_reshape_nor_getitem(
        monkeypatch):
    """One rank's way through a blocking call on the slot channel calls
    neither ``jax.Array.reshape`` nor ``jax.Array.__getitem__``: not in
    the deposit, not in the leader, not in the delivery."""
    from jax._src.array import ArrayImpl
    ranks, ndev, klass, _ = CHANNELS["slot"]
    data = _data(ranks, ranks * 128)
    want = ref.allreduce(data)
    entered = {"reshape": 0, "__getitem__": 0}
    watching = [False]
    xs, outs = [None] * ranks, [None] * ranks

    def counted(attr):
        real = getattr(ArrayImpl, attr)

        def method(self, *a, **k):
            if watching[0]:
                entered[attr] += 1
            return real(self, *a, **k)
        monkeypatch.setattr(ArrayImpl, attr, method)
    counted("reshape")
    counted("__getitem__")

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == klass
        r = comm.rank
        xs[r] = jax.device_put(data[r], ch.device)
        comm.allreduce(xs[r])               # builds the program
        comm.barrier()
        watching[0] = True
        comm.barrier()
        for _ in range(CALLS):
            outs[r] = comm.allreduce(xs[r])
        comm.barrier()
        watching[0] = False

    run_ranks(ranks, app, device_mesh=_mesh(ndev))
    assert entered == {"reshape": 0, "__getitem__": 0}
    for r in range(ranks):
        _same(outs[r], want[r])
    # the patch does count: the path a shaped buffer takes goes through it
    watching[0] = True
    local, as_is = devmod._as_local(xs[0].reshape(2, -1), None,
                                    xs[0].shape[0])
    assert not as_is and entered["reshape"] >= 1 \
        and entered["__getitem__"] >= 1
    _same(local, data[0])


def test_the_dev_coll_record_says_as_is(monkeypatch):
    """Traced, the ``dev_<coll>`` B of a call whose deposit was the
    caller's own array says ``as_is`` True, and False otherwise."""
    monkeypatch.setenv("MV2T_TRACE", "1")
    get_config().reload()
    ranks, ndev, _klass, _ = CHANNELS["slot"]
    data = _data(ranks, ranks * 16)
    said = [None] * ranks

    def app(comm):
        x = jax.device_put(data[comm.rank], comm.device_channel.device)
        comm.allreduce(x)
        comm.allreduce(x.reshape(2, -1))
        comm.allreduce(np.asarray(x))
        said[comm.rank] = [
            a["as_is"] for _t, lane, name, ph, a in comm.u.engine.tracer.events
            if (lane, name, ph) == ("device", "dev_allreduce", "B")]

    run_ranks(ranks, app, device_mesh=_mesh(ndev))
    assert said == [[True, False, False]] * ranks
