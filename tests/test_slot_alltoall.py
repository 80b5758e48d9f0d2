"""The one-chip alltoall (ISSUE 32): eight ranks bound to one device,
``comm.alltoall`` through ``HBMSlotChannel``, bit-equal to the plain
numpy reference (tests/plain_reference.py) for device and host deposits
and for blocks of whole tiles, of whole 128-lane rows and of neither;
every device result flat and on the slot device. And what the leader
and ``_deliver`` say about the eager device ops behind a result: the
``parts`` arg of ``dev_collect``'s E (summed in the pvar
``dev_slot_result_parts`` on the slot channel) and the ``relaid`` arg
of ``dev_deliver``'s E, on all three channels.
"""

import jax
import numpy as np
import pytest

import plain_reference as ref
from mvapich2_tpu import mpit
from mvapich2_tpu.parallel.mesh import make_mesh
from mvapich2_tpu.runtime.universe import run_ranks
from mvapich2_tpu.utils.config import get_config

RANKS = 8
CALLS = 2
# channel -> (ranks, devices of the mesh it binds to, class name)
CHANNELS = {"mesh": (4, 4, "DeviceCollChannel"),
            "slot": (8, 1, "HBMSlotChannel"),
            "fold": (8, 4, "DeviceFoldChannel")}


def _mesh(ndev):
    return make_mesh((ndev,), ("x",), jax.devices()[:ndev])


def _data(seed, rank, n):
    """chipbench's values: whole numbers in +-2^20, other on every rank."""
    rng = np.random.default_rng([seed, rank])
    return rng.integers(-2 ** 20, 2 ** 20, size=n,
                        endpoint=True).astype(np.float32)


def _reads(*names):
    fb = [mpit.pvar_get_info(i)["name"] for i in range(mpit.pvar_get_num())]
    names += tuple(n for n in fb if n.startswith("dev_coll_fallback_"))
    return {n: mpit.pvar(n).read() for n in names}


@pytest.fixture
def device_path(monkeypatch):
    """Host buffers of every size take the device path too."""
    monkeypatch.setenv("MV2T_DEVICE_COLL_MIN_BYTES", "1")
    get_config().reload()
    yield
    monkeypatch.undo()
    get_config().reload()


# a float32 tile is (8, 128): 4096 elements a pair are whole tiles, 384
# whole 128-lane rows and no whole tile, 1000 neither
@pytest.mark.parametrize("block", [4096, 384, 1000],
                         ids=["tiles", "rows128", "ragged"])
@pytest.mark.parametrize("deposit", ["device", "host", "host_recvbuf"])
def test_slot_alltoall_is_the_plain_reference(device_path, deposit, block):
    inputs = [_data(3201, r, RANKS * block) for r in range(RANKS)]
    want = ref.alltoall(inputs)
    got, flat_on_slot = [None] * RANKS, [None] * RANKS
    watch = ("coll_level_chip", "dev_slot_result_parts", "dev_slot_operands")
    before = _reads(*watch)

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == "HBMSlotChannel"
        r = comm.rank
        for _ in range(CALLS):
            if deposit == "device":
                out = comm.alltoall(jax.device_put(inputs[r], ch.device))
            elif deposit == "host":
                # the channel's entry with no recvbuf: a host deposit, a
                # device result (what comm.alltoall does for a caller
                # whose recvbuf is a device array)
                out = ch.alltoall(comm, inputs[r], None, block, None)
            else:
                out = comm.alltoall(inputs[r])      # into a host recvbuf
                assert isinstance(out, np.ndarray)
        if deposit != "host_recvbuf":
            flat_on_slot[r] = (out.shape == (RANKS * block,)
                               and out.dtype == np.float32
                               and out.devices() == {ch.device})
        got[r] = np.asarray(out)

    run_ranks(RANKS, app, device_mesh=_mesh(1))
    rose = {n: v - before[n] for n, v in _reads(*watch).items()}
    for r in range(RANKS):
        assert got[r].shape == want[r].shape and got[r].dtype == np.float32
        assert np.array_equal(got[r].view(np.uint32),
                              want[r].view(np.uint32)), r
    if deposit != "host_recvbuf":
        assert flat_on_slot == [True] * RANKS
    assert rose.pop("coll_level_chip") == RANKS * CALLS
    # eight arrays cut out of the program's result in every call
    assert rose.pop("dev_slot_result_parts") == RANKS * CALLS
    assert rose.pop("dev_slot_operands") == \
        (CALLS if deposit == "device" else 0)
    assert rose and not any(rose.values()), rose    # the fallback family


@pytest.fixture
def traced(monkeypatch, device_path):
    monkeypatch.setenv("MV2T_TRACE", "1")
    get_config().reload()
    yield       # device_path's teardown undoes and reloads


# (channel, collective, buffers) -> parts on rank 0's dev_collect E,
# relaid on every rank's dev_deliver E
EAGER = [("slot", "alltoall", "device", 8, 1),
         ("slot", "alltoall", "host", 8, 0),
         ("slot", "reduce_scatter_block", "device", 8, 0),
         ("slot", "allreduce", "device", 0, 0),
         ("slot", "allgather", "device", 0, 0),
         ("slot", "bcast", "device", 0, 0),
         ("mesh", "allreduce", "device", 0, 0),
         ("mesh", "alltoall", "device", 0, 0),
         ("mesh", "reduce_scatter_block", "device", 0, 0),
         ("fold", "reduce_scatter_block", "device", 8, 0),
         ("fold", "allreduce", "device", 0, 0)]


@pytest.mark.parametrize("channel,coll,buffers,parts,relaid", EAGER,
                         ids=["-".join(map(str, e[:3])) for e in EAGER])
def test_spans_say_the_eager_ops_behind_a_result(traced, channel, coll,
                                                 buffers, parts, relaid):
    ranks, ndev, klass = CHANNELS[channel]
    n = ranks * 512
    lanes = {}

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == klass
        x = _data(3202, comm.rank, n)
        if buffers == "device":
            x = jax.device_put(x, ch.device)
        call = {"bcast": lambda: comm.bcast(x, root=1)}.get(
            coll, lambda: getattr(comm, coll)(x))
        for _ in range(CALLS):
            call()
        lanes[comm.rank] = [e for e in comm.u.engine.tracer.events
                            if e[1] == "device"]

    before = mpit.pvar("dev_slot_result_parts").read()
    run_ranks(ranks, app, device_mesh=_mesh(ndev))
    rose = mpit.pvar("dev_slot_result_parts").read() - before
    assert rose == (parts * CALLS if channel == "slot" else 0)

    def ends(rank, name):
        return [a for _t, _l, nam, ph, a in lanes[rank]
                if nam == name and ph == "E"]
    collect = ends(0, "dev_collect")
    assert [(a["seq"], a["parts"]) for a in collect] == \
        [(s, parts) for s in range(1, CALLS + 1)]
    for rank in range(ranks):
        # the leader alone collects; every rank delivers, under the seq
        assert rank == 0 or not ends(rank, "dev_collect")
        assert [(a["seq"], a["relaid"]) for a in ends(rank, "dev_deliver")] \
            == [(s, relaid) for s in range(1, CALLS + 1)], rank
        # the Bs carry what they always carried
        assert all(set(a) == {"seq", "coll"} for _t, _l, nam, ph, a
                   in lanes[rank]
                   if ph == "B" and nam in ("dev_collect", "dev_deliver"))
