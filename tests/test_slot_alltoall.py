"""The one-chip alltoall (ISSUE 32) and the slot channel's other
scattered result, reduce_scatter_block: eight ranks bound to one device,
``comm.<collective>`` through ``HBMSlotChannel``, bit-equal to the plain
numpy reference (tests/plain_reference.py) for device and host deposits
and for blocks of whole tiles, of whole 128-lane rows and of neither;
every device result flat and on the slot device. Since ISSUE 33 the
slot program's outputs are the per-rank results: no eager device op
stands behind one, which the ``parts`` arg of ``dev_collect``'s E and
the ``relaid`` arg of ``dev_deliver``'s E say on all three channels,
and every rank's result is a buffer of its own that the next call
leaves alone.
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


def _mpi_op(op):
    from mvapich2_tpu.core import op as opmod
    return {"sum": opmod.SUM, "max": opmod.MAX}[op]


# collective -> (comm's method, the channel entry's arguments after
# ``count``, the plain reference); op is "sum" or "max", unused by alltoall
SCATTERED = {
    "alltoall": (lambda comm, x, op: comm.alltoall(x),
                 lambda op: (None,),
                 lambda inputs, op: ref.alltoall(inputs)),
    "reduce_scatter_block": (
        lambda comm, x, op: comm.reduce_scatter_block(x, op=_mpi_op(op)),
        lambda op: (None, _mpi_op(op)),
        ref.reduce_scatter_block)}


def _slot_call(coll, comm, x, deposit, block, op="sum"):
    """One call on the slot channel: a device deposit, a host deposit
    with a device result (the channel's entry with no recvbuf: what
    ``comm.<coll>`` does for a caller whose recvbuf is a device array),
    or a host deposit into a host recvbuf."""
    method, entry_args, _ = SCATTERED[coll]
    ch = comm.device_channel
    assert type(ch).__name__ == "HBMSlotChannel"
    if deposit == "device":
        return method(comm, jax.device_put(x, ch.device), op)
    if deposit == "host":
        return getattr(ch, coll)(comm, x, None, block, *entry_args(op))
    out = method(comm, x, op)
    assert isinstance(out, np.ndarray)
    return out


def _is_the_plain_reference(coll, deposit, block, seed, op="sum"):
    inputs = [_data(seed, r, RANKS * block) for r in range(RANKS)]
    want = SCATTERED[coll][2](inputs, op)
    got, flat_on_slot = [None] * RANKS, [None] * RANKS
    watch = ("coll_level_chip", "dev_slot_operands")
    before = _reads(*watch)

    def app(comm):
        r = comm.rank
        for _ in range(CALLS):
            out = _slot_call(coll, comm, inputs[r], deposit, block, op)
        if deposit != "host_recvbuf":
            flat_on_slot[r] = (out.shape == want[r].shape
                               and out.dtype == np.float32
                               and out.devices()
                               == {comm.device_channel.device})
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
    assert rose.pop("dev_slot_operands") == \
        (CALLS if deposit == "device" else 0)
    assert rose and not any(rose.values()), rose    # the fallback family


# a float32 tile is (8, 128): 4096 elements a pair are whole tiles, 384
# whole 128-lane rows and no whole tile, 1000 neither
BLOCKS = pytest.mark.parametrize("block", [4096, 384, 1000],
                                 ids=["tiles", "rows128", "ragged"])
DEPOSITS = pytest.mark.parametrize("deposit",
                                   ["device", "host", "host_recvbuf"])


@BLOCKS
@DEPOSITS
def test_slot_alltoall_is_the_plain_reference(device_path, deposit, block):
    _is_the_plain_reference("alltoall", deposit, block, 3201)


# the sums are exact: eight whole numbers of at most 2^20 stay under
# 2^24. sum rides the mv2t_slot_reduce kernel (interpreted here), max
# the XLA reduction over the same slot array
@pytest.mark.parametrize("op", ["sum", "max"])
@BLOCKS
@DEPOSITS
def test_slot_reduce_scatter_block_is_the_plain_reference(device_path,
                                                          deposit, block,
                                                          op):
    """The alltoall's cases for the slot channel's other per-rank
    result, in float32. The bfloat16 device deposit at 512 elements a
    block (whole rows, no whole tile) is tests/test_device_dtype.py::
    test_bfloat16_collective_is_the_plain_reference[slot-
    reduce_scatter_block] and is not repeated here."""
    _is_the_plain_reference("reduce_scatter_block", deposit, block, 3301,
                            op)


@pytest.mark.parametrize("deposit", ["device", "host"])
@pytest.mark.parametrize("coll", list(SCATTERED))
def test_a_result_outlives_the_next_call(device_path, coll, deposit):
    """The program's outputs are the callers' results, so it may neither
    donate nor alias: rank r's result of call k is a buffer of its own
    (not a send buffer, not a result of call k + 1, not another rank's)
    and still reads its reference after call k + 1 ran on other data;
    the send buffers read what was put into them."""
    block = 1024
    inputs = [[_data(3302 + k, r, RANKS * block) for r in range(RANKS)]
              for k in range(2)]
    want = [SCATTERED[coll][2](inputs[k], "sum") for k in range(2)]
    seen = [None] * RANKS

    def app(comm):
        r = comm.rank
        sent = [jax.device_put(inputs[k][r], comm.device_channel.device)
                if deposit == "device" else inputs[k][r] for k in range(2)]
        outs = [_slot_call(coll, comm, x, deposit, block) for x in sent]
        jax.block_until_ready(outs)
        held = outs + (sent if deposit == "device" else [])
        seen[r] = ([np.asarray(o) for o in outs],
                   [np.asarray(x) for x in sent],
                   [a.unsafe_buffer_pointer() for a in held])

    run_ranks(RANKS, app, device_mesh=_mesh(1))
    for r in range(RANKS):
        for k in range(2):
            assert np.array_equal(seen[r][0][k].view(np.uint32),
                                  want[k][r].view(np.uint32)), (r, k)
            assert np.array_equal(seen[r][1][k], inputs[k][r]), (r, k)
    ptrs = [p for r in range(RANKS) for p in seen[r][2]]
    assert len(set(ptrs)) == len(ptrs)      # every array its own buffer


@pytest.fixture
def traced(monkeypatch, device_path):
    monkeypatch.setenv("MV2T_TRACE", "1")
    get_config().reload()
    yield       # device_path's teardown undoes and reloads


# (channel, collective, buffers) -> parts on rank 0's dev_collect E,
# relaid on every rank's dev_deliver E. The slot rows read 0 and 0
# since ISSUE 33 (the program's own outputs, flat); what is left is the
# fold channel's eager slice a rank
EAGER = [("slot", "alltoall", "device", 0, 0),
         ("slot", "alltoall", "host", 0, 0),
         ("slot", "reduce_scatter_block", "device", 0, 0),
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

    run_ranks(ranks, app, device_mesh=_mesh(ndev))

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
        assert all(set(a) == {"seq", "coll", "ctx"} for _t, _l, nam, ph, a
                   in lanes[rank]
                   if ph == "B" and nam in ("dev_collect", "dev_deliver"))
