"""Point-to-point on device buffers (the device lane of
pt2pt/protocol.py): send / recv / isend / irecv / sendrecv of jax.Arrays
between thread-ranks, held bit for bit to the plain references of
tests/plain_reference.py (``deliver``: MPI's matching rule by a list;
``sendrecv``), with the lane's pvars, spans, ownership guarantee and
fallbacks. Host buffers keep their path (tests/test_pt2pt.py)."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import plain_reference as ref
from mvapich2_tpu import mpit, run_ranks
from mvapich2_tpu.core import datatype as dt
from mvapich2_tpu.core.errors import MPIException, MPI_ERR_TRUNCATE
from mvapich2_tpu.core.status import ANY_SOURCE, ANY_TAG, PROC_NULL
from mvapich2_tpu.parallel.mesh import make_mesh
from mvapich2_tpu.utils.config import get_config

LANE = ("dev_pt2pt_send", "dev_pt2pt_recv", "dev_pt2pt_bytes",
        "dev_pt2pt_unexpected", "dev_pt2pt_d2d", "dev_pt2pt_fallback_host")
DTYPES = ["float32", "bfloat16", "int32"]
# how the ranks are bound: all on one device (the slot channel, the
# benchmark's cell), or one device each (the 1:1 mesh channel)
BINDINGS = ["one_device", "device_each"]
N = 1000


def bound(nranks, binding):
    ndev = 1 if binding == "one_device" else nranks
    return make_mesh((ndev,), ("x",), jax.devices()[:ndev])


def run(nranks, app, binding="one_device", **kw):
    return run_ranks(nranks, app, device_mesh=bound(nranks, binding), **kw)


def plane(seed, rank, n=N, dtype="float32"):
    """Seeded whole numbers that every dtype here holds exactly."""
    rng = np.random.default_rng([seed, rank])
    return rng.integers(-100, 100, size=n).astype(jnp.dtype(dtype))


def on_device(comm, host):
    return jax.device_put(host, comm.device_channel.device)


def same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype
    bits = np.dtype(f"u{got.dtype.itemsize}")
    return bool(np.array_equal(got.view(bits), want.view(bits)))


def reads():
    return {n: int(mpit.pvar(n).read()) for n in LANE}


def rose(before):
    now = reads()
    return {n: now[n] - before[n] for n in LANE if now[n] != before[n]}


def until_there(comm, source, tag):
    """Poll until the message lies in the unexpected queue."""
    while comm.iprobe(source, tag) is None:
        pass


# ---------------------------------------------------------------------------
# send / recv, and what comes out
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("binding", BINDINGS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_send_recv(dtype, binding):
    inputs = [plane(1, r, dtype=dtype) for r in range(2)]
    want = ref.deliver([(0, 1, 7, inputs[0])], [(1, 0, 7)])

    def app(comm):
        x = on_device(comm, inputs[comm.rank])
        if comm.rank == 0:
            assert comm.send(x, 1, 7) is None
            return None
        got = comm.recv(x, 0, 7)        # x describes the receive
        assert got.devices() == {comm.device_channel.device}
        assert got is not x and same_bits(x, inputs[1])
        return np.asarray(got)
    before = reads()
    out = run(2, app, binding)
    assert same_bits(out[1], want[0][2])
    assert rose(before) == {
        "dev_pt2pt_send": 1, "dev_pt2pt_recv": 1,
        "dev_pt2pt_bytes": inputs[0].nbytes,
        **({"dev_pt2pt_d2d": 1} if binding == "device_each" else {})}


@pytest.mark.parametrize("binding", BINDINGS)
def test_isend_irecv_waitall_and_the_requests_array(binding):
    from mvapich2_tpu.core.request import waitall
    inputs = [plane(2, r) for r in range(2)]

    def app(comm):
        x = on_device(comm, inputs[comm.rank])
        other = 1 - comm.rank
        rreq = comm.irecv(x, other, 3)
        assert rreq.array is None or rreq.complete_flag
        sreq = comm.isend(x, other, 3)
        stats = waitall([rreq, sreq])
        assert stats[0].source == other and stats[0].tag == 3
        assert stats[0].count == x.nbytes
        assert sreq.array is None
        assert rreq.array.devices() == {comm.device_channel.device}
        return np.asarray(rreq.array)
    out = run(2, app, binding)
    want = ref.sendrecv(inputs, [(1, 1), (0, 0)])
    assert all(same_bits(o, w) for o, w in zip(out, want))


@pytest.mark.parametrize("binding", BINDINGS)
def test_sendrecv_exchange(binding):
    """The benchmark's call: the plane itself describes the receive."""
    inputs = [plane(3, r, n=4096) for r in range(2)]

    def app(comm):
        x = on_device(comm, inputs[comm.rank])
        other = 1 - comm.rank
        got = comm.sendrecv(x, other, 0, x, other, 0)
        assert got.devices() == {comm.device_channel.device}
        return np.asarray(got)
    before = reads()
    out = run(2, app, binding)
    want = ref.sendrecv(inputs, [(1, 1), (0, 0)])
    assert all(same_bits(o, w) for o, w in zip(out, want))
    up = rose(before)
    assert up["dev_pt2pt_send"] == 2 and up["dev_pt2pt_recv"] == 2
    assert "dev_pt2pt_fallback_host" not in up


@pytest.mark.parametrize("nranks,binding", [(2, "one_device"),
                                            (3, "one_device"),
                                            (4, "device_each"),
                                            (8, "one_device"),
                                            (8, "device_each")])
def test_sendrecv_ring_shift(nranks, binding):
    inputs = [plane(4, r) for r in range(nranks)]
    pairs = [((r + 1) % nranks, (r - 1) % nranks) for r in range(nranks)]

    def app(comm):
        x = on_device(comm, inputs[comm.rank])
        dest, source = pairs[comm.rank]
        got = comm.sendrecv(x, dest, 11, x, source, 11)
        assert got.devices() == {comm.device_channel.device}
        return np.asarray(got)
    out = run(nranks, app, binding)
    assert all(same_bits(o, w)
               for o, w in zip(out, ref.sendrecv(inputs, pairs)))


@pytest.mark.parametrize("first", ["receive", "message"])
def test_receive_posted_first_and_message_first(first):
    inputs = [plane(5, r) for r in range(2)]
    posted, sent = threading.Event(), threading.Event()

    def app(comm):
        x = on_device(comm, inputs[comm.rank])
        if comm.rank == 0:
            if first == "receive":
                assert posted.wait(60)
            comm.send(x, 1, 9)
            sent.set()
            return None
        if first == "message":
            assert sent.wait(60)
            until_there(comm, 0, 9)
        req = comm.irecv(x, 0, 9)
        posted.set()
        req.wait()
        return np.asarray(req.array)
    before = reads()
    out = run(2, app)
    assert same_bits(out[1], inputs[0])
    assert rose(before).get("dev_pt2pt_unexpected", 0) == \
        (1 if first == "message" else 0)


def test_any_source_any_tag_with_status():
    inputs = [plane(6, r) for r in range(3)]

    def app(comm):
        x = on_device(comm, inputs[comm.rank])
        if comm.rank:
            comm.send(x, 0, 20 + comm.rank)
            return None
        got = {}
        for _ in range(2):
            req = comm.irecv(x, ANY_SOURCE, ANY_TAG)
            st = req.wait()
            assert st.tag == 20 + st.source and st.count == x.nbytes
            got[st.source] = np.asarray(req.array)
        return got
    out = run(3, app)[0]
    assert sorted(out) == [1, 2]
    assert all(same_bits(out[r], inputs[r]) for r in (1, 2))


def test_eight_device_messages_on_one_tag_do_not_overtake():
    msgs = [plane(7, k, n=64 * (k + 1)) for k in range(8)]   # sizes differ
    want = ref.deliver([(0, 1, 5, m) for m in msgs], [(1, 0, 5)] * 8)

    def app(comm):
        if comm.rank == 0:
            reqs = [comm.isend(on_device(comm, m), 1, 5) for m in msgs]
            for r in reqs:
                r.wait()
            return None
        like = on_device(comm, np.zeros(64 * 8, np.float32))
        return [np.asarray(comm.recv(like, 0, 5)) for _ in range(8)]
    out = run(2, app)[1]
    assert all(same_bits(o, w[2]) for o, w in zip(out, want))
    assert [o.shape for o in out] == [(64 * (k + 1),) for k in range(8)]


def test_tags_out_of_order():
    msgs = {t: plane(8, t) for t in (1, 2, 3)}
    order = [3, 1, 2]
    want = ref.deliver([(0, 1, t, msgs[t]) for t in (1, 2, 3)],
                       [(1, 0, t) for t in order])

    def app(comm):
        if comm.rank == 0:
            for t in (1, 2, 3):
                comm.send(on_device(comm, msgs[t]), 1, t)
            return None
        like = on_device(comm, msgs[1])
        return [np.asarray(comm.recv(like, 0, t)) for t in order]
    out = run(2, app)[1]
    assert all(same_bits(o, w[2]) for o, w in zip(out, want))


@pytest.mark.parametrize("how", ["probe", "iprobe", "improbe_mrecv"])
def test_probing_a_device_message(how):
    inputs = [plane(9, r, n=300) for r in range(2)]

    def app(comm):
        x = on_device(comm, inputs[comm.rank])
        if comm.rank == 0:
            comm.send(x, 1, 13)
            return None
        if how == "probe":
            st = comm.probe(ANY_SOURCE, ANY_TAG)
        elif how == "iprobe":
            while (st := comm.iprobe(0, 13)) is None:
                pass
        else:
            while (msg := comm.improbe(0, 13)) is None:
                pass
            assert comm.iprobe(0, 13) is None       # taken off the queue
            return np.asarray(comm.mrecv(msg, x))
        assert (st.source, st.tag, st.count) == (0, 13, 1200)
        return np.asarray(comm.recv(x, st.source, st.tag))
    assert same_bits(run(2, app)[1], inputs[0])


def test_ssend_completes_only_on_a_match():
    inputs = [plane(10, r) for r in range(2)]
    looked = threading.Event()

    def app(comm):
        x = on_device(comm, inputs[comm.rank])
        if comm.rank == 0:
            req = comm.issend(x, 1, 4)
            for _ in range(50):
                assert not req.test()       # nobody has matched it
            looked.set()
            req.wait()
            comm.ssend(x, 1, 5)
            return None
        assert looked.wait(60)
        a = comm.recv(x, 0, 4)
        b = comm.recv(x, 0, 5)
        return np.asarray(a), np.asarray(b)
    a, b = run(2, app)[1]
    assert same_bits(a, inputs[0]) and same_bits(b, inputs[0])


@pytest.mark.parametrize("first", ["receive", "message"])
def test_truncation(first):
    def app(comm):
        if comm.rank == 0:
            comm.send(on_device(comm, plane(11, 0, n=100)), 1, 2)
            return None
        small = on_device(comm, np.zeros(10, np.float32))
        if first == "message":
            until_there(comm, 0, 2)
        with pytest.raises(MPIException) as e:
            comm.recv(small, 0, 2)
        assert e.value.error_class == MPI_ERR_TRUNCATE
        return True
    assert run(2, app)[1]


def test_proc_null():
    def app(comm):
        x = on_device(comm, plane(12, comm.rank))
        comm.send(x, PROC_NULL, 1)
        assert comm.isend(x, PROC_NULL, 1).wait() is not None
        assert comm.recv(x, PROC_NULL, 1) is None
        req = comm.irecv(x, PROC_NULL, 1)
        st = req.wait()
        assert st.source == PROC_NULL and req.array is None
        assert comm.sendrecv(x, PROC_NULL, 0, x, PROC_NULL, 0) is None
        return True
    before = reads()
    assert all(run(2, app))
    assert rose(before) == {}


@pytest.mark.parametrize("case", ["same_shape", "flat_into_2d",
                                  "fewer_elements"])
def test_shape_of_what_comes_out(case):
    """Shaped as the receive buffer where the count fills it, else flat."""
    sent = plane(13, 0, n=48)
    send_shape = {"same_shape": (6, 8), "flat_into_2d": (48,),
                  "fewer_elements": (4, 12)}[case]
    like_shape = {"same_shape": (6, 8), "flat_into_2d": (6, 8),
                  "fewer_elements": (8, 8)}[case]
    want_shape = {"same_shape": (6, 8), "flat_into_2d": (6, 8),
                  "fewer_elements": (48,)}[case]

    def app(comm):
        if comm.rank == 0:
            comm.send(on_device(comm, sent.reshape(send_shape)), 1, 0)
            return None
        req = comm.irecv(on_device(comm, np.zeros(like_shape, np.float32)),
                         0, 0)
        assert req.wait().count == sent.nbytes
        return np.asarray(req.array)
    out = run(2, app)[1]
    assert out.shape == want_shape
    assert same_bits(out.reshape(-1), sent)


# ---------------------------------------------------------------------------
# mixed ends and fallbacks: correct, and counted
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [N, 1 << 18])      # eager, rendezvous
@pytest.mark.parametrize("ends", ["device_to_host", "host_to_device"])
def test_mixed_ends(ends, n):
    inputs = [plane(14, r, n=n) for r in range(2)]

    def app(comm):
        if comm.rank == 0:
            x = inputs[0] if ends == "host_to_device" \
                else on_device(comm, inputs[0])
            comm.send(x, 1, 6)
            return None
        if ends == "device_to_host":
            buf = np.zeros(n, np.float32)
            st = comm.recv(buf, 0, 6)
            assert (st.source, st.tag, st.count) == (0, 6, 4 * n)
            return buf
        got = comm.recv(on_device(comm, inputs[1]), 0, 6)
        assert got.devices() == {comm.device_channel.device}
        return np.asarray(got)
    before = reads()
    assert same_bits(run(2, app)[1], inputs[0])
    # one read-back at the receiver, or one staged upload: counted once
    assert rose(before) == {
        "dev_pt2pt_fallback_host": 1,
        **({"dev_pt2pt_send": 1} if ends == "device_to_host" else {})}


@pytest.mark.parametrize("case", ["partial_send", "partial_recv",
                                  "derived_send"])
def test_a_device_buffer_not_given_whole_takes_the_host_path(case):
    inputs = [plane(15, r, n=64) for r in range(2)]
    every_other = dt.create_vector(16, 1, 2, dt.FLOAT).commit()

    def app(comm):
        x = on_device(comm, inputs[comm.rank])
        if comm.rank == 0:
            if case == "partial_send":
                comm.send(x, 1, 1, count=16)
            elif case == "derived_send":
                comm.send(x, 1, 1, count=1, datatype=every_other)
            else:
                comm.send(x[:16], 1, 1)
            return None
        if case == "partial_recv":
            # the read-back of the description is received into
            return np.asarray(comm.recv(x, 0, 1, count=16))
        buf = np.zeros(16, np.float32)
        comm.recv(buf, 0, 1)
        return buf
    before = reads()
    out = run(2, app)[1]
    if case == "partial_send":
        assert same_bits(out, inputs[0][:16])
    elif case == "derived_send":
        assert same_bits(out, inputs[0][:32:2])
    else:
        assert out.shape == (64,)
        assert same_bits(out[:16], inputs[0][:16])
        assert same_bits(out[16:], inputs[1][16:])
    up = rose(before)
    assert up["dev_pt2pt_fallback_host"] >= 1
    assert "dev_pt2pt_recv" not in up


def test_host_buffers_do_not_touch_the_lane():
    def app(comm):
        other = 1 - comm.rank
        got = np.zeros(N, np.float32)
        st = comm.sendrecv(plane(16, comm.rank), other, 0, got, other, 0)
        assert st.source == other
        return got
    before = reads()
    out = run_ranks(2, app)
    assert same_bits(out[0], plane(16, 1)) and rose(before) == {}


def test_ranks_without_a_device_binding():
    """No device_mesh: the lane still carries the array; it lies where
    the sender's did."""
    inputs = [plane(17, r) for r in range(2)]

    def app(comm):
        assert comm.device_channel is None
        x = jax.device_put(inputs[comm.rank], jax.devices()[3])
        other = 1 - comm.rank
        got = comm.sendrecv(x, other, 0, x, other, 0)
        assert got.devices() == {jax.devices()[3]}
        return np.asarray(got)
    before = reads()
    out = run_ranks(2, app)
    assert same_bits(out[0], inputs[1]) and same_bits(out[1], inputs[0])
    assert rose(before) == {"dev_pt2pt_send": 2, "dev_pt2pt_recv": 2,
                            "dev_pt2pt_bytes": 2 * 4 * N}


def test_ranks_on_different_devices():
    inputs = [plane(18, r) for r in range(4)]

    def app(comm):
        dev = comm.device_channel.device
        assert dev == jax.devices()[comm.rank]
        x = on_device(comm, inputs[comm.rank])
        peer = comm.rank ^ 1
        got = comm.sendrecv(x, peer, 0, x, peer, 0)
        assert got.devices() == {dev}
        return np.asarray(got)
    before = reads()
    out = run(4, app, "device_each")
    assert all(same_bits(out[r], inputs[r ^ 1]) for r in range(4))
    up = rose(before)
    assert up["dev_pt2pt_d2d"] == 4 and up["dev_pt2pt_send"] == 4
    assert "dev_pt2pt_fallback_host" not in up


# ---------------------------------------------------------------------------
# the guarantee: the received array is the receiver's own
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("binding", BINDINGS)
@pytest.mark.parametrize("call,posted", [
    ("send", "before"), ("send", "after"), ("isend_wait", "before"),
    ("isend_wait", "after"),
    ("ssend", "before")])   # a synchronous send completes only on a match
def test_the_sender_deletes_its_array_after_the_send(call, posted, binding):
    inputs = [plane(19, r, n=1 << 16) for r in range(2)]
    is_posted, deleted = threading.Event(), threading.Event()

    def app(comm):
        x = on_device(comm, inputs[comm.rank])
        if comm.rank == 0:
            if posted == "before":
                assert is_posted.wait(60)
            if call == "send":
                comm.send(x, 1, 8)
            elif call == "ssend":
                comm.ssend(x, 1, 8)
            else:
                comm.isend(x, 1, 8).wait()
            x.delete()                  # what a donating jit would do
            assert x.is_deleted()
            deleted.set()
            return None
        if posted == "after":
            assert deleted.wait(60)
            got = comm.recv(x, 0, 8)
        else:
            req = comm.irecv(x, 0, 8)
            is_posted.set()
            req.wait()
            assert deleted.wait(60)
            got = req.array
        # valid, and usable by the receiver's next step
        assert not got.is_deleted()
        return np.asarray(got), np.asarray(got + 1)
    got, plus = run(2, app, binding)[1]
    assert same_bits(got, inputs[0]) and same_bits(plus, inputs[0] + 1)


def test_the_receivers_array_is_no_alias_of_the_senders():
    def app(comm):
        x = on_device(comm, plane(20, comm.rank))
        other = 1 - comm.rank
        got = comm.sendrecv(x, other, 0, x, other, 0)
        jax.block_until_ready(got)
        return x.unsafe_buffer_pointer(), got.unsafe_buffer_pointer()
    (x0, g0), (x1, g1) = run(2, app)
    assert len({x0, g0, x1, g1}) == 4


# ---------------------------------------------------------------------------
# requests: cancel, persistent, replace
# ---------------------------------------------------------------------------

def test_cancel_of_a_posted_device_receive():
    def app(comm):
        x = on_device(comm, plane(21, comm.rank))
        req = comm.irecv(x, 1 - comm.rank, 99)
        req.cancel()
        st = req.wait()
        assert st.cancelled and req.array is None
        comm.barrier()
        return True
    assert all(run(2, app))


def test_persistent_receive_and_sendrecv_replace():
    inputs = [plane(22, r) for r in range(2)]

    def app(comm):
        x = on_device(comm, inputs[comm.rank])
        other = 1 - comm.rank
        rreq = comm.recv_init(x, other, 1)
        sreq = comm.send_init(x, other, 1)
        outs = []
        for _ in range(2):
            rreq.start()
            sreq.start()
            rreq.wait()
            sreq.wait()
            outs.append(np.asarray(rreq.array))
        outs.append(np.asarray(comm.sendrecv_replace(x, other, 2, other, 2)))
        return outs
    out = run(2, app)
    assert all(same_bits(o, inputs[1]) for o in out[0])
    assert all(same_bits(o, inputs[0]) for o in out[1])


# ---------------------------------------------------------------------------
# spans, and nothing compiled for the second message of a shape
# ---------------------------------------------------------------------------

@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setenv("MV2T_TRACE", "1")
    get_config().reload()
    yield
    monkeypatch.undo()
    get_config().reload()


@pytest.mark.parametrize("binding", BINDINGS)
def test_the_three_spans_and_their_args(traced, binding):
    inputs = [plane(23, r) for r in range(2)]

    def app(comm):
        x = on_device(comm, inputs[comm.rank])
        other = 1 - comm.rank
        for _ in range(2):
            comm.sendrecv(x, other, 4, x, other, 4)
        comm.send(x, other, 5) if comm.rank == 0 else comm.recv(x, other, 5)
        return list(comm.u.engine.tracer.events)
    events = run(2, app, binding)
    nbytes = 4 * N
    for rank, evs in enumerate(events):
        dev = [(nam, ph, args) for _t, lay, nam, ph, args in evs
               if lay == "device"]
        sends = [a for nam, ph, a in dev if (nam, ph) == ("dev_send", "B")]
        assert sends[:2] == [
            {"dest": 1 - rank, "tag": 4, "bytes": nbytes, "seq": s}
            for s in (1, 2)]
        copies = [a for nam, ph, a in dev if (nam, ph) == ("dev_p2p_copy", "B")]
        assert copies and all(
            a == {"bytes": nbytes, "d2d": binding == "device_each"}
            for a in copies)
        posted = [a for nam, ph, a in dev if (nam, ph) == ("dev_recv", "B")]
        assert posted[:2] == [{"source": 1 - rank, "tag": 4,
                               "capacity": nbytes}] * 2
        done = [a for nam, ph, a in dev if (nam, ph) == ("dev_recv", "E")]
        assert [(a["source"], a["tag"], a["bytes"], a["seq"])
                for a in done[:2]] == [(1 - rank, 4, nbytes, s)
                                       for s in (1, 2)]
        assert all(isinstance(a["unexpected"], bool) for a in done)
        # every span closes, in its own rank's recorder
        for name in ("dev_send", "dev_recv", "dev_p2p_copy"):
            assert sum(ph == "B" for nam, ph, _a in dev if nam == name) == \
                sum(ph == "E" for nam, ph, _a in dev if nam == name) > 0
        # the entry points' own spans come from profile.py's interceptor
        mpi = {nam for _t, lay, nam, _ph, _a in evs if lay == "mpi"}
        assert {"sendrecv", "isend", "irecv"} <= mpi
        assert ("send" if rank == 0 else "recv") in mpi
    # the two ends join by (src, dst, tag, seq)
    for src in (0, 1):
        sent = {(a["dest"], a["tag"], a["seq"])
                for _t, lay, nam, ph, a in events[src]
                if (lay, nam, ph) == ("device", "dev_send", "E")}
        got = {(1 - src, a["tag"], a["seq"])
               for _t, lay, nam, ph, a in events[1 - src]
               if (lay, nam, ph) == ("device", "dev_recv", "E")}
        assert sent == got and len(sent) >= 2


def test_the_lanes_spans_are_in_the_conformance_grammar(traced):
    from mvapich2_tpu.analysis import conform

    def app(comm):
        x = on_device(comm, plane(24, comm.rank))
        other = 1 - comm.rank
        comm.sendrecv(x, other, 0, x, other, 0)
        req = comm.irecv(x, other, 77)
        req.cancel()
        return [conform.Event(t, comm.rank, lay, nam, ph, a)
                for t, lay, nam, ph, a in comm.u.engine.tracer.events]
    events = [e for evs in run(2, app) for e in evs]
    assert {e.name for e in events if e.layer == "device"} >= \
        {"dev_send", "dev_recv", "dev_p2p_copy"}
    assert conform.check_events(events) == []


def compilations():
    """Backend compilations jax has reported since the counter went in
    (the benchmark's own: one listener for the life of the process)."""
    from chipbench.harness import COMPILE_EVENT, CompileCounter
    return CompileCounter.installed().counts().get(COMPILE_EVENT, 0)


@pytest.mark.parametrize("binding", BINDINGS)
def test_the_second_message_of_a_shape_compiles_nothing(binding):
    inputs = [plane(25, r, n=3210) for r in range(2)]     # a fresh shape
    counts = {}

    def app(comm):
        x = on_device(comm, inputs[comm.rank])
        other = 1 - comm.rank
        jax.block_until_ready(comm.sendrecv(x, other, 0, x, other, 0))
        comm.barrier()
        if comm.rank == 0:
            counts["warm"] = compilations()
        comm.barrier()
        for _ in range(5):
            got = jax.block_until_ready(comm.sendrecv(x, other, 0, x,
                                                      other, 0))
        comm.barrier()
        if comm.rank == 0:
            counts["after"] = compilations()
        return np.asarray(got)
    compilations()
    out = run(2, app, binding)
    assert counts["after"] == counts["warm"]
    assert same_bits(out[0], inputs[1])
