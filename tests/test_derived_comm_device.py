"""Device collectives on derived communicators (ISSUE 55): a
communicator made by ``dup``, ``split``, ``create``, ``cart_create`` /
``cart_sub`` from a device-bound one is device-bound. Its six blocking
collectives on device arrays return ``jax.Array``s on the rank's own
device, bit-equal to the plain numpy reference's derived forms
(tests/plain_reference.py ``on_groups``): for every group of two or
more ranks on the slot binding (eight ranks, one device), for groups
that are the parent's in order on the 1:1 mesh and fold bindings. Every
other case takes the host arm, answers right and is counted
(``dev_coll_fallback_host_comm``). One rendezvous a communicator, found
by its members under (context id, world ranks), gone with the last
``free``; a dying rank releases its row-mates and nobody else.

The Pallas interpreter the CPU runs the slot sum under keeps one shared
memory a process, so two groups' *reductions* are run one after the
other here (a world barrier between them); what only moves data runs in
every group at once. On a chip nothing is interpreted
(``chip_smoke.py``'s ``derived_comms`` step runs them all at once).
"""

import functools
import threading

import jax
import numpy as np
import pytest

import plain_reference as ref
from mvapich2_tpu import mpit
from mvapich2_tpu.parallel.mesh import make_mesh
from mvapich2_tpu.runtime.universe import run_ranks
from mvapich2_tpu.utils.config import get_config

RANKS = 8
N = 1024                # float32 a rank: whole blocks for groups of 2, 4, 8
CALLS = 2
ROOT = 1                # of reduce and bcast, within every group
ROWS = [[0, 1, 2, 3], [4, 5, 6, 7]]
COLUMNS = [[0, 4], [1, 5], [2, 6], [3, 7]]


def _mesh(ndev):
    return make_mesh((ndev,), ("x",), jax.devices()[:ndev])


def _data(seed, rank, n=N):
    """chipbench's values: whole numbers in +-2^20, other on every rank."""
    rng = np.random.default_rng([seed, rank])
    return rng.integers(-2 ** 20, 2 ** 20, size=n,
                        endpoint=True).astype(np.float32)


def _reads(*names):
    fb = [mpit.pvar_get_info(i)["name"] for i in range(mpit.pvar_get_num())]
    names += tuple(n for n in fb if n.startswith("dev_coll_fallback_"))
    return {n: mpit.pvar(n).read() for n in names}


def _rose(before):
    return {n: mpit.pvar(n).read() - v for n, v in before.items()}


def _rows(comm):
    return comm.split(comm.rank // 4, comm.rank % 4)


def _cart_rows(comm, made):
    made.append(comm.cart_create([2, 4]))
    return made[-1].cart_sub([False, True])


def _pairs_of_rows(comm, made):
    """A derived of a derived, and turned round: each row split again
    into pairs, the higher world rank first."""
    made.append(_rows(comm))
    return made[-1].split(made[-1].rank // 2, -made[-1].rank)


# derivation -> (what a rank calls on the world (``made`` takes a
# communicator made on the way, to be freed too), the partition it
# makes: each group's world ranks in the new communicator's rank order)
DERIVATIONS = {
    "dup": (lambda c, made: c.dup(), [list(range(RANKS))]),
    "split_one_colour_in_order": (lambda c, made: c.split(0, c.rank),
                                  [list(range(RANKS))]),
    "split_one_colour_reversed": (lambda c, made: c.split(0, -c.rank),
                                  [list(range(RANKS))[::-1]]),
    "split_rows": (lambda c, made: _rows(c), ROWS),
    "split_columns": (lambda c, made: c.split(c.rank % 4, c.rank // 4),
                      COLUMNS),
    "cart_create_cart_sub": (_cart_rows, ROWS),
    "create_over_a_subset": (
        lambda c, made: c.create(c.group.incl([1, 3, 4, 6])), [[1, 3, 4, 6]]),
    "derived_of_derived": (_pairs_of_rows,
                           [[1, 0], [3, 2], [5, 4], [7, 6]]),
}

# collective -> (the call, its plain reference over a group's inputs,
# whether the slot program computes: a Pallas kernel under the interpreter)
COLLECTIVES = {
    "allreduce": (lambda c, x: c.allreduce(x), ref.allreduce, True),
    "reduce": (lambda c, x: c.reduce(x, root=ROOT),
               lambda xs: ref.reduce(xs, ROOT), True),
    "bcast": (lambda c, x: c.bcast(x, root=ROOT),
              lambda xs: ref.bcast(xs, ROOT), False),
    "allgather": (lambda c, x: c.allgather(x), ref.allgather, False),
    "alltoall": (lambda c, x: c.alltoall(x), ref.alltoall, False),
    "reduce_scatter_block": (lambda c, x: c.reduce_scatter_block(x),
                             ref.reduce_scatter_block, True),
}
COUNTED = ("coll_level_chip", "dev_coll_derived")


@functools.lru_cache(maxsize=None)
def _ran(derivation):
    """One run a derivation: eight ranks on one device derive the
    communicator and call every collective ``CALLS`` times on it. Keeps,
    per collective, every rank's results and what the pvars rose by."""
    make, groups = DERIVATIONS[derivation]
    mine = {w: gi for gi, g in enumerate(groups) for w in g}
    seed = sum(map(ord, derivation))
    data = [_data(seed, r) for r in range(RANKS)]
    results = {c: [None] * RANKS for c in COLLECTIVES}
    rose, bound = {}, [None] * RANKS

    def app(comm):
        dev = comm.device_channel.device
        x = jax.device_put(data[comm.rank], dev)
        made = []
        sub = make(comm, made)
        assert (sub is None) == (comm.rank not in mine)
        if sub is not None:
            ch = sub.device_channel
            bound[comm.rank] = (type(ch).__name__, ch.derived, sub.rank,
                                sub.size, ch.device == dev)
        for coll, (call, _ref, computes) in COLLECTIVES.items():
            comm.barrier()
            if comm.rank == 0:
                before = _reads(*COUNTED)
            comm.barrier()
            # every group at once, but for the interpreted reductions
            for turn in (range(len(groups)) if computes else [None]):
                if sub is not None and turn in (None, mine[comm.rank]):
                    outs = [call(sub, x) for _ in range(CALLS)]
                    results[coll][comm.rank] = [
                        None if o is None else
                        (np.asarray(jax.block_until_ready(o)),
                         isinstance(o, jax.Array) and o.devices() == {dev})
                        for o in outs]
                comm.barrier()
            if comm.rank == 0:
                rose[coll] = _rose(before)
        for c in [sub] + made:
            if c is not None:
                c.free()
        comm.barrier()
        if comm.rank == 0:
            rose["live after free"] = len(comm.device_channel.rv.live())

    run_ranks(RANKS, app, device_mesh=_mesh(1))
    return data, results, rose, bound


@pytest.mark.parametrize("coll", list(COLLECTIVES))
@pytest.mark.parametrize("derivation", list(DERIVATIONS))
def test_a_derived_communicators_collective_is_the_plain_reference(
        derivation, coll):
    data, results, rose, bound = _ran(derivation)
    groups = DERIVATIONS[derivation][1]
    want = ref.on_groups(COLLECTIVES[coll][1], data, groups)
    members = sum(len(g) for g in groups)
    for w in range(RANKS):
        got = results[coll][w]
        if not any(w in g for g in groups):
            assert got is None and bound[w] is None     # not a member
            continue
        # bound to a slot channel of the group's own, on the rank's device
        group = next(g for g in groups if w in g)
        assert bound[w] == ("HBMSlotChannel", True, group.index(w),
                            len(group), True)
        assert len(got) == CALLS
        for call in got:
            if want[w] is None:         # reduce, off the root
                assert call is None
                continue
            arr, on_device = call
            assert on_device, (derivation, coll, w)
            assert arr.dtype == want[w].dtype and arr.shape == want[w].shape
            assert np.array_equal(arr, want[w]), (derivation, coll, w)
    # the device path on the derived channel, a rank a call, no fallback
    r = dict(rose[coll])
    assert r.pop("coll_level_chip") == members * CALLS
    assert r.pop("dev_coll_derived") == members * CALLS
    assert r and not any(r.values()), r
    assert rose["live after free"] == 0


def test_the_reference_of_a_partition_is_the_worlds_on_each_group():
    """``on_groups`` by hand: two rows' alltoall, a reversed group's
    gather, a root within its group, a rank in no group."""
    xs = [np.arange(4, dtype=np.float32) + 10 * r for r in range(4)]
    got = ref.on_groups(ref.alltoall, xs, [[0, 1], [3, 2]])
    assert [g.tolist() for g in got] == [
        [0, 1, 10, 11], [2, 3, 12, 13], [32, 33, 22, 23], [30, 31, 20, 21]]
    got = ref.on_groups(ref.allgather, xs, [[2, 0]])
    assert got[1] is None and got[3] is None
    assert got[0].tolist() == got[2].tolist() == [20, 21, 22, 23, 0, 1, 2, 3]
    got = ref.on_groups(ref.reduce, xs, [[1, 3]], 1)
    assert got[1] is None and got[3].tolist() == [40, 42, 44, 46]


def test_the_colours_of_one_split_share_a_context_id_and_nothing_else():
    """The two rows of one split hold one context id and a rendezvous
    each; they run different numbers of calls at once, on data that
    changes every call, and neither sees the other's."""
    calls = {0: 3, 1: 9}
    data = [_data(55, r) for r in range(RANKS)]
    got, ctx, keys = [None] * RANKS, [None] * RANKS, []

    def app(comm):
        dev = comm.device_channel.device
        rows = _rows(comm)
        ctx[comm.rank] = rows.context_id
        comm.barrier()
        if comm.rank == 0:
            keys.extend(rv.key for rv in comm.device_channel.rv.live())
        outs = []
        for i in range(calls[comm.rank // 4]):
            x = jax.device_put(data[comm.rank] + i, dev)
            outs.append(np.asarray(rows.alltoall(x)))
        got[comm.rank] = outs
        comm.barrier()

    run_ranks(RANKS, app, device_mesh=_mesh(1))
    assert len(set(ctx)) == 1
    assert sorted(keys) == [(ctx[0], tuple(g)) for g in ROWS]
    for w in range(RANKS):
        assert len(got[w]) == calls[w // 4]
        for i, arr in enumerate(got[w]):
            want = ref.on_groups(ref.alltoall, [d + i for d in data], ROWS)
            assert np.array_equal(arr, want[w]), (w, i)


def test_world_and_derived_calls_interleave_on_one_rank():
    """Three channels on every rank, each counting its own calls from
    1: the world's, a dup's, a row's."""
    data = [_data(56, r) for r in range(RANKS)]
    got, seqs = [None] * RANKS, [None] * RANKS

    def app(comm):
        dev = comm.device_channel.device
        x = jax.device_put(data[comm.rank], dev)
        d, rows = comm.dup(), _rows(comm)
        outs = []
        for _ in range(3):
            outs.append((np.asarray(comm.alltoall(x)),
                         np.asarray(d.allgather(x)),
                         np.asarray(rows.alltoall(x)),
                         np.asarray(comm.allgather(x))))
        got[comm.rank] = outs
        seqs[comm.rank] = [c.device_channel._seq for c in (comm, d, rows)]
        assert len({c.device_channel.ctx for c in (comm, d, rows)}) == 3

    run_ranks(RANKS, app, device_mesh=_mesh(1))
    world = [list(range(RANKS))]
    want = (ref.alltoall(data), ref.allgather(data),
            ref.on_groups(ref.alltoall, data, ROWS),
            ref.on_groups(ref.allgather, data, world))
    for w in range(RANKS):
        assert seqs[w] == [6, 3, 3]
        for outs in got[w]:
            for arr, expect in zip(outs, want):
                assert np.array_equal(arr, expect[w])


# -- the 1:1 mesh and the fold binding ---------------------------------------

BINDINGS = {"mesh": (4, "DeviceCollChannel"), "fold": (8, "DeviceFoldChannel")}


@pytest.fixture
def interpreted(monkeypatch):
    """Four virtual devices, the ring kernels under the TPU interpreter,
    the streaming tier at these sizes (as tests/test_fold_device.py)."""
    monkeypatch.setenv("MV2T_DEVICE_COLL_MIN_BYTES", "1")
    monkeypatch.setenv("MV2T_ICI_INTERPRET", "1")
    monkeypatch.setenv("MV2T_DEV_TIER_VMEM_MAX", "8192")
    monkeypatch.setenv("MV2T_DEV_TIER_XLA_MIN", "-1")
    get_config().reload()
    yield
    monkeypatch.undo()
    get_config().reload()


SPANNING = {"dup": lambda c: c.dup(),
            "split_in_order": lambda c: c.split(0, c.rank),
            "cart_create": lambda c: c.cart_create([c.size])}


@pytest.mark.parametrize("how", list(SPANNING))
@pytest.mark.parametrize("binding", list(BINDINGS))
def test_a_spanning_communicator_on_the_mesh_runs_the_parents_programs(
        interpreted, binding, how):
    """The parent's whole group in the parent's order: a channel of the
    parent's class over the same mesh, with a rendezvous of its own and
    the parent's programs (the first derived allreduce builds nothing:
    the world's built it). The device path, on the rank's own chip."""
    ranks, klass = BINDINGS[binding]
    data = [_data(57, r, 4096) for r in range(ranks)]
    got, built = [None] * ranks, []

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == klass
        x = jax.device_put(data[comm.rank], ch.device)
        first = np.asarray(comm.allreduce(x))
        d = SPANNING[how](comm)
        dch = d.device_channel
        assert type(dch) is type(ch) and dch.derived and not ch.derived
        assert dch.rv is not ch.rv and dch.rv.root is ch.rv
        assert dch._programs is ch._programs
        assert dch._chan_desc() == ch._chan_desc()
        assert (dch.mesh, dch.axes, dch.device) == (ch.mesh, ch.axes,
                                                    ch.device)
        comm.barrier()
        if comm.rank == 0:
            before = (set(ch._programs), _reads("coll_level_ici",
                                                "dev_coll_derived"))
        comm.barrier()
        out = d.allreduce(x)
        gathered = d.allgather(x)
        assert out.devices() == gathered.devices() == {ch.device}
        got[comm.rank] = (first, np.asarray(out), np.asarray(gathered))
        comm.barrier()
        if comm.rank == 0:
            keys = set(ch._programs) - before[0]
            built.append(({k[0] for k in keys}, _rose(before[1])))
        d.free()

    run_ranks(ranks, app, device_mesh=_mesh(4))
    total, cat = ref.allreduce(data)[0], ref.allgather(data)[0]
    for first, out, gathered in got:
        assert np.array_equal(first, total) and np.array_equal(out, total)
        assert np.array_equal(gathered, cat)
    (new, rose), = built
    assert new == {"allgather"}        # the allreduce ran the world's program
    assert rose.pop("coll_level_ici") == rose.pop("dev_coll_derived") \
        == 2 * ranks
    assert rose and not any(rose.values()), rose


SUBGROUPS = {"pairs": (lambda c: c.split(c.rank // 2, c.rank),
                       lambda n: [[r, r + 1] for r in range(0, n, 2)]),
             "reversed": (lambda c: c.split(0, -c.rank),
                          lambda n: [list(range(n))[::-1]])}


@pytest.mark.parametrize("how", list(SUBGROUPS))
@pytest.mark.parametrize("binding", list(BINDINGS))
def test_another_group_on_the_mesh_takes_the_host_arm_and_is_counted(
        interpreted, binding, how):
    """A ring over some chips of the mesh, or in another order, has no
    program yet: no channel, the host algorithm on the array read back,
    the right answer as numpy, ``dev_coll_fallback_host_comm`` +1 a rank
    a call."""
    ranks, _klass = BINDINGS[binding]
    make, groups = SUBGROUPS[how]
    data = [_data(58, r, 512) for r in range(ranks)]
    got, rose = [None] * ranks, []

    def app(comm):
        x = jax.device_put(data[comm.rank], comm.device_channel.device)
        sub = make(comm)
        assert sub.device_channel is None
        comm.barrier()
        if comm.rank == 0:
            before = _reads("dev_coll_derived", "coll_level_ici")
        comm.barrier()
        outs = [sub.allreduce(x) for _ in range(CALLS)]
        outs.append(sub.alltoall(x))
        assert all(isinstance(o, np.ndarray) for o in outs)
        got[comm.rank] = outs
        comm.barrier()
        if comm.rank == 0:
            rose.append(_rose(before))

    run_ranks(ranks, app, device_mesh=_mesh(4))
    sums = ref.on_groups(ref.allreduce, data, groups(ranks))
    swaps = ref.on_groups(ref.alltoall, data, groups(ranks))
    for w in range(ranks):
        assert all(np.array_equal(o, sums[w]) for o in got[w][:CALLS])
        assert np.array_equal(got[w][CALLS], swaps[w])
    (r,) = rose
    assert r.pop("dev_coll_fallback_host_comm") == ranks * (CALLS + 1)
    assert not any(r.values()), r


# -- the registry --------------------------------------------------------------

def test_free_drops_the_members_reference_and_the_last_one_the_entry():
    seen = {}

    def app(comm):
        root = comm.device_channel.rv
        d, rows = comm.dup(), _rows(comm)
        pairs = rows.split(rows.rank // 2, rows.rank)   # keyed at the root too
        assert pairs.device_channel.rv.root is root
        comm.barrier()
        if comm.rank == 0:
            seen["made"] = sorted(len(rv.key[1]) for rv in root.live())
            seen["mine"] = sorted(len(rv.key[1]) for rv in root.live(0))
        comm.barrier()
        if comm.rank != 3:              # one member holds on
            for c in (pairs, rows, d):
                c.free()
        comm.barrier()
        if comm.rank == 0:
            seen["held by rank 3"] = sorted(rv.key[1] for rv in root.live())
        comm.barrier()
        if comm.rank == 3:
            for c in (pairs, rows, d):
                c.free()
            d.free()                    # a second free is a no-op
        comm.barrier()
        if comm.rank == 0:
            seen["after"] = root.live()
            seen["world"] = comm.device_channel.release()   # a no-op
        # the world's channel works on
        comm.allgather(jax.device_put(np.ones(128, np.float32),
                                      comm.device_channel.device))

    run_ranks(RANKS, app, device_mesh=_mesh(1))
    assert seen["made"] == [2, 2, 2, 2, 4, 4, 8]
    assert seen["mine"] == [2, 4, 8]
    assert seen["held by rank 3"] == [(0, 1, 2, 3), (0, 1, 2, 3, 4, 5, 6, 7),
                                      (2, 3)]
    assert seen["after"] == [] and seen["world"] is None


def test_200_dup_free_rounds_hold_no_rendezvous():
    rounds, held = 200, []
    data = [_data(59, r, 128) for r in range(RANKS)]

    def app(comm):
        dev = comm.device_channel.device
        x = jax.device_put(data[comm.rank], dev)
        for i in range(rounds):
            d = comm.dup()
            if i % 50 == 0:
                assert np.array_equal(np.asarray(d.allgather(x)),
                                      np.concatenate(data))
            d.free()
        comm.barrier()
        if comm.rank == 0:
            held.append(len(comm.device_channel.rv.live()))

    run_ranks(RANKS, app, device_mesh=_mesh(1), timeout=300.0)
    assert held == [0]


# -- failure ---------------------------------------------------------------------

def test_a_dying_rank_releases_its_row_mates_and_the_other_row_finishes():
    """Rank 1 raises in front of its row's alltoall: ranks 0, 2 and 3
    raise the abort error as on the world's channel, inside the timeout;
    the other row's collective completes."""
    data = [_data(60, r) for r in range(RANKS)]
    outcome = [None] * RANKS
    go = threading.Event()

    def app(comm):
        dev = comm.device_channel.device
        x = jax.device_put(data[comm.rank], dev)
        rows = _rows(comm)
        comm.barrier()
        if comm.rank == 1:
            go.wait(10.0)               # the row-mates are at the gate
            raise ValueError("rank 1 is gone")
        try:
            if comm.rank == 0:
                go.set()
            out = np.asarray(rows.alltoall(x))
            outcome[comm.rank] = out
        except RuntimeError as e:
            outcome[comm.rank] = str(e)

    with pytest.raises(RuntimeError, match="rank 1 failed"):
        run_ranks(RANKS, app, device_mesh=_mesh(1), timeout=60.0)
    for w in (0, 2, 3):
        assert outcome[w] == "device collective aborted: a peer rank failed"
    want = ref.on_groups(ref.alltoall, data, ROWS)
    for w in ROWS[1]:
        assert np.array_equal(outcome[w], want[w])


def test_abort_reaches_the_rendezvous_of_the_dying_rank_alone():
    from mvapich2_tpu.coll.device import _Rendezvous
    root = _Rendezvous(8, last_first=True)
    row0 = root.derive((10, (0, 1, 2, 3)))
    row1 = root.derive((10, (4, 5, 6, 7)))
    assert root.derive((10, (0, 1, 2, 3))) is row0      # found, not made
    assert row0 is not row1 and row0.root is root and row0.size == 4
    assert row0.gate.last_first and row1.gate.last_first    # inherited
    assert root.live(5) == [row1] and len(root.live()) == 2
    assert _Rendezvous(4).derive((3, (0, 1))).gate.last_first is False
    root.release(row0)
    assert root.live(0) == [row0]       # one of its two holders is left
    root.release(row0)
    assert root.live(0) == []
    root.release(row0)                  # late, or of an entry made anew
    again = root.derive((10, (0, 1, 2, 3)))
    root.release(row0)                  # ... does not touch the new one
    assert root.live(0) == [again] and again is not row0


# -- what stays unbound ------------------------------------------------------------

def test_the_two_level_communicators_have_no_channel():
    seen = [None] * RANKS

    def app(comm):
        shmem, leader = comm.build_2level()
        seen[comm.rank] = (shmem.device_channel,
                           leader and leader.device_channel,
                           comm.split(comm.rank // 4, comm.rank)
                           .device_channel is not None)

    run_ranks(RANKS, app, nodes=[0] * 4 + [1] * 4, device_mesh=_mesh(1))
    assert seen == [(None, None, True)] * RANKS


# -- spans -----------------------------------------------------------------------

@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setenv("MV2T_TRACE", "1")
    get_config().reload()
    yield
    monkeypatch.undo()
    get_config().reload()


def test_the_spans_say_which_communicator_and_the_watchdog_who_waits(traced):
    from mvapich2_tpu.trace import watchdog
    lanes, ctxs, dump = {}, {}, []

    def app(comm):
        x = jax.device_put(_data(61, comm.rank), comm.device_channel.device)
        rows = _rows(comm)
        alone = comm.split(comm.rank, 0)        # one rank each: no channel
        comm.alltoall(x)
        rows.alltoall(x)
        ctxs[comm.rank] = (comm.ctx_coll, rows.ctx_coll, alone.ctx_coll)
        if comm.rank == 5:
            dump.extend(watchdog._device_report(comm.u))
        comm.barrier()
        lanes[comm.rank] = [e for e in comm.u.engine.tracer.events
                            if e[1] == "device"]

    run_ranks(RANKS, app, device_mesh=_mesh(1))
    for rank, lane in lanes.items():
        world, row, alone = ctxs[rank]
        derives = [(ph, a) for _t, _l, name, ph, a in lane
                   if name == "dev_comm_derive"]
        assert [ph for ph, _a in derives] == ["B", "E"] * 2
        assert derives[0][1] == {"parent_ctx": world, "ctx": row, "size": 4}
        assert derives[1][1] == {"parent_ctx": world, "ctx": row, "size": 4,
                                 "channel": "HBMSlotChannel",
                                 "same_mesh": False}
        assert derives[3][1] == {"parent_ctx": world, "ctx": alone, "size": 1,
                                 "channel": "none", "same_mesh": False}
        calls = [a for _t, _l, name, ph, a in lane
                 if name == "dev_alltoall" and ph == "B"]
        assert [(a["ctx"], a["derived"], a["seq"]) for a in calls] == \
            [(world, False, 1), (row, True, 1)]
        # every phase event of a call carries its ctx
        phases = [a for _t, _l, name, _ph, a in lane
                  if name in ("dev_arrive", "dev_release", "dev_deliver")]
        assert {a["ctx"] for a in phases} == {world, row}
    text = "\n".join(dump)
    assert f"derived rendezvous ctx {ctxs[5][1] - 1} " \
        f"(world ranks [4, 5, 6, 7])" in text
    assert "world ranks [0, 1, 2, 3]" not in text


def test_the_host_arm_of_an_unbound_communicator_leaves_an_instant(
        traced, interpreted):
    notes = {}

    def app(comm):
        x = jax.device_put(_data(62, comm.rank, 256),
                           comm.device_channel.device)
        pairs = comm.split(comm.rank // 2, comm.rank)
        pairs.allreduce(x)
        notes[comm.rank] = ([a for _t, lay, name, ph, a
                             in comm.u.engine.tracer.events
                             if (lay, name, ph) == ("channel",
                                                    "dev_coll_fallback", "i")
                             and a["reason"] == "host_comm"],
                            pairs.ctx_coll)

    run_ranks(4, app, device_mesh=_mesh(4))
    for rank, (said, ctx) in notes.items():
        assert said == [{"reason": "host_comm", "ctx": ctx, "size": 2}]
