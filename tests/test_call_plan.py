"""A call's decisions are made once per signature (ISSUE 37): the first
blocking device collective of a signature on a rank walks
``comm.<coll>``'s lines, ``_select_transport``, ``_as_local``,
``_op_name`` and ``_decide_tier`` and files what they said
(``coll/device.py`` ``_CallPlan``); every later call of that signature
finds the plan (``plan_of``) and runs on it (``run_plan``). Counted per
rank per call by ``dev_call_plan_hit`` and ``dev_call_plan_filed``; the
``dev_<coll>`` B says ``planned``. A plan is good while no cvar was
written and no profile loaded (``Config.writes``); everything that is
not a hit takes the path it took."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import plain_reference as ref
from mvapich2_tpu import mpit
from mvapich2_tpu.coll import tuning
from mvapich2_tpu.core import op as opmod
from mvapich2_tpu.core.errors import MPIException
from mvapich2_tpu.parallel.mesh import make_mesh
from mvapich2_tpu.runtime.universe import run_ranks
from mvapich2_tpu.utils.config import get_config

ROOT = 2
HIT, FILED = "dev_call_plan_hit", "dev_call_plan_filed"
# channel -> (ranks, devices of the mesh it binds to, class, level pvar)
CHANNELS = {"slot": (8, 1, "HBMSlotChannel", "coll_level_chip"),
            "mesh": (4, 4, "DeviceCollChannel", "coll_level_ici")}
BLOCKING = ["allreduce", "reduce", "bcast", "allgather", "alltoall",
            "reduce_scatter_block"]


@pytest.fixture(autouse=True)
def device_path(monkeypatch):
    """Buffers of every size take the device path; the ring and alltoall
    kernels run under the interpreter, the streaming (HBM) tier from
    8 KiB up, no XLA crossover."""
    monkeypatch.setenv("MV2T_DEVICE_COLL_MIN_BYTES", "1")
    monkeypatch.setenv("MV2T_ICI_INTERPRET", "1")
    monkeypatch.setenv("MV2T_DEV_TIER_VMEM_MAX", "8192")
    monkeypatch.setenv("MV2T_DEV_TIER_XLA_MIN", "-1")
    get_config().reload()
    yield
    monkeypatch.undo()
    get_config().reload()


def _mesh(channel):
    ndev = CHANNELS[channel][1]
    return make_mesh((ndev,), ("x",), jax.devices()[:ndev])


def _data(ranks, n, dtype="float32", seed=37):
    """Whole numbers, other on every rank; small enough that bfloat16
    holds them and every sum over eight ranks exactly."""
    return [np.random.default_rng([seed, r]).integers(
        -15, 15, size=n, endpoint=True).astype(jnp.dtype(dtype))
        for r in range(ranks)]


def _want(name, data, root=ROOT):
    if name in ("reduce", "bcast"):
        return getattr(ref, name)(data, root)
    return getattr(ref, name)(data)


def _call(name, comm, x, root=ROOT, **kw):
    """The collective, waited for: an interpreted kernel whose result
    nobody reads is still running when the test ends, and holds the
    interpreter's shared memory, sized by this mesh, into the next."""
    if name in ("reduce", "bcast"):
        kw["root"] = root
    return jax.block_until_ready(getattr(comm, name)(x, **kw))


def _reads(*names):
    return {n: mpit.pvar(n).read() for n in names}


def _fallbacks():
    return [n for n in (mpit.pvar_get_info(i)["name"]
                        for i in range(mpit.pvar_get_num()))
            if n.startswith("dev_coll_fallback_")]


def _same(got, want):
    np.testing.assert_array_equal(
        np.asarray(got).astype(np.float32).reshape(-1),
        np.asarray(want).astype(np.float32).reshape(-1))


class _Steps:
    """Every rank runs ``steps`` in turn between barriers; rank 0 reads
    the watched pvars after each, so ``rose[i]`` is what step ``i``
    added over all ranks."""

    def __init__(self, channel, watch):
        self.ranks, _, self.klass, self.level = CHANNELS[channel]
        self.channel = channel
        self.watch = tuple(watch)
        self.rose = []

    def run(self, steps, between=None):
        marks = []

        def app(comm):
            assert type(comm.device_channel).__name__ == self.klass
            comm.barrier()
            if comm.rank == 0:
                marks.append(_reads(*self.watch))
            for i, step in enumerate(steps):
                comm.barrier()
                step(comm)
                comm.barrier()
                if comm.rank == 0:
                    marks.append(_reads(*self.watch))
                    if between is not None:
                        between(i)
            comm.barrier()

        run_ranks(self.ranks, app, device_mesh=_mesh(self.channel))
        self.rose = [{k: b[k] - a[k] for k in a}
                     for a, b in zip(marks, marks[1:])]
        return self.rose


@pytest.mark.parametrize("name", BLOCKING)
@pytest.mark.parametrize("channel", list(CHANNELS))
def test_a_second_call_of_one_signature_is_a_hit(channel, name):
    """The first call files, the second and third run the plan: the hit
    pvar rises by the ranks, the filed pvar not at all, the level pvar
    and the deposit's as on every call, no program is built, and the
    values are the first call's, which are the plain reference's."""
    st = _Steps(channel, (HIT, FILED, "dev_deposit_as_is") + (
        CHANNELS[channel][3],))
    data = _data(st.ranks, st.ranks * 16)
    want = _want(name, data)
    outs = [[None] * st.ranks for _ in range(3)]
    programs = [None] * 3

    def call(i):
        def step(comm):
            x = jax.device_put(data[comm.rank], comm.device_channel.device)
            outs[i][comm.rank] = _call(name, comm, x)
            if comm.rank == 0:      # the leader's are the ones that run
                programs[i] = set(comm.device_channel._programs)
        return step

    rose = st.run([call(0), call(1), call(2)])
    # a planned call runs the program its deciding call built (reduce and
    # bcast hand _run the root as that call did), and builds none
    assert programs[0] and programs[0] == programs[1] == programs[2]
    for i, (hit, filed) in enumerate([(0, st.ranks), (st.ranks, 0),
                                      (st.ranks, 0)]):
        assert rose[i] == {HIT: hit, FILED: filed,
                           "dev_deposit_as_is": st.ranks,
                           st.level: st.ranks}, (i, rose[i])
    for r in range(st.ranks):
        for i in range(3):
            if want[r] is None:
                assert outs[i][r] is None
            else:
                _same(outs[i][r], want[r])


def _other_size(comm, x, data, name):
    half = x.shape[0] // 2
    return (_call(name, comm, x[:half] + 0),
            _want(name, [d[:half] for d in data]), "files")


def _other_dtype(comm, x, data, name):
    return (_call(name, comm, x.astype(jnp.int32)),
            _want(name, [d.astype(np.int32) for d in data]), "files")


def _other_op(comm, x, data, name):
    return (comm.allreduce(x, op=opmod.MAX),
            [np.max(np.stack(data), axis=0)] * len(data), "files")


def _other_root(comm, x, data, name):
    return (_call(name, comm, x, root=1), _want(name, data, root=1),
            "files")


def _shaped(comm, x, data, name):
    return _call(name, comm, x.reshape(2, -1)), _want(name, data), "bypasses"


def _partial(comm, x, data, name):
    half = x.shape[0] // 2
    return (_call(name, comm, x, count=half),
            _want(name, [d[:half] for d in data]), "bypasses")


def _host(comm, x, data, name):
    return _call(name, comm, np.asarray(x)), _want(name, data), "bypasses"


def _in_place(comm, x, data, name):
    from mvapich2_tpu.coll.api import IN_PLACE
    c = x.shape[0]
    recv = jnp.zeros(comm.size * c, x.dtype).at[
        comm.rank * c:(comm.rank + 1) * c].set(x)
    return (comm.allgather(IN_PLACE, recv, count=c), ref.allgather(data),
            "bypasses")


OTHERS = [(_other_size, "allreduce"), (_other_size, "alltoall"),
          (_other_dtype, "allreduce"), (_other_op, "allreduce"),
          (_other_root, "reduce"), (_other_root, "bcast"),
          (_shaped, "allreduce"), (_partial, "allreduce"),
          (_host, "allreduce"), (_in_place, "allgather")]


@pytest.mark.parametrize("channel", list(CHANNELS))
@pytest.mark.parametrize("how,name", OTHERS, ids=[
    f"{h.__name__.strip('_')}-{n}" for h, n in OTHERS])
def test_another_signature_never_runs_this_plan(channel, how, name):
    """With the plan of one signature filed, a call that differs in
    size, dtype, op or root decides for itself and files its own; a
    shaped, partial, host or in-place buffer goes round the plans and
    files nothing. Either gives the plain reference's result, on the
    device, and the filed signature is still a hit afterwards."""
    fallbacks = _fallbacks()
    st = _Steps(channel, [HIT, FILED, CHANNELS[channel][3]] + fallbacks)
    data = _data(st.ranks, st.ranks * 16)
    got = [None] * st.ranks
    kind = [None]

    def filed_signature(comm):
        x = jax.device_put(data[comm.rank], comm.device_channel.device)
        _call(name, comm, x)

    def other(comm):
        x = jax.device_put(data[comm.rank], comm.device_channel.device)
        out, want, kind[0] = how(comm, x, data, name)
        got[comm.rank] = (out, want[comm.rank])

    rose = st.run([filed_signature, filed_signature, other,
                   filed_signature])
    assert (rose[0][HIT], rose[0][FILED]) == (0, st.ranks)
    assert (rose[1][HIT], rose[1][FILED]) == (st.ranks, 0)
    assert rose[2][HIT] == 0
    assert rose[2][FILED] == (st.ranks if kind[0] == "files" else 0)
    assert (rose[3][HIT], rose[3][FILED]) == (st.ranks, 0)
    for step in rose:
        assert step[st.level] == st.ranks       # the device carried it
        assert not any(step[k] for k in fallbacks), step
    for out, want in got:
        if want is None:
            assert out is None
        else:
            _same(out, want)


def _to_a_host_algorithm():
    get_config().set("ALLREDUCE_ALGO", "recursive_doubling")


def _device_coll_off():
    get_config().set("USE_DEVICE_COLL", False)


def _vmem_edge_down():
    get_config().set("DEV_TIER_VMEM_MAX", 16)


def _profile_loaded():
    tuning.load_profile(device_crossovers={"dev_tier_vmem_max": 16})


def _by_mpi_t():
    mpit.cvar_write(mpit.cvar_get_index("USE_DEVICE_COLL"), False)


# what is written between two calls, the channel, and what the very
# next call then does: the host carries it, or another tier is counted
WRITES = [(_to_a_host_algorithm, "slot", "host"),
          (_to_a_host_algorithm, "mesh", "host"),
          (_device_coll_off, "slot", "host"),
          (_device_coll_off, "mesh", "host"),
          (_by_mpi_t, "mesh", "host"),
          (_vmem_edge_down, "mesh", "hbm"),
          (_profile_loaded, "slot", "files again")]


@pytest.mark.parametrize("write,channel,then", WRITES, ids=[
    f"{w.__name__.strip('_')}-{c}" for w, c, _ in WRITES])
def test_a_cvar_written_between_two_calls_decides_the_next_call(
        write, channel, then):
    """A filed plan does not outlive the cvars it was decided under:
    ``Config.set``, MPI_T's cvar write and a loaded profile each make
    the next call decide again, so a forced host algorithm or
    USE_DEVICE_COLL off takes that very call to the host, and a moved
    tier edge counts it under the other tier."""
    cfg = get_config()
    saved = (dict(tuning._DEVICE_CROSSOVERS),
             {n: (cv._value, cv._explicit) for n, cv in cfg._vars.items()})
    watch = (HIT, FILED, CHANNELS[channel][3], "dev_coll_tier_vmem",
             "dev_coll_tier_hbm")
    st = _Steps(channel, watch)
    data = _data(st.ranks, st.ranks * 16)
    want = ref.allreduce(data)

    def call(comm):
        x = jax.device_put(data[comm.rank], comm.device_channel.device)
        _same(comm.allreduce(x), want[comm.rank])

    try:
        rose = st.run([call, call, call, call],
                      between=lambda i: write() if i == 1 else None)
    finally:
        tuning._DEVICE_CROSSOVERS.clear()
        tuning._DEVICE_CROSSOVERS.update(saved[0])
        for n, (value, explicit) in saved[1].items():
            cfg._vars[n]._value, cfg._vars[n]._explicit = value, explicit
    mesh = channel == "mesh"
    assert rose[0] == {HIT: 0, FILED: st.ranks, st.level: st.ranks,
                       "dev_coll_tier_vmem": st.ranks * mesh,
                       "dev_coll_tier_hbm": 0}
    assert rose[1] == dict(rose[0], **{HIT: st.ranks, FILED: 0})
    if then == "host":
        assert rose[2] == rose[3] == dict.fromkeys(watch, 0)
    elif then == "hbm":
        assert rose[2] == {HIT: 0, FILED: st.ranks, st.level: st.ranks,
                           "dev_coll_tier_vmem": 0,
                           "dev_coll_tier_hbm": st.ranks}
        assert rose[3] == dict(rose[2], **{HIT: st.ranks, FILED: 0})
    else:
        assert rose[2] == rose[0] and rose[3] == rose[1]


def _set():
    get_config().set("DEBUG_LEVEL", get_config()["DEBUG_LEVEL"])


def _set_value():
    get_config().cvars()["DEBUG_LEVEL"].set_value(0)


def _reload():
    get_config().reload()


def _cvar_write():
    mpit.cvar_write(mpit.cvar_get_index("DEBUG_LEVEL"), 0)


def _load_profile():
    tuning.load_profile()


@pytest.mark.parametrize("write", [_set, _set_value, _reload, _cvar_write,
                                   _load_profile],
                         ids=lambda w: w.__name__.strip("_"))
def test_every_run_time_write_moves_the_write_count(write):
    """One process-wide count (``Config.writes``): whatever can change a
    cvar's value or what overrides it at run time bumps it; reading
    does not."""
    cfg = get_config()
    had = cfg.writes
    cfg["DEBUG_LEVEL"], cfg.get("USE_DEVICE_COLL"), cfg.cvars()
    assert cfg.writes == had
    write()
    assert cfg.writes > had


COUNTED = [("slot", "allreduce", "float32", 1024),
           ("slot", "alltoall", "float32", 1024),
           ("mesh", "allreduce", "float32", 1024),       # the VMEM ring
           ("mesh", "allreduce", "float32", 4096),       # the HBM ring
           ("mesh", "alltoall", "bfloat16", 8192),
           ("mesh", "allgather", "bfloat16", 8192)]


@pytest.mark.parametrize("channel,name,dtype,n", COUNTED, ids=[
    f"{c}-{nm}-{n}" for c, nm, _, n in COUNTED])
def test_planned_calls_count_what_deciding_calls_count(channel, name, dtype,
                                                       n):
    """Over N calls on the plan every counter a call bumps rises exactly
    as over N calls that each decide (the plans dropped before every
    call, which is the parent's path): the level pvar, the tier's, the
    deposit's, the kernel's wire bytes, the tier's latency histogram's
    count, and no fallback."""
    N = 4
    fallbacks = _fallbacks()
    hists = ["lat_dev_slot", "lat_dev_vmem", "lat_dev_hbm", "lat_dev_xla"]
    watch = [HIT, FILED, CHANNELS[channel][3], "dev_deposit_as_is",
             "dev_coll_tier_vmem", "dev_coll_tier_hbm", "dev_a2a_wire_bytes",
             "dev_ag_wire_bytes"] + hists + fallbacks
    st = _Steps(channel, watch)
    data = _data(st.ranks, n, dtype)
    want = _want(name, data)

    def loop(decide_every_call):
        def step(comm):
            x = jax.device_put(data[comm.rank], comm.device_channel.device)
            for _ in range(N):
                if decide_every_call:
                    comm.device_channel._plans.clear()
                _same(_call(name, comm, x), want[comm.rank])
        return step

    deciding, planned = st.run([loop(True), loop(False)])
    assert (deciding.pop(HIT), deciding.pop(FILED)) == (0, N * st.ranks)
    assert (planned.pop(HIT), planned.pop(FILED)) == (N * st.ranks, 0)
    assert deciding == planned
    assert planned[st.level] == planned["dev_deposit_as_is"] == N * st.ranks
    assert sum(planned[h] for h in hists) == N * st.ranks
    assert not any(planned[k] for k in fallbacks)
    if channel == "mesh":
        tier = "vmem" if n * jnp.dtype(dtype).itemsize * (
            st.ranks if name == "allgather" else 1) <= 8192 else "hbm"
        if name == "alltoall":
            tier = "hbm"
        assert planned[f"dev_coll_tier_{tier}"] == N * st.ranks
        assert planned[f"lat_dev_{tier}"] == N * st.ranks
        wire = {"alltoall": "dev_a2a_wire_bytes",
                "allgather": "dev_ag_wire_bytes"}.get(name)
        if wire and tier == "hbm":
            assert planned[wire] > 0 and planned[wire] % (N * st.ranks) == 0


@pytest.mark.parametrize("name", ["allreduce", "alltoall", "allgather"])
@pytest.mark.parametrize("channel", list(CHANNELS))
def test_traced_a_planned_call_records_what_the_deciding_call_did(
        monkeypatch, channel, name):
    """Traced, calls 2 and 3 of a loop leave the same events as call 1:
    names, lanes, phases, order and arg keys, ``mpi:<coll>`` B/E from
    the profile wrapper round them, the wire instant under the call's
    seq; only ``planned`` on the ``dev_<coll>`` B reads other."""
    monkeypatch.setenv("MV2T_TRACE", "1")
    get_config().reload()
    ranks = CHANNELS[channel][0]
    data = _data(ranks, 8192, "bfloat16")
    calls = [None] * ranks

    def app(comm):
        x = jax.device_put(data[comm.rank], comm.device_channel.device)
        comm.barrier()
        start = len(comm.u.engine.tracer.events)
        for _ in range(3):
            _call(name, comm, x)
        evs = [e for e in list(comm.u.engine.tracer.events)[start:]
               if e[1] in ("mpi", "device") and not e[2].startswith("ici_")]
        cuts = [i for i, e in enumerate(evs)
                if (e[1], e[2], e[3]) == ("mpi", name, "B")]
        assert len(cuts) == 3
        calls[comm.rank] = [evs[a:b] for a, b in
                            zip(cuts, cuts[1:] + [len(evs)])]

    run_ranks(ranks, app, device_mesh=_mesh(channel))
    for r in range(ranks):
        shapes, began, seqs = [], [], []
        for call in calls[r]:
            shapes.append([(lane, nm, ph, tuple(args or ()))
                           for _t, lane, nm, ph, args in call])
            b, = [dict(a) for _t, _l, nm, ph, a in call
                  if (nm, ph) == (f"dev_{name}", "B")]
            began.append(b)
            seq, = {a["seq"] for _t, lane, _n, _p, a in call
                    if lane == "device"}      # the wire instant's too
            seqs.append(seq)
        assert shapes[0] == shapes[1] == shapes[2], r
        assert [b.pop("planned") for b in began] == [False, True, True]
        assert seqs == [b.pop("seq") for b in began] \
            == [seqs[0], seqs[0] + 1, seqs[0] + 2]
        assert began[0] == began[1] == began[2]     # tier, op, bytes, ...
        names = [nm for _l, nm, _p, _a in shapes[0]]
        assert names[0] == name and names[-1] == name
        assert f"dev_{name}" in names and "dev_arrive" in names \
            and "dev_release" in names and "dev_deliver" in names
        if channel == "mesh" and name != "allreduce":
            wire = {"alltoall": "dev_a2a_wire",
                    "allgather": "dev_ag_wire"}[name]
            assert names.count(wire) == 1


@pytest.mark.parametrize("channel", list(CHANNELS))
def test_a_leader_that_raises_fails_every_rank_on_a_planned_call(channel):
    """``rv.error`` reaches every rank on a call that runs a plan as on
    one that decides, and the call after it runs the plan again."""
    st = _Steps(channel, (HIT, FILED))
    data = _data(st.ranks, st.ranks * 16)
    want = ref.allreduce(data)
    raised = [None] * st.ranks

    def call(comm):
        x = jax.device_put(data[comm.rank], comm.device_channel.device)
        _same(_call("allreduce", comm, x), want[comm.rank])

    def broken_leader(comm):
        ch = comm.device_channel
        if comm.rank == 0:
            leader = ch._leader

            def broken(*a):
                ch._leader = leader
                raise ValueError("the leader's own")
            ch._leader = broken
        comm.barrier()
        x = jax.device_put(data[comm.rank], ch.device)
        try:
            comm.allreduce(x)
        except RuntimeError as e:
            raised[comm.rank] = (str(e), type(e.__cause__).__name__)

    rose = st.run([call, call, broken_leader, call])
    assert rose == [{HIT: 0, FILED: st.ranks}] + [{HIT: st.ranks, FILED: 0}] * 3
    assert raised == [("device collective allreduce failed on the leader",
                       "ValueError")] * st.ranks


@pytest.mark.parametrize("how", ["freed", "revoked"])
def test_a_dead_comm_raises_before_its_plan_is_looked_up(how):
    """``comm._check()`` comes first on a planned call too."""
    ranks = CHANNELS["slot"][0]
    data = _data(ranks, ranks * 16)

    def app(comm):
        x = jax.device_put(data[comm.rank], comm.device_channel.device)
        _call("allreduce", comm, x)
        _call("allreduce", comm, x)             # a hit
        comm.barrier()
        had = _reads(HIT, FILED)
        setattr(comm, how, True)
        try:
            with pytest.raises(MPIException):
                comm.allreduce(x)
        finally:
            setattr(comm, how, False)
        assert _reads(HIT, FILED) == had        # nothing ran, none filed
        comm.barrier()

    run_ranks(ranks, app, device_mesh=_mesh("slot"))


def test_alltoallv_and_the_nonblocking_entries_file_nothing():
    """alltoallv (its key would be a count vector) and the nonblocking
    device entries decide every call: no plan filed, none run."""
    ranks = CHANNELS["mesh"][0]
    data = _data(ranks, ranks * 16)
    st = _Steps("mesh", (HIT, FILED))

    def v(comm):
        x = jax.device_put(data[comm.rank], comm.device_channel.device)
        counts = [16] * ranks
        displs = [16 * r for r in range(ranks)]
        for _ in range(2):
            out = np.empty(ranks * 16, np.float32)
            comm.alltoallv(x, counts, displs, out, counts, displs)
            _same(out, ref.alltoall(data)[comm.rank])

    def nb(comm):
        x = jax.device_put(data[comm.rank], comm.device_channel.device)
        for _ in range(2):
            out = np.empty(ranks * 16, np.float32)
            comm.iallreduce(x, out).wait()
            _same(out, ref.allreduce(data)[comm.rank])

    rose = st.run([v, nb])
    assert rose == [{HIT: 0, FILED: 0}] * 2


def _traced_context(planned, device=True):
    """Two ranks, three ``dev_allreduce`` B each inside the window and
    one before it, each saying ``planned`` as ``planned(rank, i)`` does
    (None: the arg is absent, a program without call plans)."""
    from chipbench import harness
    from chipbench.context import DeviceTrace, RunContext
    spans = {}
    for r in range(2):
        spans[r] = []
        for i in range(4):
            args = {"seq": i, "coll": "allreduce", "tier": "slot",
                    "op": "sum", "bytes": 4096, "as_is": True}
            if planned(r, i) is not None:
                args["planned"] = planned(r, i)
            spans[r].append((10.0 + i, "device", "dev_allreduce", "B", args))
            spans[r].append((10.5 + i, "device", "dev_allreduce", "E",
                             {"seq": i, "coll": "allreduce"}))
    return RunContext(
        collective=harness.load_by_name("collectives", "allreduce"),
        config={}, traffic={}, ranks=2, bytes_per_rank=4096,
        device_kind="TPU v5 lite", peaks={}, window_mono=(10.9, 14.0),
        spans=spans,
        devices={0: DeviceTrace(0, 0.0, 1.0, [], [])} if device else {})


@pytest.mark.parametrize("planned,device,want", [
    (lambda r, i: True, True, 100.0),
    (lambda r, i: i > 0, True, 100.0),          # the filing call: warm-up
    (lambda r, i: not (r == 1 and i == 2), True, 100.0 * 5 / 6),
    (lambda r, i: False, True, 0.0),
    (lambda r, i: None, True, None),            # the parent: no such arg
    (lambda r, i: True, False, None),           # no device traced
], ids=["all", "filed-in-the-warm-up", "one-decided-again", "none",
        "no-arg", "untraced"])
def test_calls_planned_pct_reads_the_planned_arg(planned, device, want):
    """The benchmark's reader: of all ranks' ``dev_<coll>`` B inside the
    window, the share whose ``planned`` is true; nothing where no B says
    it or no device was traced."""
    from chipbench import harness
    reader = harness.load_by_name("layer_metrics", "calls_planned_pct")
    assert reader.NAME == "calls_planned_pct"
    got = reader.compute(_traced_context(planned, device))
    assert got == (pytest.approx(want) if want is not None else None)
