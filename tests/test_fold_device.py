"""Two ranks a chip (ISSUE 38): eight ranks over four devices bind
``DeviceFoldChannel`` and hand it **device-resident** flat float32
buffers, as ``osu4.allreduce_2level.64MiB.dev`` does at size on the chip.
Every supported collective is held to ``plain_reference`` bit for bit, on
the caller's own device; the level pvars, ``dev_fold_stacked``,
``dev_fold_operands`` and the call plan count what they say; the
``dev_chip_fold`` span lies inside the leader's ``dev_stage`` and nowhere
else. Level 1 of the reduce family takes a chip's deposits as its
operands where they lie flat on the chip (ISSUE 41) and stages anything
else. No test here asserts a time."""

import jax
import numpy as np
import pytest

import plain_reference as ref
from mvapich2_tpu import mpit
from mvapich2_tpu.core import op as opmod
from mvapich2_tpu.parallel.mesh import make_mesh
from mvapich2_tpu.runtime.universe import run_ranks
from mvapich2_tpu.utils.config import get_config

RANKS, CHIPS, K = 8, 4, 2
N = 4096                # 16 KiB a rank: the streaming tier under the fixture
ROOT = 5                # a chip's second rank, not chip 0
# case -> (collective, op, planar copies a call on device-resident
# deposits); on host deposits every case but the bcast copies a chip's
# two (HOST_STACKED)
CASES = {"allreduce_sum": ("allreduce", "sum", 0),
         "allreduce_max": ("allreduce", "max", 0),
         "allgather": ("allgather", None, CHIPS),
         "reduce_scatter_block": ("reduce_scatter_block", "sum", 0),
         "bcast": ("bcast", None, 0),
         "reduce": ("reduce", "sum", 0)}
HOST_STACKED = {case: 0 if case == "bcast" else CHIPS for case in CASES}
FOLDED = ("allreduce_sum", "allreduce_max", "reduce_scatter_block",
          "reduce")     # level 1 is _fold_chip
LEVELS = ("coll_level_chip", "coll_level_ici")
COUNTED = LEVELS + ("dev_fold_stacked", "dev_fold_operands",
                    "dev_call_plan_hit", "dev_call_plan_filed",
                    "dev_deposit_as_is")


@pytest.fixture(autouse=True)
def device_path(monkeypatch):
    """Every size takes the device; the ring runs interpreted, on the
    HBM-streaming tier from 8 KiB up, with no XLA crossover."""
    monkeypatch.setenv("MV2T_DEVICE_COLL_MIN_BYTES", "1")
    monkeypatch.setenv("MV2T_ICI_INTERPRET", "1")
    monkeypatch.setenv("MV2T_DEV_TIER_VMEM_MAX", "8192")
    monkeypatch.setenv("MV2T_DEV_TIER_XLA_MIN", "-1")
    get_config().reload()
    yield
    monkeypatch.undo()
    get_config().reload()


@pytest.fixture
def traced(monkeypatch, device_path):
    monkeypatch.setenv("MV2T_TRACE", "1")
    get_config().reload()


def _inputs(seed, n=N):
    """chipbench's values: whole numbers in +-2^20 from [seed, rank], so
    every float32 sum over eight ranks is exact in any grouping."""
    return [np.random.default_rng([seed, r]).integers(
        -(1 << 20), 1 << 20, size=n, endpoint=True).astype(np.float32)
        for r in range(RANKS)]


def _want(case, data):
    name, op, _stacked = CASES[case]
    if name == "bcast":
        return ref.bcast(data, ROOT)
    if name == "reduce":
        return ref.reduce(data, ROOT, op)
    if name == "allgather":
        return ref.allgather(data)
    return getattr(ref, name)(data, op)


def _call(case, comm, x):
    name, op, _stacked = CASES[case]
    kw = {}
    if op is not None:
        kw["op"] = opmod.MAX if op == "max" else opmod.SUM
    if name in ("bcast", "reduce"):
        kw["root"] = ROOT
    out = getattr(comm, name)(x, **kw)
    return out if out is None else jax.block_until_ready(out)


def _on_own_chip(comm, x):
    return jax.device_put(x, comm.device_channel.device)


def _on_host(comm, x):
    return x.copy()     # a host bcast writes its buffer in place


def _run(case, seed, calls=1, after=None, n=N, deposit=_on_own_chip):
    """``calls`` calls of ``case`` on what ``deposit(comm, values)``
    makes of a rank's values (device-resident on its own chip unless
    said); every rank's last result read back, the devices it lay on,
    and what ``after(comm)`` returned."""
    data = _inputs(seed, n)
    got, homes, extra = [None] * RANKS, [None] * RANKS, [None] * RANKS

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == "DeviceFoldChannel", type(ch).__name__
        assert (ch.k, ch.ndev, ch.chip) == (K, CHIPS, comm.rank // K)
        x = deposit(comm, data[comm.rank])
        for _ in range(calls):
            out = _call(case, comm, x)
        if out is not None:
            if not isinstance(out, np.ndarray):
                homes[comm.rank] = (out.devices(), {ch.device})
            got[comm.rank] = np.asarray(out)
        if after is not None:
            extra[comm.rank] = after(comm)

    run_ranks(RANKS, app,
              device_mesh=make_mesh((CHIPS,), ("x",), jax.devices()[:CHIPS]))
    return data, got, homes, extra


def _reads():
    return {n: mpit.pvar(n).read() for n in COUNTED}


@pytest.mark.parametrize("case", list(CASES))
def test_device_resident_deposits_match_the_plain_reference(case):
    data, got, homes, _ = _run(case, seed=2**31 + 38)
    for r, want in enumerate(_want(case, data)):
        if want is None:            # reduce, off the root
            assert got[r] is None
            continue
        assert got[r].dtype == want.dtype and got[r].shape == want.shape
        assert np.count_nonzero(got[r] != want) == 0, (case, r)
        assert homes[r][0] == homes[r][1], (case, r, homes[r])
    on = [h[1] for h in homes if h is not None]
    assert len({frozenset(d) for d in on}) == (CHIPS if len(on) > 1 else 1)


@pytest.mark.parametrize("case", list(CASES))
def test_levels_copies_and_plans_are_counted(case):
    """Both levels rise per rank per call; on device-resident deposits
    the reduce family makes no planar copy and ``dev_fold_operands``
    rises by one per leader call, allgather still copies a chip's two;
    the first call of the signature files a plan and the two after run
    on it."""
    calls = 3
    before = _reads()
    _run(case, seed=38, calls=calls)
    rose = {n: v - before[n] for n, v in _reads().items()}
    assert all(rose[lv] == RANKS * calls for lv in LEVELS), rose
    assert rose["dev_fold_stacked"] == CASES[case][2] * calls, rose
    assert rose["dev_fold_operands"] == \
        (calls if case in FOLDED else 0), rose
    assert rose["dev_call_plan_filed"] == RANKS, rose
    assert rose["dev_call_plan_hit"] == RANKS * (calls - 1), rose
    assert rose["dev_deposit_as_is"] == RANKS * calls, rose


@pytest.mark.parametrize("case", list(CASES))
def test_host_deposits_are_staged_and_match_the_plain_reference(case):
    """Host buffers take ``_chip_stack`` and the one-operand program, as
    before ISSUE 41: one planar copy per chip per leader call, no call
    counted as operands, nothing deposited as it is and no plan filed."""
    calls = 2
    before = _reads()
    data, got, _, _ = _run(case, seed=2**31 + 41, calls=calls,
                           deposit=_on_host)
    rose = {n: v - before[n] for n, v in _reads().items()}
    for r, want in enumerate(_want(case, data)):
        if want is None:
            continue
        assert np.count_nonzero(got[r] != want) == 0, (case, r)
    assert all(rose[lv] == RANKS * calls for lv in LEVELS), rose
    assert rose["dev_fold_stacked"] == HOST_STACKED[case] * calls, rose
    assert rose["dev_fold_operands"] == 0, rose
    assert rose["dev_deposit_as_is"] == rose["dev_call_plan_filed"] == 0


@pytest.mark.parametrize("case", FOLDED)
def test_the_fold_programs_operands_are_the_deposited_objects(monkeypatch,
                                                              case):
    """Level 1 hands its program what the ranks handed over: chip
    ``j``'s call takes ``k`` operands and operand ``i`` *is* rank
    ``j * k + i``'s array, no reshape and no eager op between."""
    from mvapich2_tpu.coll.device import DeviceFoldChannel
    sound = DeviceFoldChannel._fold_prog
    seen, deposits = [], [None] * RANKS

    def watched(self, op):
        prog = sound(self, op)

        def call(*xs):
            seen.append(xs)
            return prog(*xs)
        return call
    monkeypatch.setattr(DeviceFoldChannel, "_fold_prog", watched)

    def deposit(comm, x):
        deposits[comm.rank] = _on_own_chip(comm, x)
        return deposits[comm.rank]
    data, got, _, _ = _run(case, seed=41, deposit=deposit)
    assert len(seen) == CHIPS and all(len(xs) == K for xs in seen)
    for j, xs in enumerate(seen):
        for i, x in enumerate(xs):
            assert x is deposits[j * K + i], (j, i)
    for r, want in enumerate(_want(case, data)):
        if want is not None:
            assert np.count_nonzero(got[r] != want) == 0, (case, r)


@pytest.mark.parametrize("n", [N, 1000])
@pytest.mark.parametrize("op", ["sum", "max"])
def test_operand_form_is_bit_equal_to_the_stacked_form(op, n):
    """One program, two forms: ``k`` flat operands and one planar
    ``(k, n)`` operand give the same bits (the kernel adds its terms in
    operand order in both); at ``n % 128 != 0`` the flat operands are
    stacked and padded inside the program."""
    import jax.numpy as jnp

    from mvapich2_tpu.coll.device import DeviceFoldChannel, _Rendezvous
    mesh = make_mesh((CHIPS,), ("x",), jax.devices()[:CHIPS])
    ch = DeviceFoldChannel(mesh, "x", _Rendezvous(RANKS), 0, RANKS)
    dev = ch._mesh_devices[CHIPS - 1]
    xs = [jax.device_put(x, dev) for x in _inputs(2**31 + 7, n)[:K]]
    prog = ch._fold_prog(op)
    assert prog is ch._fold_prog(op)        # one cached program an op
    lying = prog(*xs)
    staged = prog(jnp.stack(xs))
    assert lying.shape == staged.shape == (n,)
    assert lying.devices() == staged.devices() == {dev}
    want = getattr(np, op)(np.stack([np.asarray(x) for x in xs]), axis=0)
    assert np.array_equal(np.asarray(lying), np.asarray(staged))
    assert np.array_equal(np.asarray(lying), want)


def test_a_ragged_length_still_goes_in_as_it_lies():
    """``n % 128 != 0``: the deposits are still the program's operands
    (no eager stack); the program pads them itself and the result agrees
    with ``numpy``."""
    before = _reads()
    data, got, homes, _ = _run("allreduce_sum", seed=9, n=1000)
    rose = {n: v - before[n] for n, v in _reads().items()}
    want = np.sum(np.stack(data), axis=0)
    for r in range(RANKS):
        assert np.array_equal(got[r], want), r
        assert homes[r][0] == homes[r][1]
    assert (rose["dev_fold_stacked"], rose["dev_fold_operands"]) == (0, 1)


@pytest.mark.parametrize("case", ["allreduce_sum", "allreduce_max"])
def test_a_deposit_on_another_chips_device_is_staged(case):
    """Rank 3 hands over an array committed to chip 2's device: chip 1
    stages its two deposits (one planar copy), the other three take
    theirs as they lie, the call does not count as operands, and the
    result agrees with ``numpy`` on every rank's own chip."""
    def deposit(comm, x):
        ch = comm.device_channel
        if comm.rank == 3:
            return jax.device_put(x, ch._mesh_devices[2])
        return _on_own_chip(comm, x)
    before = _reads()
    data, got, homes, _ = _run(case, seed=11, deposit=deposit)
    rose = {n: v - before[n] for n, v in _reads().items()}
    for r, want in enumerate(_want(case, data)):
        assert np.count_nonzero(got[r] != want) == 0, (case, r)
        assert homes[r][0] == homes[r][1], (case, r)
    assert (rose["dev_fold_stacked"], rose["dev_fold_operands"]) == (1, 0)


def _device_lane(comm):
    return [e for e in comm.u.engine.tracer.events if e[1] == "device"]


@pytest.mark.parametrize("case", ["allreduce_sum", "allgather", "bcast"])
def test_chip_fold_span_lies_in_the_leaders_stage(traced, case):
    """``dev_chip_fold``: a B/E pair of the device lane on rank 0 only,
    inside ``dev_stage``, ``seq`` and ``coll`` on both, the E adding
    ``k``, ``chips`` and ``stacked``; the second call says ``planned``."""
    name, _op, stacked = CASES[case]
    _, _, _, lanes = _run(case, seed=7, calls=2, after=_device_lane)
    for rank, lane in enumerate(lanes):
        began = [a for _t, _l, n, ph, a in lane
                 if n == f"dev_{name}" and ph == "B"]
        assert [a["planned"] for a in began] == [False, True]
        assert all(a["as_is"] for a in began)
        folds = [(ph, a) for _t, _l, n, ph, a in lane if n == "dev_chip_fold"]
        if rank != 0:
            assert folds == []
            continue
        assert [ph for ph, _a in folds] == ["B", "E"] * 2
        for i, (ph, a) in enumerate(folds):
            assert (a["seq"], a["coll"]) == (i // 2 + 1, name)
            extra = {k: v for k, v in a.items() if k not in ("seq", "coll")}
            assert extra == ({} if ph == "B" else
                             {"k": K, "chips": CHIPS, "stacked": stacked})
        # nested: stage B, fold B, fold E, stage E, in that order
        order = [(n, ph) for _t, _l, n, ph, _a in lane
                 if n in ("dev_stage", "dev_chip_fold")]
        assert order == [("dev_stage", "B"), ("dev_chip_fold", "B"),
                         ("dev_chip_fold", "E"), ("dev_stage", "E")] * 2
