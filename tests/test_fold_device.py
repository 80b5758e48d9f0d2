"""Two ranks a chip (ISSUE 38): eight ranks over four devices bind
``DeviceFoldChannel`` and hand it **device-resident** flat float32
buffers, as ``osu4.allreduce_2level.64MiB.dev`` does at size on the chip.
Every supported collective is held to ``plain_reference`` bit for bit, on
the caller's own device; the level pvars, ``dev_fold_stacked``,
``dev_fold_operands`` and the call plan count what they say; the
``dev_chip_fold`` span lies inside the leader's ``dev_stage`` and nowhere
else. Level 1 of the reduce family takes a chip's deposits as its
operands where they lie flat on the chip (ISSUE 41) and stages anything
else; where every chip's lie so it rides in the level-2 mesh program,
one launch a call (ISSUE 44: ``dev_fold_fused``), bit-equal to a launch
a chip; where the streaming ring takes the call and the deposits make
whole-tile ring blocks the ring's fold rounds read both deposits and no
slot reduction runs (ISSUE 49: ``dev_fold_in_ring``). No test here
asserts a time."""

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
                    "dev_fold_fused", "dev_fold_in_ring",
                    "dev_coll_tier_hbm", "dev_call_plan_hit",
                    "dev_call_plan_filed", "dev_deposit_as_is")


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


def _run(case, seed, calls=1, after=None, n=N, deposit=_on_own_chip,
         mesh=None):
    """``calls`` calls of ``case`` on what ``deposit(comm, values)``
    makes of a rank's values (device-resident on its own chip unless
    said), over the four chips in a line unless another ``mesh`` of
    them is given; every rank's last result read back, the devices it
    lay on, and what ``after(comm)`` returned."""
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

    run_ranks(RANKS, app, device_mesh=mesh or make_mesh(
        (CHIPS,), ("x",), jax.devices()[:CHIPS]))
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
    the reduce family makes no planar copy and ``dev_fold_operands``,
    ``dev_fold_fused`` and ``dev_fold_in_ring`` rise by one per leader
    call (the deposits went over as they lay, into the one mesh program,
    and at ``N``, four whole-tile blocks on the streaming tier, into the
    ring kernel), allgather still copies a chip's two; every call counts
    the tier once a rank, as before; the first call of the signature
    files a plan and the two after run on it."""
    calls = 3
    before = _reads()
    _run(case, seed=38, calls=calls)
    rose = {n: v - before[n] for n, v in _reads().items()}
    assert all(rose[lv] == RANKS * calls for lv in LEVELS), rose
    assert rose["dev_fold_stacked"] == CASES[case][2] * calls, rose
    assert rose["dev_fold_operands"] == rose["dev_fold_fused"] == \
        rose["dev_fold_in_ring"] == (calls if case in FOLDED else 0), rose
    # bcast too, since ISSUE 51: the fold channel's level 2 is the 1:1
    # channel's mesh program, which past the vmem bin is the chain
    assert rose["dev_coll_tier_hbm"] == RANKS * calls, rose
    assert rose["dev_call_plan_filed"] == RANKS, rose
    assert rose["dev_call_plan_hit"] == RANKS * (calls - 1), rose
    assert rose["dev_deposit_as_is"] == RANKS * calls, rose


@pytest.mark.parametrize("case", list(CASES))
def test_host_deposits_are_staged_and_match_the_plain_reference(case):
    """Host buffers take ``_chip_stack`` and the one-operand program, as
    before ISSUE 41: one planar copy per chip per leader call, no call
    counted as operands or as fused, nothing deposited as it is and no
    plan filed."""
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
    assert rose["dev_fold_operands"] == rose["dev_fold_fused"] == \
        rose["dev_fold_in_ring"] == 0, rose
    assert rose["dev_deposit_as_is"] == rose["dev_call_plan_filed"] == 0


@pytest.mark.parametrize("case", FOLDED)
def test_the_fold_programs_operands_are_the_deposited_objects(monkeypatch,
                                                              case):
    """Level 1 rides in the level-2 program: the leader makes one launch
    a call, of the program keyed ``extra=k``, on ``k`` mesh-sharded
    operands, and shard ``j`` of operand ``i`` *is* rank ``j * k + i``'s
    array (the object handed to ``_global``, the buffer the program
    reads): no per-chip fold launch, no reshape, no eager op between."""
    from mvapich2_tpu.coll.device import DeviceFoldChannel
    sound_program = DeviceFoldChannel._program
    sound_global = DeviceFoldChannel._global
    launches, globals_, deposits = [], [], [None] * RANKS

    def watched(self, name, n, dtype_str, op, root, extra=None):
        prog = sound_program(self, name, n, dtype_str, op, root, extra)

        def call(*xs):
            launches.append((name, extra, xs))
            return prog(*xs)
        return call

    def no_fold_launch(self, op):
        raise AssertionError("a per-chip fold launch in the fused arm")

    def kept(self, shards, n):
        globals_.append(list(shards))
        return sound_global(self, shards, n)
    monkeypatch.setattr(DeviceFoldChannel, "_program", watched)
    monkeypatch.setattr(DeviceFoldChannel, "_fold_prog", no_fold_launch)
    monkeypatch.setattr(DeviceFoldChannel, "_global", kept)

    def deposit(comm, x):
        deposits[comm.rank] = _on_own_chip(comm, x)
        return deposits[comm.rank]
    data, got, _, _ = _run(case, seed=41, deposit=deposit)
    (name, extra, operands), = launches     # one launch a call
    assert (name, extra, len(operands)) == (CASES[case][0], K, K)
    assert len(globals_) == K
    for i, (shards, operand) in enumerate(zip(globals_, operands)):
        assert operand.shape == (CHIPS * N,)
        lying = sorted(operand.addressable_shards,
                       key=lambda s: s.index[0].start)
        for j in range(CHIPS):
            assert shards[j] is deposits[j * K + i], (j, i)
            assert lying[j].data.unsafe_buffer_pointer() == \
                deposits[j * K + i].unsafe_buffer_pointer(), (j, i)
    for r, want in enumerate(_want(case, data)):
        if want is not None:
            assert np.count_nonzero(got[r] != want) == 0, (case, r)


def _leader_once(name, op, xs, unfused=False):
    """One leader call of a fold channel bound by hand over
    ``jax.devices()[:CHIPS]`` on the deposits ``xs`` (no rank threads:
    the leader's work alone); ``unfused`` folds every chip by its own
    launch, as the tree did until ISSUE 44. Returns every rank's result
    read back and what ``dev_fold_fused`` rose by."""
    from mvapich2_tpu.coll.device import DeviceFoldChannel, _Rendezvous
    mesh = make_mesh((CHIPS,), ("x",), jax.devices()[:CHIPS])
    ch = DeviceFoldChannel(mesh, "x", _Rendezvous(RANKS), 0, RANKS)
    if unfused:
        hands_over = ch._fold_chip

        def a_launch_a_chip(j, n, dtype, op):
            c = hands_over(j, n, dtype, op)
            return ch._fold_prog(op)(*c) if isinstance(c, tuple) else c
        ch._fold_chip = a_launch_a_chip
    ch.rv.slots[:] = [jax.device_put(x, ch.devices[r])
                      for r, x in enumerate(xs)]
    before = mpit.pvar("dev_fold_fused").read()
    out = ch._leader(name, op, 0)
    for r, o in enumerate(out):
        assert o.devices() == {ch.devices[r]}, r
    return ([np.asarray(o) for o in out],
            mpit.pvar("dev_fold_fused").read() - before)


@pytest.mark.parametrize("n", [N, 1000], ids=["whole_rows", "ragged"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("op", ["sum", "max", "min", "prod"])
def test_fused_arm_is_bit_equal_to_a_launch_a_chip(op, dtype, n):
    """Same fold body on the same operands in the same order, then the
    same ring on its result: the one mesh program and the five launches
    give the same bits on every rank, for the four ops, two types, whole
    128-lane rows and a ragged length (stacked and padded inside the
    trace); float32 sums are also numpy's."""
    import ml_dtypes
    rng = np.random.default_rng([44, n])
    lim = 2 if op == "prod" else 1 << 20
    xs = [rng.integers(-lim, lim, size=n, endpoint=True).astype(np.float32)
          .astype(ml_dtypes.bfloat16 if dtype == "bfloat16" else np.float32)
          for _ in range(RANKS)]
    fused, rose = _leader_once("allreduce", op, xs)
    apart, rose_apart = _leader_once("allreduce", op, xs, unfused=True)
    assert (rose, rose_apart) == (1, 0)
    for r in range(RANKS):
        assert fused[r].dtype == apart[r].dtype == xs[0].dtype
        assert fused[r].shape == apart[r].shape == (n,)
        assert fused[r].tobytes() == apart[r].tobytes(), (op, dtype, n, r)
    if dtype == "float32":
        red = {"sum": np.sum, "max": np.max, "min": np.min,
               "prod": np.prod}[op]
        assert np.array_equal(fused[0], red(np.stack(xs), axis=0))


@pytest.mark.parametrize("n", [N, 1000], ids=["whole_rows", "ragged"])
@pytest.mark.parametrize("op", ["sum", "max"])
def test_fused_reduce_scatter_block_is_bit_equal_to_a_launch_a_chip(op, n):
    """reduce_scatter_block through the same two arms: fold, then the
    ring's fold rounds, then one slice a rank, the same bits."""
    xs = _inputs(2**31 + 44, n)
    fused, rose = _leader_once("reduce_scatter_block", op, xs)
    apart, rose_apart = _leader_once("reduce_scatter_block", op, xs,
                                     unfused=True)
    assert (rose, rose_apart) == (1, 0)
    want = getattr(np, op)(np.stack(xs), axis=0)
    c = n // RANKS
    for r in range(RANKS):
        assert fused[r].tobytes() == apart[r].tobytes(), (op, n, r)
        assert np.array_equal(fused[r], want[r * c:(r + 1) * c]), (op, n, r)


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
    (no eager stack, and the one fused program); the program stacks,
    pads and folds them itself in front of the ring (in the ring a pad
    would be ``k`` copies: not ``dev_fold_in_ring``) and the result
    agrees with ``numpy``."""
    before = _reads()
    data, got, homes, _ = _run("allreduce_sum", seed=9, n=1000)
    rose = {n: v - before[n] for n, v in _reads().items()}
    want = np.sum(np.stack(data), axis=0)
    for r in range(RANKS):
        assert np.array_equal(got[r], want), r
        assert homes[r][0] == homes[r][1]
    assert (rose["dev_fold_stacked"], rose["dev_fold_operands"],
            rose["dev_fold_fused"], rose["dev_fold_in_ring"]) == (0, 1, 1, 0)


# where level 1 stays in front of the level-2 collective: (elements a
# rank, MV2T_DEV_TIER_VMEM_MAX, the four chips' mesh, dev_fold_fused a
# call, the tier pvar counted once a rank a call)
FOLDS_FIRST = {
    # a ring block of 750 elements: the ring would pad each operand
    "ragged": (3000, "8192", ((CHIPS,), ("x",)), 1, "dev_coll_tier_hbm"),
    # N under the streaming tier's edge: a sum rides the flat VMEM ring
    "under_the_hbm_tier": (N, "65536", ((CHIPS,), ("x",)), 1,
                           "dev_coll_tier_vmem"),
    # 2 x 2: the chips' folds go to the unfused multi-axis program
    "multi_axis": (N, "8192", ((2, 2), ("x", "y")), 0, None),
}


@pytest.mark.parametrize("why", list(FOLDS_FIRST))
@pytest.mark.parametrize("case", ["allreduce_sum", "reduce"])
def test_level_1_outside_the_ring_is_not_counted_in_ring(monkeypatch, traced,
                                                         case, why):
    """``dev_fold_in_ring`` is the streaming ring of a 1-D mesh on
    whole-tile blocks and nothing else: a ragged length, a message under
    the ``hbm`` tier's edge and a multi-axis mesh fold first, as before
    ISSUE 49 (``dev_fold_fused`` as then), the E says ``in_ring``
    false, both levels still rise once a rank a call, and the result is
    the plain reference's."""
    n, vmem_max, (shape, axes), fused, tier = FOLDS_FIRST[why]
    monkeypatch.setenv("MV2T_DEV_TIER_VMEM_MAX", vmem_max)
    get_config().reload()
    before = _reads()
    tier0 = mpit.pvar(tier).read() if tier else 0
    data, got, _, lanes = _run(
        case, seed=2**31 + 49, after=_device_lane, n=n,
        mesh=make_mesh(shape, axes, jax.devices()[:CHIPS]))
    rose = {n: v - before[n] for n, v in _reads().items()}
    for r, want in enumerate(_want(case, data)):
        if want is not None:
            assert np.count_nonzero(got[r] != want) == 0, (case, why, r)
    assert all(rose[lv] == RANKS for lv in LEVELS), rose
    assert (rose["dev_fold_fused"], rose["dev_fold_in_ring"]) == (fused, 0)
    if tier:
        assert mpit.pvar(tier).read() - tier0 == RANKS
    (end,) = [a for _t, _l, n, ph, a in lanes[0]
              if n == "dev_chip_fold" and ph == "E"]
    assert (end["fused"], end["in_ring"]) == (bool(fused), False)


@pytest.mark.parametrize("where", ["another_chip", "host"])
@pytest.mark.parametrize("case", ["allreduce_sum", "allreduce_max",
                                  "reduce_scatter_block"])
def test_one_deposit_off_its_chip_sends_the_call_down_the_unfused_arm(
        case, where):
    """Rank 3 hands over an array committed to chip 2's device, or a
    host buffer: chip 1 stages its two deposits (one planar copy), the
    other three hand theirs over as they lie and are folded by a launch
    each, the call counts neither as operands nor as fused, and the
    result agrees with ``numpy`` on every rank's own chip."""
    def deposit(comm, x):
        ch = comm.device_channel
        if comm.rank != 3:
            return _on_own_chip(comm, x)
        if where == "host":
            return _on_host(comm, x)
        return jax.device_put(x, ch._mesh_devices[2])
    before = _reads()
    data, got, homes, _ = _run(case, seed=11, deposit=deposit)
    rose = {n: v - before[n] for n, v in _reads().items()}
    for r, want in enumerate(_want(case, data)):
        assert np.count_nonzero(got[r] != want) == 0, (case, r)
        if homes[r] is not None:        # a host caller gets a host array
            assert homes[r][0] == homes[r][1], (case, r)
    assert (rose["dev_fold_stacked"], rose["dev_fold_operands"],
            rose["dev_fold_fused"], rose["dev_fold_in_ring"]) == (1, 0, 0, 0)


def test_a_chip_answering_with_one_array_reaches_the_ring_as_it_is(
        monkeypatch):
    """``_fold_chip`` says what a chip contributes to level 2: an answer
    that is one array (here chip 1's first deposit alone, as
    ``chipbench/tests/test_rehearsal_fold.py`` breaks the path) goes to
    the unfused mesh program as it is, the other chips' deposits are
    folded by a launch each, and rank 3 is in no sum."""
    from mvapich2_tpu.coll.device import DeviceFoldChannel
    sound = DeviceFoldChannel._fold_chip

    def short(self, j, n, dtype, op):
        if j != 1:
            return sound(self, j, n, dtype, op)
        return self.rv.slots[j * self.k]
    monkeypatch.setattr(DeviceFoldChannel, "_fold_chip", short)
    before = _reads()
    data, got, _, _ = _run("allreduce_sum", seed=13)
    rose = {n: v - before[n] for n, v in _reads().items()}
    want = np.sum(np.stack(data[:3] + data[4:]), axis=0)
    for r in range(RANKS):
        assert np.array_equal(got[r], want), r
    assert rose["dev_fold_fused"] == 0


def _device_lane(comm):
    return [e for e in comm.u.engine.tracer.events if e[1] == "device"]


@pytest.mark.parametrize("case", ["allreduce_sum", "reduce_scatter_block",
                                  "allgather", "bcast"])
def test_chip_fold_span_lies_in_the_leaders_stage(traced, case):
    """``dev_chip_fold``: a B/E pair of the device lane on rank 0 only,
    inside ``dev_stage``, ``seq`` and ``coll`` on both, the E adding
    ``k``, ``chips``, ``stacked``, ``fused`` and ``in_ring`` (the reduce
    family on deposits that lie: level 1 went into the mesh program, and
    at ``N`` into its ring kernel); the second call says ``planned``."""
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
            extra = {k: v for k, v in a.items()
                     if k not in ("seq", "coll", "ctx")}
            assert extra == ({} if ph == "B" else
                             {"k": K, "chips": CHIPS, "stacked": stacked,
                              "fused": case in FOLDED,
                              "in_ring": case in FOLDED})
        # nested: stage B, fold B, fold E, stage E, in that order
        order = [(n, ph) for _t, _l, n, ph, _a in lane
                 if n in ("dev_stage", "dev_chip_fold")]
        assert order == [("dev_stage", "B"), ("dev_chip_fold", "B"),
                         ("dev_chip_fold", "E"), ("dev_stage", "E")] * 2
