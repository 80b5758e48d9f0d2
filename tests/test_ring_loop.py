"""A ring round's chunk steps run as one loop, not as unrolled copies
(ISSUE 54): ``_RingStreamer.stream_step`` traces a round as its first
``depth`` steps, one ``fori_loop`` over the groups of ``depth`` steps
that follow and a tail, through the helper the broadcast chain uses
(``pallas_ici._looped_steps``). Bit-equality with the unrolled schedule
under the interpreter, the traced kernels' length at any payload, and
the broadcast's kernel against its parent's. Its own file: the
interpreted cases take a worker a few minutes, beside the minutes
tests/test_pallas_ici.py takes another."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from mvapich2_tpu.ops import pallas_ici  # noqa: E402
from mvapich2_tpu.parallel import MeshComm, make_mesh  # noqa: E402

from test_pallas_ici import (P4, ROW, _RING_FNS, _STEP_NAMES,  # noqa: E402
                             _all_eqns, _clean_env, _eqns, _traced_kernel,
                             _unrolled_stream_step, comm4)  # noqa: F401


_LOOPED = {
    # name: (rows a ring block, chunk rows, lanes, depth, k, op)
    "two_groups_exactly": (96, 8, True, 2, 1, "sum"),   # 6 chunks a lane
    "odd_full_chunks": (112, 8, True, 2, 1, "sum"),     # 7 a lane
    "short_last_chunk_on_one_lane": (216, 16, True, 2, 1, "sum"),  # 7 | 6.5
    "uneven_lanes": (104, 8, True, 2, 1, "sum"),        # 7 | 6 chunks
    "one_lane": (56, 8, False, 2, 1, "sum"),            # 7 chunks
    "depth_3": (144, 8, True, 3, 1, "sum"),             # 9 a lane: 2 groups
    "two_operands_max": (112, 8, True, 2, 2, "max"),
    "three_operands": (48, 8, False, 2, 3, "sum"),      # one lane of 6
}


# every shape through the allreduce, whose rounds are the other two's;
# each of those alone on the shapes that are its own to get wrong (the
# fold rounds' k operands and last store, the gather rounds' round 0)
_LOOPED_CASES = [("all_reduce", case) for case in sorted(_LOOPED)] + [
    ("reduce_scatter", "two_groups_exactly"),
    ("reduce_scatter", "short_last_chunk_on_one_lane"),
    ("reduce_scatter", "two_operands_max"),
    ("all_gather", "odd_full_chunks"),
    ("all_gather", "short_last_chunk_on_one_lane"),
    ("all_gather", "one_lane"), ("all_gather", "depth_3")]


@pytest.mark.parametrize("coll,case", _LOOPED_CASES)
def test_looped_round_is_the_unrolled_one(comm4, monkeypatch, coll, case):
    """The rounds whose chunk steps run as a loop give, bit for bit on
    data whose sums depend on the order, what the same rounds give with
    every step written out (the schedule until ISSUE 54, kept above),
    and the shape does loop: two groups exactly, an odd number of full
    chunks, a short last chunk on one lane of a ragged block, uneven
    lanes, one lane, a deeper pipeline, ``k`` operands with another
    reducer."""
    rows, chunk, bidir, depth, k, op = _LOOPED[case]
    blk = rows * ROW - (5 if "short" in case else 0)
    kw = dict(chunk_bytes=chunk * ROW * 4, depth=depth, bidirectional=bidir)
    n = blk if coll == "all_gather" else P4 * blk
    steps = pallas_ici.ring_steps(_STEP_NAMES[coll], n, np.float32, P4, **kw)
    assert steps["steps_looped"] > 0, steps
    if coll != "all_gather":
        kw["op"] = op
    rng = np.random.default_rng([54, rows])
    xjs = [jnp.asarray(rng.uniform(0.5, 1.5, P4 * n).astype(np.float32))
           for _ in range(k)]

    def run():
        return np.asarray(comm4.run(
            lambda *s: _RING_FNS[coll](s if k > 1 else s[0], "x", P4,
                                       interpret=True, **kw),
            *xjs, out_specs=P("x")))
    got = run()
    monkeypatch.setattr(pallas_ici._RingStreamer, "stream_step",
                        _unrolled_stream_step)
    assert got.tobytes() == run().tobytes()
    if coll == "all_gather":
        np.testing.assert_array_equal(got.reshape(P4, -1)[0],
                                      np.asarray(xjs[0]))


_MIB = 1 << 20
# equations of the traced kernel wherever its rounds loop (256 KiB
# chunks, depth 2, two lanes, float32, p = 4); at 8 MiB a rank a
# reduction's lane is 4 chunks a round, under the two groups a loop
# takes: its kernel is the parent's, step for step
_KERNEL_EQNS = {("all_reduce", 1): 988, ("all_reduce", 2): 1132,
                ("reduce_scatter", 1): 580, ("reduce_scatter", 2): 724,
                ("all_gather", 1): 432}
_UNLOOPED_8MIB = {("all_reduce", 1): 868, ("all_reduce", 2): 1012,
                  ("reduce_scatter", 1): 520, ("reduce_scatter", 2): 664}


@pytest.mark.parametrize("coll,k", sorted(_KERNEL_EQNS))
def test_traced_kernel_is_as_long_at_any_payload(coll, k):
    """The traced kernel holds the same number of equations at 16, 64
    and 256 MiB a rank (the all-gather's at 8 MiB too), one and two
    operands a chip: a few steps a round and a loop, whatever the
    payload (until ISSUE 54 the 64 MiB allreduce's held 6 244, and a
    first call lowered them for 20 s). What the instant of the
    signature says (``ring_steps``) is what the trace holds: the loops
    stand for all but a few of the steps, and the 8 MiB reductions,
    too short to loop, are written out as they were."""
    kw = dict(chunk_bytes=256 << 10, depth=2)
    rounds = (P4 - 1) * (2 if coll == "all_reduce" else 1)

    def at(mib):
        n = mib * _MIB // 4         # float32 a rank
        blk = n if coll == "all_gather" else n // P4    # a ring block
        kernel = _traced_kernel(lambda s: _RING_FNS[coll](
            s if k > 1 else s[0], "x", P4, interpret=True, **kw),
            n, np.float32, k)
        return (sum(1 for _ in _all_eqns(kernel)),
                len(list(_eqns(kernel, "scan"))), blk * 4 // 2 // (256 << 10),
                pallas_ici.ring_steps(_STEP_NAMES[coll], n, np.float32,
                                      P4, **kw))

    written = set()
    for mib in (8, 64, 256) if coll == "all_gather" else (16, 64, 256):
        eqns, loops, chunks, steps = at(mib)
        assert (eqns, loops) == (_KERNEL_EQNS[coll, k], rounds)
        # a round is a step more than a lane's chunks; a loop's body is
        # written out once and stands for every group
        assert steps["steps_traced"] + steps["steps_looped"] == \
            rounds * (chunks + 1 + 2), steps
        written.add(steps["steps_traced"])
    assert len(written) == 1, written
    if coll != "all_gather":
        eqns, loops, chunks, steps = at(8)
        assert (eqns, loops) == (_UNLOOPED_8MIB[coll, k], 0)
        assert steps == {"steps_traced": rounds * (chunks + 1),
                         "steps_looped": 0}


def _kernel_digest(kernel):
    """Count and a digest of the kernel's equations in order: each
    primitive's name with the avals it reads and writes."""
    import hashlib
    h, count = hashlib.sha256(), 0
    for e in _all_eqns(kernel):
        count += 1
        h.update(repr((e.primitive.name,
                       [str(v.aval) for v in e.invars],
                       [str(v.aval) for v in e.outvars])).encode())
    return count, h.hexdigest()[:16]


@pytest.mark.parametrize("mib,root,want", [
    (1, 0, (225, "3b808b136d416d8f")), (8, 2, (593, "004508efede59ac5")),
    (64, 0, (593, "e54585713a87fba9"))])
def test_bcast_kernel_on_the_shared_loop_is_the_parents(mib, root, want):
    """The broadcast chain's steps go through the helper the rings'
    rounds use (``_looped_steps``), and its traced kernel is, equation
    for equation (primitive names and avals in order), what the parent
    of ISSUE 54 (commit ae12062) traced with the loop written out in
    ``_chain``: bfloat16, 256 KiB chunks, p = 4, read off that tree."""
    kernel = _traced_kernel(
        lambda s: pallas_ici.hbm_ring_bcast(
            s[0], "x", P4, root, interpret=True, chunk_bytes=256 << 10,
            depth=2), mib * _MIB // 2, jnp.bfloat16)
    assert _kernel_digest(kernel) == want
    steps = pallas_ici.ring_steps("bcast", mib * _MIB // 2, jnp.bfloat16,
                                  P4, chunk_bytes=256 << 10, depth=2)
    assert (steps["steps_looped"] > 0) == (mib > 1), steps


@pytest.mark.parametrize("coll,mib,looped", [
    ("all_reduce", 64, True), ("all_reduce", 8, False),
    ("reduce_scatter", 128, True), ("all_gather", 16, True),
    ("all_gather", 2, False), ("bcast", 64, True)])
def test_the_lowering_instant_says_how_the_ring_is_written(monkeypatch, coll,
                                                           mib, looped):
    """The trace-time ``ici_<coll>`` instant of a signature that lowers
    to the streaming ring carries ``ring_steps``'s two numbers, the
    chunk steps its trace holds and those its loops stand for: looped
    at the four ring cells' sizes and the broadcast's, written out
    whole under two groups a lane; an instant of another tier (4 KiB a
    rank) carries neither."""
    import types

    from mvapich2_tpu.runtime import universe
    from mvapich2_tpu.utils.config import get_config
    said = []
    tracer = types.SimpleNamespace(
        record=lambda lane, name, ph, **a: said.append((name, a)))
    monkeypatch.setattr(
        universe, "current_universe", lambda: types.SimpleNamespace(
            engine=types.SimpleNamespace(tracer=tracer)))
    # the chip's chunk and the default tier edges, said out loud: the
    # CPU's measured profile, loaded once an earlier test of the worker
    # has bound ranks, cuts the one and sends every size to XLA
    for name, value in (("MV2T_ICI_CHUNK_BYTES", 256 << 10),
                        ("MV2T_DEV_TIER_VMEM_MAX", 4 * _MIB),
                        ("MV2T_DEV_TIER_XLA_MIN", -1)):
        monkeypatch.setenv(name, str(value))
    get_config().reload()
    fn = {"bcast": lambda s: pallas_ici.ici_bcast(s, "x", P4, 0,
                                                  interpret=True)}.get(
        coll, lambda s: getattr(pallas_ici, "ici_" + coll)(
            s, "x", P4, interpret=True))
    comm = MeshComm(make_mesh((P4,), ("x",), jax.devices()[:P4]))
    for nbytes in (mib * _MIB, 4096):
        jax.make_jaxpr(lambda x: comm.run(fn, x, out_specs=P("x")))(
            jax.ShapeDtypeStruct((P4 * nbytes // 4,), np.float32))
    (name, big), (_, small) = said
    assert name == "ici_" + _STEP_NAMES.get(coll, coll), name
    assert big["tier"] == "hbm", big
    assert (big["steps_looped"] > 0) == looped and big["steps_traced"] > 0
    if small["tier"] == "hbm":      # a reduce-scatter streams at any size
        assert small["steps_looped"] == 0 < small["steps_traced"]
    else:
        assert not {"steps_traced", "steps_looped"} & set(small)
