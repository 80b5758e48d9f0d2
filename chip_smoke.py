#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process that owns the chip drives the main path once, through the
entry points a user calls, and checks every result bit for bit against a
plain numpy reference computed on the host from the same ``--seed``:

  1. *Device.* ``jax.devices()`` must be a TPU, else exit non-zero.
  2. *Library door.* ``run_ranks(8, app, device_mesh=True)`` — eight
     thread-ranks bound to the one chip (``HBMSlotChannel``) holding
     device-resident ``jax.Array`` buffers, 64 MiB f32 per rank:
     allreduce sum (the Pallas slot kernel), allreduce max, bcast,
     reduce_scatter_block at 64 MiB; allgather and alltoall at 8 MiB;
     allreduce sum at 4 B / 4 KiB / 1 MiB; allreduce at 1 MiB on host
     numpy buffers under ``MV2T_ALLREDUCE_ALGO=device`` (staging); and
     alltoall at 128 MiB, 16 MiB a pair, the size the benchmark's cell
     ``osu1.alltoall.128MiB.dev`` times.
  3. *Launcher door.* ``mvapich2_tpu.run --vpod -np 8
     benchmarks/osu_allreduce.py -m 67108864 -i 3 -x 1`` in this same
     process (the launcher runs rank threads in-process on the real
     device), to the port's own ``No Errors``.
  4. *Point-to-point lane.* ``run_ranks(2, app, device_mesh=True)``: the
     two ranks swap 1 MiB of float32 by ``comm.sendrecv`` on device
     buffers, each deletes its own array once the call has returned,
     and what each received is still bit-equal to what the other sent,
     on its own device (``dev_pt2pt_send`` +2, no fallback).
  5. *Derived communicators.* ``run_ranks(8, app, device_mesh=True)``
     once more: a ``dup`` of the world, its 2 x 4 rows and its columns
     by ``comm.split`` (both rows, and all four columns, calling at
     once) and the world with its keys reversed; on each the six
     blocking collectives on device arrays at 4 MiB a rank, bit-equal
     to ``tests/plain_reference.py``'s derived forms, every result on
     the chip, ``dev_coll_derived`` +1 a rank a call and no
     ``dev_coll_fallback_*``.
  6. *Proof the chip did the work.* ``coll_level_chip`` rose by exactly
     the device collectives issued, ``dev_coll_fallback_host_dtype`` by
     exactly the port's float64 latency statistics (x64 is off, so they
     are turned away and counted), every other ``dev_coll_fallback_*``
     pvar is 0, and the lowered text of the slot program that ran holds a
     ``tpu_custom_call``.

``--chips 4`` runs the four-chip phases instead, and nothing else:
``run_ranks(4, app, device_mesh=True)`` (1:1 ``DeviceCollChannel``, the
Pallas ICI ring kernels) compared with numpy AND with the stock XLA
lowering (``lax.psum`` / ``all_gather`` / ``all_to_all`` /
``psum_scatter``: allreduce at 4 KiB, 1 MiB and 64 MiB, allgather and
alltoall at 16 MiB, allreduce max and reduce_scatter_block at 1 MiB, the
last on the ring's fold rounds alone; bcast at 32 MiB from rank 0 and
from rank 2 by the streaming chain, ``dev_coll_tier_hbm`` +1 a rank a
call, the senders' buffers deleted after the calls; and the 64 MiB
allreduce once more on a ``dup`` of the world, whose channel runs the
world's programs: the ring kernel, ``dev_coll_tier_hbm`` and
``dev_coll_derived`` +1 a rank, nothing lowered again); then the fold
phase, ``run_ranks(8, app, device_mesh=<the four chips>)`` (two ranks a
chip, ``DeviceFoldChannel``): allreduce sum and max, allgather,
reduce_scatter_block, bcast and reduce at 1 MiB a rank on device-resident
buffers, once each, compared with numpy; level 1 of the four reductions
has to ride in the mesh program (``dev_fold_fused`` +4), and inside the
ring kernel's fold rounds wherever the streaming ring takes the call
(``dev_fold_in_ring``: at 1 MiB the max and the reduce_scatter_block; a
sum of that size rides the flat VMEM ring behind the slot reduction),
and the allreduce once more on a ``dup``;
then the
point-to-point lane between two ranks on two chips (``dev_pt2pt_d2d``
+2).

Any failed phase raises: the exit code is non-zero and no result line is
printed. Timings are host-clock smoke timings around
``block_until_ready`` — not metrics. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

MiB = 1 << 20
NRANKS = 8
STEADY_CALLS = 3
OSU_ARGS = ["-m", str(64 * MiB), "-i", "3", "-x", "1"]


def say(msg: str) -> None:
    print(msg, flush=True)


def rank_data(seed: int, tag: int, rank: int, nelems: int) -> np.ndarray:
    """Rank ``rank``'s f32 buffer for phase ``tag``: whole numbers from
    [-2^20, 2^20] as ``chipbench`` draws them, so f32 sums over 8 ranks
    are exact in any order and a sum carried in bfloat16 is not."""
    rng = np.random.default_rng([seed, tag, rank])
    return rng.integers(-(1 << 20), 1 << 20, nelems,
                        endpoint=True).astype(np.float32)


def fallback_pvars() -> dict:
    from mvapich2_tpu import mpit
    return {n: mpit.pvar(n).read()
            for n in (mpit.pvar_get_info(i)["name"]
                      for i in range(mpit.pvar_get_num()))
            if n.startswith("dev_coll_fallback_")}


@contextlib.contextmanager
def forced_device_allreduce():
    """``MV2T_ALLREDUCE_ALGO=device`` for the duration: host numpy
    buffers then take the device whatever crossover is compiled in."""
    from mvapich2_tpu.utils.config import get_config
    cfg = get_config()
    os.environ["MV2T_ALLREDUCE_ALGO"] = "device"
    cfg.reload()
    try:
        yield
    finally:
        del os.environ["MV2T_ALLREDUCE_ALGO"]
        cfg.reload()


class _Phase:
    """One collective at one size: inputs, the call, the reference."""

    def __init__(self, tag, label, nelems, call, ref, host=False):
        self.tag, self.label, self.nelems = tag, label, nelems
        self.call, self.ref, self.host = call, ref, host


def _library_phases(nranks: int, big: int, mid: int, fft: int):
    """The library door's calls. ``big``/``mid``/``fft``: elements per
    rank at the 64 MiB / 8 MiB / 128 MiB points (cut for the CPU
    rehearsal)."""
    from mvapich2_tpu.core import op as opmod
    root = 3 % nranks

    def rsb_ref(xs):
        return np.sum(xs, axis=0).reshape(nranks, -1)

    def a2a_ref(xs):
        c = xs[0].size // nranks
        return np.stack([np.concatenate(
            [xs[s][r * c:(r + 1) * c] for s in range(nranks)])
            for r in range(nranks)])

    P = _Phase
    return [
        P(1, "allreduce sum", big, lambda c, x: c.allreduce(x),
          lambda xs: np.sum(xs, axis=0)),
        P(1, "allreduce max", big,
          lambda c, x: c.allreduce(x, op=opmod.MAX),
          lambda xs: np.max(xs, axis=0)),
        P(1, "bcast", big, lambda c, x: c.bcast(x, root=root),
          lambda xs: xs[root]),
        P(1, "reduce_scatter_block", big,
          lambda c, x: c.reduce_scatter_block(x), rsb_ref),
        P(2, "allgather", mid, lambda c, x: c.allgather(x),
          lambda xs: np.concatenate(xs)),
        P(2, "alltoall", mid, lambda c, x: c.alltoall(x), a2a_ref),
        P(3, "allreduce sum", 1, lambda c, x: c.allreduce(x),
          lambda xs: np.sum(xs, axis=0)),
        P(4, "allreduce sum", 1024, lambda c, x: c.allreduce(x),
          lambda xs: np.sum(xs, axis=0)),
        P(5, "allreduce sum", MiB // 4, lambda c, x: c.allreduce(x),
          lambda xs: np.sum(xs, axis=0)),
        P(6, "allreduce sum [host buffers, MV2T_ALLREDUCE_ALGO=device]",
          MiB // 4, lambda c, x: c.allreduce(x),
          lambda xs: np.sum(xs, axis=0), host=True),
        P(7, "alltoall", fft, lambda c, x: c.alltoall(x), a2a_ref),
    ]


def _expect(ref: np.ndarray, label: str, rank: int) -> np.ndarray:
    """This rank's slice of the reference (rank-indexed for the
    scattering collectives, shared otherwise)."""
    if label in ("reduce_scatter_block", "alltoall"):
        return ref[rank]
    return ref


def _peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def library_door(seed: int, nranks: int = NRANKS, big: int = 16 * MiB,
                 mid: int = 2 * MiB, fft: int = 32 * MiB, device_mesh=True,
                 channel: str = "HBMSlotChannel",
                 expect_kernel: bool = True) -> int:
    """Door 1: MPI calls on thread-ranks bound to the device. Returns
    the number of device collectives issued (per rank)."""
    import jax

    from mvapich2_tpu import run_ranks

    phases = _library_phases(nranks, big, mid, fft)
    data, refs = {}, {}
    for ph in phases:       # inputs + references: host, outside timing
        if ph.tag not in data:
            data[ph.tag] = [rank_data(seed, ph.tag, r, ph.nelems)
                            for r in range(nranks)]
        refs[id(ph)] = ph.ref(data[ph.tag])
    report = [None] * len(phases)

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == channel, type(ch).__name__
        dev = ch.device
        for i, ph in enumerate(phases):
            x = data[ph.tag][comm.rank]
            if not ph.host:
                x = jax.device_put(x, dev)
            want = _expect(refs[id(ph)], ph.label, comm.rank)
            times = []
            for _ in range(1 + STEADY_CALLS):
                comm.barrier()
                t0 = time.perf_counter()
                out = jax.block_until_ready(ph.call(comm, x))
                times.append(time.perf_counter() - t0)
                got = np.asarray(out)           # outside the timing
                if not ph.host:
                    assert out.devices() == {dev}, (ph.label, out.devices())
                if got.shape != want.shape or not np.array_equal(got, want):
                    raise AssertionError(
                        f"rank {comm.rank}: {ph.label} at "
                        f"{ph.nelems * 4} B differs from the numpy "
                        f"reference")
                del out, got
            if comm.rank == 0:
                report[i] = (times[0], statistics.median(times[1:]),
                             _peak_bytes(dev))
        if comm.rank == 0:
            # proof from the program, not from the rule: the slot
            # program rank 0 (the leader) built and ran for the 64 MiB
            # sum, on the ranks' device buffers as its operands, lowers
            # to a Mosaic kernel (the CPU rehearsal interprets it, and
            # checks only that the program is where this looks)
            prog = ch._programs[("allreduce", big, "float32", "sum", 0,
                                 nranks)]
            prog = getattr(prog, "fn", prog)
            text = prog.lower(*[jax.ShapeDtypeStruct(
                (big,), np.float32)] * nranks).as_text()
            if expect_kernel:
                assert "tpu_custom_call" in text, \
                    "the slot program holds no Pallas kernel"
                say("library door: tpu_custom_call present in the "
                    "lowered slot program (allreduce sum, "
                    f"{nranks} x {big * 4} B)")

    with forced_device_allreduce():                # host-buffer phase
        run_ranks(nranks, app, device_mesh=device_mesh, timeout=900.0)
    for ph, (first, steady, peak) in zip(phases, report):
        say(f"library door: {ph.label:<22} {ph.nelems * 4:>10} B/rank  "
            f"bit-equal to numpy on {nranks} ranks | first call "
            f"{first:.3f} s (compile), steady {steady * 1e3:.3f} ms/call "
            f"(smoke timing) | peak_bytes_in_use {peak}")
    return len(phases) * (1 + STEADY_CALLS)


def derived_comms(seed: int, nranks: int = NRANKS, nbytes: int = 4 * MiB,
                  device_mesh=True, at_once: bool = True) -> int:
    """Communicators derived from the bound world (ISSUE 55): a ``dup``,
    the 2 x ``nranks / 2`` rows and the columns by ``comm.split`` (the
    groups of a split calling at once), and the world with its keys
    reversed; on each the six blocking collectives once on device
    arrays, against ``tests/plain_reference.py``'s derived forms. Every
    rank is a member of all four, so the step adds its return value, the
    device collectives issued per rank, to ``coll_level_chip``.
    ``at_once=False`` lets the groups of a split reduce one after the
    other: the CPU rehearsal's, whose Pallas interpreter keeps one
    shared memory a process."""
    import jax

    from mvapich2_tpu import mpit, run_ranks
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import plain_reference as ref

    half, n, root = nranks // 2, nbytes // 4, 1
    world = list(range(nranks))
    made = {     # name -> (the call on the world, the partition it makes)
        "dup": (lambda c: c.dup(), [world]),
        "rows": (lambda c: c.split(c.rank // half, c.rank % half),
                 [world[:half], world[half:]]),
        "columns": (lambda c: c.split(c.rank % half, c.rank // half),
                    [[j, j + half] for j in range(half)]),
        "reversed": (lambda c: c.split(0, -c.rank), [world[::-1]])}
    colls = {    # name -> (the call, the reference of one group, computes)
        "allreduce": (lambda c, x: c.allreduce(x), ref.allreduce, True),
        "reduce": (lambda c, x: c.reduce(x, root=root),
                   lambda xs: ref.reduce(xs, root), True),
        "bcast": (lambda c, x: c.bcast(x, root=root),
                  lambda xs: ref.bcast(xs, root), False),
        "allgather": (lambda c, x: c.allgather(x), ref.allgather, False),
        "alltoall": (lambda c, x: c.alltoall(x), ref.alltoall, False),
        "reduce_scatter_block": (lambda c, x: c.reduce_scatter_block(x),
                                 ref.reduce_scatter_block, True)}
    xs = [rank_data(seed, 400, r, n) for r in range(nranks)]
    results = {(m, c): [None] * nranks for m in made for c in colls}
    took = {}

    def app(comm):
        dev = comm.device_channel.device
        x = jax.device_put(xs[comm.rank], dev)
        for m, (make, groups) in made.items():
            t0 = time.perf_counter()
            sub = make(comm)
            if comm.rank == 0:
                took[m] = time.perf_counter() - t0
            ch = sub.device_channel
            assert type(ch).__name__ == "HBMSlotChannel" and ch.derived \
                and ch.device == dev, (m, type(ch).__name__)
            mine = next(i for i, g in enumerate(groups) if comm.rank in g)
            for c, (call, _ref, computes) in colls.items():
                turns = [None] if at_once or not computes \
                    else range(len(groups))
                for turn in turns:
                    if turn in (None, mine):
                        out = call(sub, x)
                        if out is not None:     # reduce, off the root
                            out = jax.block_until_ready(out)
                            assert out.devices() == {dev}, (m, c)
                            results[m, c][comm.rank] = np.asarray(out)
                    comm.barrier()
            sub.free()

    names = ("coll_level_chip", "dev_coll_derived")
    before = {n_: mpit.pvar(n_).read() for n_ in names}
    fb0 = fallback_pvars()
    run_ranks(nranks, app, device_mesh=device_mesh, timeout=900.0)
    for m, (_make, groups) in made.items():
        for c, (_call, reference, _computes) in colls.items():
            want = ref.on_groups(reference, xs, groups)
            for r in range(nranks):
                got = results[m, c][r]
                if (got is None) != (want[r] is None) or (
                        got is not None and not np.array_equal(got, want[r])):
                    raise AssertionError(
                        f"derived comms: {c} on {m}: rank {r} differs "
                        f"from the plain reference")
        say(f"derived comms: {m:<9} ({len(groups)} group(s) of "
            f"{len(groups[0])}) made in {took[m] * 1e3:.1f} ms; six "
            f"collectives at {nbytes} B/rank bit-equal to the plain "
            f"reference, results on the chip")
    calls = len(made) * len(colls)
    rose = {n_: int(mpit.pvar(n_).read() - v) for n_, v in before.items()}
    fb = {n_: v - fb0[n_] for n_, v in fallback_pvars().items()}
    say(f"proof (derived comms): {rose}; fallbacks {fb}")
    assert rose == {n_: nranks * calls for n_ in names}, rose
    assert fb and not any(fb.values()), fb
    return calls


def pt2pt_lane(seed: int, device_mesh=True, nbytes: int = MiB,
               d2d: int = 0) -> None:
    """The device point-to-point lane through the library door: two
    thread-ranks swap ``nbytes`` of float32 by ``comm.sendrecv``; each
    sender deletes its array once the call has returned (the send has
    completed: what a donating jit would do next), and what each rank
    received has to be its own, still valid and bit-equal to what the
    other sent. ``d2d`` is how often the receiver-owned copy has to be
    the runtime's device-to-device copy: 0 where both ranks share one
    chip, 2 where they sit on two."""
    import jax

    from mvapich2_tpu import mpit, run_ranks

    names = ("dev_pt2pt_send", "dev_pt2pt_recv", "dev_pt2pt_d2d",
             "dev_pt2pt_fallback_host")
    xs = [rank_data(seed, 300, r, nbytes // 4) for r in range(2)]
    homes = [None, None]

    def app(comm):
        dev = homes[comm.rank] = comm.device_channel.device
        other = 1 - comm.rank
        x = jax.device_put(xs[comm.rank], dev)
        got = comm.sendrecv(x, other, 0, x, other, 0)
        x.delete()
        comm.barrier()          # both have deleted before either reads
        assert got.devices() == {dev}, (comm.rank, got.devices())
        if not np.array_equal(np.asarray(got), xs[other]):
            raise AssertionError(f"pt2pt lane: rank {comm.rank} holds "
                                 f"something else than rank {other} sent")

    before = {n: mpit.pvar(n).read() for n in names}
    run_ranks(2, app, device_mesh=device_mesh, timeout=300.0)
    rose = {n: int(mpit.pvar(n).read() - v) for n, v in before.items()}
    say(f"pt2pt lane: sendrecv {nbytes} B each way between 2 ranks on "
        f"{len(set(homes))} device(s), senders' arrays deleted, results "
        f"bit-equal to what was sent | {rose}")
    assert rose == {"dev_pt2pt_send": 2, "dev_pt2pt_recv": 2,
                    "dev_pt2pt_d2d": d2d, "dev_pt2pt_fallback_host": 0}, rose
    assert len(set(homes)) == (2 if d2d else 1), homes


class _Tee:
    """Pass-through stdout that keeps what went by."""

    def __init__(self, inner):
        self.inner, self.kept = inner, []

    def write(self, s):
        self.kept.append(s)
        return self.inner.write(s)

    def flush(self):
        self.inner.flush()


def launcher_door(nranks: int = NRANKS, osu_args=OSU_ARGS):
    """Door 2: the mpirun front door, in-process on the real device.
    Returns, per rank, the number of device collectives issued and the
    number of calls turned away from the device for their dtype."""
    from types import SimpleNamespace

    from mvapich2_tpu.bench import osu_util
    from mvapich2_tpu.runtime import launcher

    argv = ["--vpod", "-np", str(nranks),
            os.path.join(REPO, "benchmarks", "osu_allreduce.py"), *osu_args]
    say("launcher door: python -m mvapich2_tpu.run " + " ".join(argv)
        + "   [MV2T_ALLREDUCE_ALGO=device]")
    tee = sys.stdout = _Tee(sys.stdout)
    t0 = time.perf_counter()
    try:
        with forced_device_allreduce():   # the port allocates host buffers
            rc = launcher.main(argv)
    finally:
        sys.stdout = tee.inner
    out = "".join(tee.kept)
    if rc != 0 or "No Errors" not in out:
        raise AssertionError(f"launcher door failed: rc={rc}, "
                             f"'No Errors' {'in' if 'No Errors' in out else 'not in'} output")
    # the size table the port walked: skip + iters calls per size, plus
    # the int32 error-count allreduce of finalize_ok; the three f64
    # latency statistics a size do not lower with x64 off, keep the host
    # path and are counted as turned away
    it = iter(osu_args)
    o = dict(zip(it, it))
    opts = SimpleNamespace(min_size=4, max_size=int(o["-m"]),
                           iterations=int(o["-i"]), skip=int(o["-x"]))
    sizes = list(osu_util.sizes(opts))
    calls = sum(opts.skip + osu_util.scale_iters(opts, s)
                for s in sizes) + 1
    say(f"launcher door: No Errors; {calls} device allreduces per rank "
        f"up to {opts.max_size} B in {time.perf_counter() - t0:.1f} s "
        f"(smoke timing)")
    return calls, 3 * len(sizes)


def one_chip(seed: int) -> None:
    import jax

    from mvapich2_tpu import mpit
    from mvapich2_tpu.transport import shm
    say("native helpers: " + ("libshmring.so built on demand and loaded"
                              if shm._load_native() is not None
                              else "not built; python fallback"))
    chip0, fb0 = mpit.pvar("coll_level_chip").read(), fallback_pvars()
    calls = library_door(seed)
    osu_calls, osu_stats = launcher_door()
    calls += osu_calls
    calls += derived_comms(seed)
    pt2pt_lane(seed)            # no collective of the device path in it
    rose = mpit.pvar("coll_level_chip").read() - chip0
    fb = {n: v - fb0[n] for n, v in fallback_pvars().items()}
    say(f"proof: coll_level_chip rose by {rose} = {NRANKS} ranks x {calls} "
        f"device collectives issued; fallbacks {fb}")
    assert rose == NRANKS * calls, (rose, NRANKS * calls)
    assert fb.pop("dev_coll_fallback_host_dtype") == NRANKS * osu_stats, \
        (fb, osu_stats)
    assert fb and not any(fb.values()), fb
    say(f"proof: peak_bytes_in_use {_peak_bytes(jax.devices()[0])}")


# ---------------------------------------------------------------------------
# --chips 4: the ICI ring kernels against numpy and the stock lowering
# ---------------------------------------------------------------------------

def _stock(mesh, name: str, op: str, xs):
    """The stock XLA lowering of one collective over the same mesh, on
    the same data: [p] per-rank results as numpy."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P
    p = len(xs)
    body = {
        ("allreduce", "sum"): lambda x: lax.psum(x, "x"),
        ("allreduce", "max"): lambda x: lax.pmax(x, "x"),
        ("allgather", None): lambda x: lax.all_gather(
            x.reshape(-1), "x", tiled=True).reshape(1, -1),
        ("alltoall", None): lambda x: lax.all_to_all(
            x.reshape(p, -1), "x", 0, 0).reshape(1, -1),
        ("reduce_scatter_block", "sum"): lambda x: lax.psum_scatter(
            x.reshape(-1), "x", tiled=True).reshape(1, -1),
        # a bcast's ``op`` is its root: the one-hot psum the kernel replaced
        ("bcast", op): lambda x: lax.psum(
            jnp.where(lax.axis_index("x") == op, x, jnp.zeros_like(x)), "x"),
    }[(name, op)]
    g = jax.device_put(np.stack(xs), NamedSharding(mesh, P("x", None)))
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("x", None),),
                              out_specs=P("x", None), check_vma=False))
    return np.asarray(f(g))


def _tag(op) -> str:
    """A case's op, or a bcast's root, as the report prints it."""
    return "" if op is None else str(op)


def _ring_steps_note(name: str, op, nbytes: int, p: int) -> str:
    """How the streaming ring kernel under a float32 call of ``nbytes``
    a shard is written (``pallas_ici.ring_steps``: chunk steps traced,
    chunk steps its loops stand for); nothing for a collective with no
    such kernel or where the one tier rule sends the call elsewhere."""
    from mvapich2_tpu.ops.pallas_ici import planned_tier, ring_steps
    coll = {"allreduce": "allreduce", "reduce": "allreduce",
            "reduce_scatter_block": "reduce_scatter",
            "allgather": "allgather", "bcast": "bcast"}.get(name)
    if coll is None or planned_tier(
            name, nbytes * (p if name == "allgather" else 1), np.float32,
            op if isinstance(op, str) else None, num_devices=p)[0] != "hbm":
        return ""
    steps = ring_steps(coll, nbytes // 4, np.float32, p)
    return (f" | ring steps {steps['steps_traced']} traced, "
            f"{steps['steps_looped']} looped")


def four_chips(seed: int, nranks: int = 4, scale: int = 1) -> None:
    """``scale`` divides the element counts (CPU rehearsal only)."""
    import jax

    from mvapich2_tpu import mpit, run_ranks
    from mvapich2_tpu.core import op as opmod
    from mvapich2_tpu.parallel.mesh import make_mesh

    devs = jax.devices()[:nranks]
    assert len(set(devs)) == nranks, f"need {nranks} devices, have {devs}"
    mesh = make_mesh((nranks,), ("x",), devs)
    K = 1024
    cases = [   # (tag, name, op, bytes per rank)
        (1, "allreduce", "sum", 4 * K), (2, "allreduce", "sum", MiB),
        (3, "allreduce", "sum", 64 * MiB), (4, "allgather", None, 16 * MiB),
        (5, "alltoall", None, 16 * MiB), (6, "allreduce", "max", MiB),
        (7, "reduce_scatter_block", "sum", MiB),
        # a bcast's op is its root: rank 0 and one in mid-ring, at a
        # streaming size (the chain kernel, a program a root)
        (8, "bcast", 0, 32 * MiB), (9, "bcast", 2, 32 * MiB),
    ]
    cases = [(t, n, o, max(nranks * 128 * 4, b // scale))
             for t, n, o, b in cases]
    data = {t: [rank_data(seed, 100 + t, r, b // 4) for r in range(nranks)]
            for t, _n, _o, b in cases}
    results = {t: [None] * nranks for t, *_ in cases}
    homes = [None] * nranks
    report = {}
    bcast_counts = ("dev_coll_tier_hbm", "dev_bc_wire_bytes")

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == "DeviceCollChannel", type(ch).__name__
        dev = ch.device
        homes[comm.rank] = dev
        for t, name, op, nbytes in cases:
            x = jax.device_put(data[t][comm.rank], dev)
            assert x.sharding.device_set == {dev}
            call = {"allreduce": lambda: comm.allreduce(
                        x, op=opmod.MAX if op == "max" else opmod.SUM),
                    "allgather": lambda: comm.allgather(x),
                    "alltoall": lambda: comm.alltoall(x),
                    "reduce_scatter_block":
                        lambda: comm.reduce_scatter_block(x),
                    "bcast": lambda: comm.bcast(x, root=op)}[name]
            if name == "bcast":
                comm.barrier()
                counted = {n: mpit.pvar(n).read() for n in bcast_counts}
            times = []
            for _ in range(1 + STEADY_CALLS):
                comm.barrier()
                t0 = time.perf_counter()
                out = jax.block_until_ready(call())
                times.append(time.perf_counter() - t0)
                assert out.sharding.device_set == {dev}, \
                    (name, comm.rank, out.sharding.device_set)
            if name == "bcast":
                # every call took the chain (the tier pvar, a rank a
                # call) and counted the message once on the root's wire;
                # the senders' buffers go, the results hold
                comm.barrier()
                rose = {n: mpit.pvar(n).read() - v
                        for n, v in counted.items()}
                calls = nranks * (1 + STEADY_CALLS)
                assert rose == {"dev_coll_tier_hbm": calls,
                                "dev_bc_wire_bytes": calls * nbytes}, rose
                comm.barrier()
                x.delete()
            results[t][comm.rank] = np.asarray(out)
            if comm.rank == 0:
                report[t] = (times[0], statistics.median(times[1:]))
                say(f"four chips: ran {name} {_tag(op)} {nbytes} B/rank "
                    f"x {1 + STEADY_CALLS}")
        # a dup of the world (ISSUE 55): a channel of its own over the
        # same mesh, the world's programs. The largest allreduce once
        # more, on it: the ring kernel (the tier pvar), on the derived
        # channel, with nothing lowered again
        t, _name, _op, nbytes = DUP_CASE
        x = jax.device_put(data[t][comm.rank], dev)
        dup = comm.dup()
        assert type(dup.device_channel) is type(ch) \
            and dup.device_channel.derived
        comm.barrier()
        counted = {n: mpit.pvar(n).read() for n in dup_counts}
        comm.barrier()
        t0 = time.perf_counter()
        out = jax.block_until_ready(dup.allreduce(x))
        first = time.perf_counter() - t0
        assert out.sharding.device_set == {dev}
        comm.barrier()
        rose = {n: mpit.pvar(n).read() - v for n, v in counted.items()}
        assert rose == dict.fromkeys(dup_counts, nranks), rose
        dup_results[comm.rank] = np.asarray(out)
        if comm.rank == 0:
            report["dup"] = first
        dup.free()

    DUP_CASE = cases[2]         # allreduce sum at the largest size
    dup_counts = ("dev_coll_tier_hbm", "dev_coll_derived", "coll_level_ici")
    dup_results = [None] * nranks
    before = {n: mpit.pvar(n).read()
              for n in ("dev_coll_tier_vmem", "dev_coll_tier_hbm",
                        "coll_level_ici", "dev_rs_wire_bytes")}
    fb0 = fallback_pvars()
    run_ranks(nranks, app, device_mesh=mesh, timeout=900.0)
    assert len(set(homes)) == nranks, \
        f"the {nranks} shards do not live on {nranks} devices: {homes}"
    say(f"four chips: ranks 0..{nranks - 1} on devices "
        f"{[(d.id, getattr(d, 'coords', None)) for d in homes]} (make_mesh's "
        f"ring order; given {[d.id for d in devs]})")

    for t, name, op, nbytes in cases:
        xs = data[t]
        c = xs[0].size // nranks
        ref = {"allreduce": lambda: [np.sum(xs, axis=0) if op == "sum"
                                     else np.max(xs, axis=0)] * nranks,
               "allgather": lambda: [np.concatenate(xs)] * nranks,
               "alltoall": lambda: [np.concatenate(
                   [xs[s][r * c:(r + 1) * c] for s in range(nranks)])
                   for r in range(nranks)],
               "reduce_scatter_block": lambda: list(
                   np.sum(xs, axis=0).reshape(nranks, c)),
               "bcast": lambda: [xs[op]] * nranks}[name]()
        stock = _stock(mesh, name, op, xs)
        for r in range(nranks):
            got = results[t][r]
            if not np.array_equal(got, ref[r]):
                raise AssertionError(f"{name} {op} {nbytes} B: rank {r} "
                                     f"differs from the numpy reference")
            if not np.array_equal(got, stock[r].reshape(got.shape)):
                raise AssertionError(f"{name} {op} {nbytes} B: rank {r} "
                                     f"differs from the stock lowering")
        first, steady = report[t]
        say(f"four chips: {name} {_tag(op):<3} {nbytes:>9} B/rank  "
            f"bit-equal to numpy and to the stock XLA lowering on "
            f"{nranks} ranks | first call {first:.3f} s (compile), steady "
            f"{steady * 1e3:.3f} ms/call (smoke timing)"
            + _ring_steps_note(name, op, nbytes, nranks))
    total = np.sum(data[DUP_CASE[0]], axis=0)
    for r in range(nranks):
        if not np.array_equal(dup_results[r], total):
            raise AssertionError(f"allreduce on a dup of the world: rank {r} "
                                 f"differs from the numpy reference")
    say(f"four chips: allreduce sum {DUP_CASE[3]:>9} B/rank on comm.dup(): "
        f"bit-equal to numpy, the ring kernel on the derived channel | "
        f"first call {report['dup'] * 1e3:.3f} ms (the world's program: "
        f"smoke timing)")
    rose = {n: mpit.pvar(n).read() - v for n, v in before.items()}
    fb = {n: v - fb0[n] for n, v in fallback_pvars().items()}
    say(f"proof: {rose}; fallbacks {fb}")
    assert rose["dev_coll_tier_vmem"] > 0 and rose["dev_coll_tier_hbm"] > 0
    assert rose["coll_level_ici"] == \
        nranks * (len(cases) * (1 + STEADY_CALLS) + 1)
    # the reduce-scatter's calls took the ring kernel, which counts what
    # it sends (three quarters of the send buffer at 1 MiB: whole tiles)
    from mvapich2_tpu.ops.pallas_ici import reduce_scatter_wire_bytes
    sent = sum(reduce_scatter_wire_bytes(b // 4, np.float32, nranks)
               for _t, n, _o, b in cases if n == "reduce_scatter_block")
    assert rose["dev_rs_wire_bytes"] == \
        nranks * (1 + STEADY_CALLS) * sent > 0, rose
    assert fb and not any(fb.values()), fb


def fold_phase(seed: int, nranks: int = 8, ndev: int = 4,
               nbytes: int = 32 * MiB) -> None:
    """Two ranks a chip: ``run_ranks(8, app, device_mesh=<four chips>)``
    binds ``DeviceFoldChannel``; its five supported collectives once
    each on device-resident buffers, against numpy (``nbytes`` a rank:
    a streaming size for all four reductions, at which the ring folds
    both deposits in rounds long enough to loop; a CPU rehearsal passes
    a smaller one)."""
    import jax

    from mvapich2_tpu import mpit, run_ranks
    from mvapich2_tpu.core import op as opmod
    from mvapich2_tpu.parallel.mesh import make_mesh

    devs = jax.devices()[:ndev]
    assert len(set(devs)) == ndev, f"need {ndev} devices, have {devs}"
    mesh = make_mesh((ndev,), ("x",), devs)
    k, n = nranks // ndev, nbytes // 4
    c = n // nranks
    root = nranks - 3           # not a chip's first rank, not chip 0
    names = ("allreduce", "allreduce max", "allgather",
             "reduce_scatter_block", "bcast", "reduce")
    xs = [rank_data(seed, 200, r, n) for r in range(nranks)]
    total = np.sum(xs, axis=0)
    refs = {"allreduce": [total] * nranks,
            "allreduce max": [np.max(xs, axis=0)] * nranks,
            "allgather": [np.concatenate(xs)] * nranks,
            "reduce_scatter_block": [total[r * c:(r + 1) * c]
                                     for r in range(nranks)],
            "bcast": [xs[root]] * nranks,
            "reduce": [total if r == root else None for r in range(nranks)]}
    results = {name: [None] * nranks for name in names}
    homes = [None] * nranks
    took = {}

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == "DeviceFoldChannel", type(ch).__name__
        assert (ch.k, ch.ndev) == (k, ndev), (ch.k, ch.ndev)
        dev = homes[comm.rank] = ch.device
        x = jax.device_put(xs[comm.rank], dev)
        calls = {"allreduce": lambda: comm.allreduce(x),
                 "allreduce max": lambda: comm.allreduce(x, op=opmod.MAX),
                 "allgather": lambda: comm.allgather(x),
                 "reduce_scatter_block":
                     lambda: comm.reduce_scatter_block(x),
                 "bcast": lambda: comm.bcast(x, root=root),
                 "reduce": lambda: comm.reduce(x, root=root)}
        for name in names:
            comm.barrier()
            t0 = time.perf_counter()
            out = calls[name]()
            if out is None:         # reduce, off the root
                continue
            out = jax.block_until_ready(out)
            if comm.rank == root:
                took[name] = time.perf_counter() - t0
            assert out.sharding.device_set == {dev}, \
                (name, comm.rank, out.sharding.device_set)
            results[name][comm.rank] = np.asarray(out)
            if comm.rank == 0:
                say(f"fold: ran {name} {nbytes} B/rank")
        # a dup of the world (ISSUE 55): the fold channel once more over
        # the same mesh, the world's fused program
        dup = comm.dup()
        assert type(dup.device_channel) is type(ch) \
            and dup.device_channel.derived
        out = jax.block_until_ready(dup.allreduce(x))
        assert out.sharding.device_set == {dev}
        on_dup[comm.rank] = np.asarray(out)
        dup.free()

    on_dup = [None] * nranks
    levels = ("coll_level_chip", "coll_level_ici")
    # level 1 of the four reductions rides in the mesh program, one
    # launch a call, and in the ring kernel itself where the one tier
    # rule sends the call down the streaming ring (whole-tile blocks at
    # these sizes); allgather alone still copies a chip's deposits
    from mvapich2_tpu.ops.pallas_ici import planned_tier, ring_folds
    in_ring = sum(
        ring_folds(planned_tier(coll, nbytes, np.float32, op,
                                num_devices=ndev)[0], n, np.float32, ndev)
        for coll, op in (("allreduce", "sum"), ("allreduce", "max"),
                         ("reduce_scatter_block", "sum"), ("reduce", "sum")))
    assert in_ring >= 2, in_ring    # the max and the reduce-scatter stream
    # the allreduce on the dup folds as the world's first one did
    dup_in_ring = int(ring_folds(planned_tier(
        "allreduce", nbytes, np.float32, "sum", num_devices=ndev)[0],
        n, np.float32, ndev))
    folds = {"dev_fold_fused": 5, "dev_fold_in_ring": in_ring + dup_in_ring,
             "dev_fold_operands": 5, "dev_fold_stacked": ndev,
             "dev_coll_derived": nranks}
    before = {n_: mpit.pvar(n_).read() for n_ in levels + tuple(folds)}
    fb0 = fallback_pvars()
    run_ranks(nranks, app, device_mesh=mesh, timeout=900.0)
    assert len(set(homes)) == ndev and \
        all(homes[r] == homes[r - r % k] for r in range(nranks)), homes
    for name in names:
        for r in range(nranks):
            got, want = results[name][r], refs[name][r]
            if (got is None) != (want is None) or \
                    (want is not None and not np.array_equal(got, want)):
                raise AssertionError(f"fold {name}: rank {r} differs from "
                                     f"the numpy reference")
        coll, _, op = name.partition(" ")
        say(f"fold: {name:<20} {nbytes:>8} B/rank  bit-equal to numpy on "
            f"{nranks} ranks over {ndev} chips | first call "
            f"{took[name]:.3f} s (compile)"
            # the reductions' program is the ring over the chips (a
            # gather or a broadcast moves the chips' stacked deposits)
            + (_ring_steps_note(coll, op or "sum", nbytes, ndev)
               if coll.startswith(("allreduce", "reduce")) else ""))
    for r in range(nranks):
        if not np.array_equal(on_dup[r], total):
            raise AssertionError(f"fold allreduce on a dup of the world: "
                                 f"rank {r} differs from the numpy reference")
    say(f"fold: allreduce on comm.dup()  {nbytes:>8} B/rank  bit-equal to "
        f"numpy on the derived channel")
    rose = {n_: mpit.pvar(n_).read() - v for n_, v in before.items()}
    fb = {n_: v - fb0[n_] for n_, v in fallback_pvars().items()}
    say(f"proof (fold): {rose}; fallbacks {fb}")
    assert all(rose[lv] == nranks * (len(names) + 1) for lv in levels), rose
    assert all(rose[n_] == v for n_, v in folds.items()), rose
    assert fb and not any(fb.values()), fb


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the four-chip ICI and fold phases and nothing else")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    from mvapich2_tpu.utils.compile_cache import (cache_entries,
                                                  ensure_compile_cache)
    cache_dir = ensure_compile_cache()
    devs = jax.devices()
    d0 = devs[0]
    libtpu = "n/a"
    if d0.platform == "tpu":
        libtpu = getattr(d0.client, "platform_version", "?").replace(
            "\n", " ")
    n0 = cache_entries(cache_dir)
    say(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)} | jax {jax.__version__} jaxlib "
        f"{jaxlib.__version__} | libtpu {libtpu}")
    say(f"compile cache: {cache_dir or '(none placed: CPU asked for)'} "
        f"({n0} entries before)")
    if d0.platform != "tpu":
        say("chip_smoke: jax found no TPU; nothing was checked")
        return 1
    if len(devs) != args.chips:
        say(f"chip_smoke: --chips {args.chips} but jax reports "
            f"{len(devs)} devices")
        return 1

    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed)
        fold_phase(args.seed)
        pt2pt_lane(args.seed, d2d=2)
    else:
        one_chip(args.seed)
    say(f"compile cache: {cache_dir} ({cache_entries(cache_dir)} entries "
        f"after, {n0} before)")
    say(f"chip_smoke: all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
