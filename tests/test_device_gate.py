"""The blocking device collectives' meeting point (coll/device.py:_Gate):
the leader alone waits for the ranks to arrive, the others wait in one
line and leave it first in, first out (last in, first out where the
ranks share one device); a broken gate raises under
whoever waits or comes later; and through the front door every rank of
a call gets that call's result, call after call."""

import threading
import time

import numpy as np
import pytest

from mvapich2_tpu import run_ranks
from mvapich2_tpu.coll.device import _Gate, _Rendezvous

R = 8


def _until(cond, what, timeout=10.0):
    end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < end, f"timed out waiting for {what}"
        time.sleep(0.001)


def _in_line(gate, ranks, left, errors, turns=None):
    """Start one waiting thread a rank, each only after the one before
    it stands in the line, so the line's order is ``ranks``; ``turns``
    takes what ``leave`` told each rank."""
    def waiter(rank):
        try:
            gate.arrive(rank, False)
            turn = gate.leave(rank)
            left.append(rank)
            if turns is not None:
                turns[rank] = turn
        except threading.BrokenBarrierError:
            errors.append(rank)
    threads = []
    for k, rank in enumerate(ranks):
        t = threading.Thread(target=waiter, args=(rank,), daemon=True)
        t.start()
        threads.append(t)
        _until(lambda: gate.n_waiting == k + 1, f"rank {rank} to arrive")
    return threads


@pytest.mark.parametrize("order", [
    (1, 2, 3, 4, 5, 6, 7), (7, 6, 5, 4, 3, 2, 1), (4, 1, 7, 2, 6, 3, 5),
    (2, 1), (1,)], ids=lambda o: "-".join(map(str, o)))
@pytest.mark.parametrize("last_first", [False, True],
                         ids=["first_in_first_out", "last_first"])
def test_ranks_leave_in_the_order_they_came(order, last_first):
    """... or, through the slot channel's ``last_first`` gate (ISSUE
    50), in the reverse of it; one at a time either way, and ``leave``
    tells each rank its place in the line let go (ISSUE 53)."""
    gate = _Gate(len(order) + 1, last_first)
    left, errors, turns = [], [], {}
    threads = _in_line(gate, order, left, errors, turns)
    assert left == []               # nobody leaves before the leader opens
    gate.arrive(0, True)            # the last to arrive: does not wait
    gate.open()
    for t in threads:
        t.join(10)
    assert left == list(order)[::-1 if last_first else 1] and errors == []
    assert turns == {rank: turn for turn, rank in enumerate(left)}
    assert gate.n_waiting == 0 and not gate.broken


@pytest.mark.parametrize("binding,last_first", [
    ("slot", True), ("mesh", False), ("fold", False)])
def test_only_the_one_device_binding_lets_go_last_first(binding,
                                                        last_first):
    """Ranks that share one device wait on one completion, which the
    runtime hands to its waiters last come, first served; ranks on
    devices of their own do not: their gates stay first in, first
    out."""
    import jax
    from mvapich2_tpu.parallel.mesh import make_mesh
    ranks, ndev = {"slot": (4, 1), "mesh": (4, 4), "fold": (8, 4)}[binding]
    seen = []

    def app(comm):
        seen.append(comm.device_channel.rv.gate.last_first)

    run_ranks(ranks, app, device_mesh=make_mesh(
        (ndev,), ("x",), jax.devices()[:ndev]))
    assert seen == [last_first] * ranks


def test_the_leader_waits_for_the_last_rank():
    gate = _Gate(3)
    through = []
    leader = threading.Thread(
        target=lambda: (gate.arrive(0, True), through.append(0)), daemon=True)
    leader.start()
    _until(lambda: gate.n_waiting == 1, "the leader to arrive")
    gate.arrive(1, False)           # returns at once: only the leader waits
    time.sleep(0.05)
    assert through == [] and gate.n_waiting == 2
    gate.arrive(2, False)
    leader.join(10)
    assert through == [0]


def test_a_gate_of_one_rank_never_blocks():
    gate = _Gate(1)
    for _ in range(3):
        gate.arrive(0, True)
        gate.open()
    assert gate.n_waiting == 0


@pytest.mark.parametrize("who_waits", ["leader", "line", "both"])
def test_abort_raises_under_whoever_waits_and_whoever_comes_later(who_waits):
    gate = _Gate(4)
    left, errors = [], []
    threads = []
    if who_waits in ("line", "both"):
        threads += _in_line(gate, (2, 1), left, errors)
    if who_waits in ("leader", "both"):
        def leader():
            try:
                gate.arrive(0, True)
                left.append(0)
            except threading.BrokenBarrierError:
                errors.append(0)
        had = gate.n_waiting
        t = threading.Thread(target=leader, daemon=True)
        t.start()
        threads.append(t)
        _until(lambda: gate.n_waiting == had + 1, "the leader to arrive")
    gate.abort()
    for t in threads:
        t.join(10)
        assert not t.is_alive()
    want = {"leader": [0], "line": [1, 2], "both": [0, 1, 2]}[who_waits]
    assert sorted(errors) == want and left == [] and gate.broken
    with pytest.raises(threading.BrokenBarrierError):
        gate.arrive(3, False)
    with pytest.raises(threading.BrokenBarrierError):
        gate.open()


def test_abort_after_the_line_was_let_go_leaves_the_chain_whole():
    """The line the leader opened is no longer the gate's to release: an
    abort then breaks the gate and every rank of the chain still wakes,
    once."""
    gate = _Gate(4)
    left, errors = [], []
    threads = _in_line(gate, (3, 1, 2), left, errors)
    gate.arrive(0, True)
    gate.open()
    gate.abort()
    for t in threads:
        t.join(10)
        assert not t.is_alive()
    assert sorted(left + errors) == [1, 2, 3]
    assert all(sem.locked() for sem in gate._sems[1:])


def test_no_rank_passes_a_round_the_leader_has_not_opened():
    """2 000 rounds of eight threads with no work between them: a rank
    that got out early, twice, or not at all would show as a count that
    is not the round's."""
    gate = _Gate(R)
    rounds, opened, bad = 2000, [0], []

    def rank_thread(rank):
        for i in range(rounds):
            gate.arrive(rank, rank == 0)
            if rank == 0:
                opened[0] = i + 1
                gate.open()
            else:
                gate.leave(rank)
                if opened[0] != i + 1:   # the next needs this rank in
                    bad.append((rank, i, opened[0]))
    threads = [threading.Thread(target=rank_thread, args=(r,), daemon=True)
               for r in range(R)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    assert bad == [] and opened[0] == rounds and gate.n_waiting == 0


def test_the_rendezvous_holds_one_gate_and_abort_breaks_it():
    rv = _Rendezvous(4)
    assert isinstance(rv.gate, _Gate) and rv.gate.size == 4
    rv.abort()
    assert rv.gate.broken and rv.nb_failed


@pytest.mark.parametrize("ranks,mesh", [(8, True), (4, "mesh")],
                         ids=["slot", "mesh"])
def test_every_call_hands_every_rank_that_calls_result(ranks, mesh):
    """Fifty calls back to back on other data each time: what a rank
    gets is this call's sum, never the call's before or after."""
    import jax
    from mvapich2_tpu.parallel.mesh import make_mesh
    if mesh == "mesh":
        mesh = make_mesh((ranks,), ("x",), jax.devices()[:ranks])
    wrong = []

    def app(comm):
        for i in range(50):
            x = np.full(256, float(i * ranks + comm.rank), np.float32)
            got = np.asarray(comm.allreduce(jax.device_put(
                x, comm.device_channel.device)))
            want = float(sum(i * ranks + r for r in range(ranks)))
            if not (got == want).all():
                wrong.append((comm.rank, i, float(got[0]), want))

    run_ranks(ranks, app, device_mesh=mesh)
    assert wrong == []
