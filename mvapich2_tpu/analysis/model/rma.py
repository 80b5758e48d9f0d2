"""Passive-target epoch model of the one-sided RMA engine
(ops/pallas_rma.py + rma/device.py).

The lock/flush/unlock grammar and the target-side fold pipeline of the
device RMA lane have never run against an adversarial interleaving:
an interpreter (or chip) run executes the credit handshake but sees one
interleaving of it, so such runs validate the data schedule, not every
order of the sync grammar the hardware path depends on. This model is that
grammar's verification net — the one-sided sibling of the ici
chunk-credit model.

The protocol, reduced to its sync skeleton: an **origin** opens an
exclusive passive epoch on the target (MPI_Win_lock), streams C
accumulate chunks through the D-credit slot schedule, flushes (the
completion wave: every fold committed, credit balance restored —
``_RmaStreamer.finish()``), and unlocks. At the target a **folder**
(the target-side agent of the origin's epoch — the DMA landings plus
the VPU fold) processes each landed chunk in two phases, exactly the
kernel's shape: *begin* captures the window operand and computes the
fold (the ``pending_fold`` prefetch + VPU add), *end* commits the
result to the window cell and re-grants the slot credit (the
``pending_store`` wave). Between begin and end the cell is mid-commit:
a concurrent load would tear. A local **reader** at the target takes
the same lock, loads every window cell, and unlocks — the
"concurrent Put + local load" pair of the no-torn-read contract.

What the model proves (exhaustively, within C x D x W bounds):

  * **lock-exclusive** — the origin's passive epoch and the local
    reader never hold the window lock simultaneously;
  * **no-torn-window-read** — the reader never loads a cell while a
    fold commit is in flight on it (the lock + flush grammar is what
    makes this true; there is no per-element interlock);
  * **flush-completes-all-outstanding** — when flush returns, every
    issued chunk's fold has committed and the credit balance is back
    to D (the MPI_Win_flush contract on the chunk-credit wave);
  * **acc-atomicity** — once all folds committed, every window cell
    equals the exact sum of its contributions: no fold ever captured a
    stale operand (read-modify-write per element is atomic);
  * **no-deadlock** — the epoch always completes (explorer built-in).

Mutations (tests/test_modelcheck.py asserts every one is caught by a
named invariant):

  flush_skips_chunk    flush's completion wave waits one chunk short
                       (the finish() loop dropping a pending handle) —
                       flush returns with a fold outstanding
  unlock_before_drain  unlock released before the completion wave (the
                       epoch grammar inverted) — the reader acquires a
                       legitimately free lock and tears a mid-commit
                       cell
  no_target_fold_order the fold of chunk c+1 captures its window
                       operand before chunk c's commit landed (the
                       ``prev_st.wait()`` slot-reuse wait dropped) —
                       a lost update: the cell misses a contribution
  torn_window_read     the local load bypasses the lock protocol
                       entirely (a raw shard read outside the epoch
                       grammar) — it tears a mid-commit cell
  no_lock_wait         the reader's lock acquire ignores the holder
                       (the exclusivity guard dropped) — both sides
                       inside the epoch at once

Payloads are distinct integers (chunk c contributes c+1), so a lost
update or stale fold is visible in the final cell sums, and a torn
cell is the seqlock model's TORN sentinel.
"""

from __future__ import annotations

from typing import Optional

from .explorer import Model, Transition
from .seqlock import TORN


def build_passive(chunks: int = 3, depth: int = 2, cells: int = 1,
                  mutation: Optional[str] = None) -> Model:
    """One origin streams ``chunks`` accumulate chunks (chunk c lands
    in window cell c % ``cells``) through a ``depth``-credit slot
    schedule inside a lock/flush/unlock passive epoch, against a
    concurrent local reader of every cell.

    Note: the ``no_target_fold_order`` stale-operand race needs
    ``depth > cells`` — with depth <= cells the credit schedule itself
    keeps two folds of the same cell from being in flight at once, so
    the dropped slot-reuse wait is masked. The default bounds
    (C=3, D=2, W=1) expose it."""
    assert chunks >= 1 and depth >= 1 and cells >= 1
    C, D, W = chunks, depth, min(cells, chunks)

    def cell(c: int) -> int:
        return c % W

    expected = [sum(c + 1 for c in range(C) if cell(c) == w)
                for w in range(W)]

    # origin program: lock, issue 0..C-1, flush, unlock — the mutant
    # inverts the last two (unlock before the completion wave)
    prog = ["lock"] + [("issue", c) for c in range(C)]
    if mutation == "unlock_before_drain":
        prog += ["unlock", "flush"]
    else:
        prog += ["flush", "unlock"]
    flush_idx = prog.index("flush")
    # chunks issued once the origin program counter has passed step i
    issued_at = [0]
    for step in prog:
        issued_at.append(issued_at[-1]
                         + (1 if isinstance(step, tuple) else 0))

    init = {"opc": 0, "rpc": 0, "lo": False, "lr": False, "cr": D,
            "begun": 0, "ended": 0, "res": ()}
    for w in range(W):
        init[f"val{w}"] = 0
    for c in range(C):
        init[f"tmp{c}"] = None

    ts = []

    # ---- origin --------------------------------------------------------
    for i, step in enumerate(prog):
        def mk(i=i, step=step):
            if step == "lock":
                def guard(s):
                    return s["opc"] == i and not s["lo"] and not s["lr"]

                def apply(s):
                    s["lo"] = True
                    s["opc"] = i + 1
                    return s
                return Transition(f"o.lock", "origin", guard, apply,
                                  frozenset({"opc", "lo", "lr"}),
                                  frozenset({"opc", "lo"}))
            if step == "unlock":
                def guard(s):
                    return s["opc"] == i and s["lo"]

                def apply(s):
                    s["lo"] = False
                    s["opc"] = i + 1
                    return s
                return Transition(f"o.unlock", "origin", guard, apply,
                                  frozenset({"opc", "lo"}),
                                  frozenset({"opc", "lo"}))
            if step == "flush":
                def guard(s):
                    if s["opc"] != i:
                        return False
                    if mutation == "flush_skips_chunk":
                        # MUTANT: the completion wave drops one pending
                        # handle — returns a chunk short
                        return s["ended"] >= C - 1
                    return s["ended"] == C and s["cr"] == D

                def apply(s):
                    s["opc"] = i + 1
                    return s
                return Transition(f"o.flush", "origin", guard, apply,
                                  frozenset({"opc", "ended", "cr"}),
                                  frozenset({"opc"}))
            _t, c = step

            def guard(s):
                return s["opc"] == i and s["cr"] > 0

            def apply(s):
                s["cr"] -= 1       # the slot credit of the remote DMA
                s["opc"] = i + 1
                return s
            return Transition(f"o.issue{c}", "origin", guard, apply,
                              frozenset({"opc", "cr"}),
                              frozenset({"opc", "cr"}))
        ts.append(mk())

    # ---- the target-side folder (DMA landings + VPU fold) --------------
    for c in range(C):
        def mkb(c=c):
            vw = f"val{cell(c)}"

            def guard(s):
                if s["begun"] != c or issued_at[s["opc"]] <= c:
                    return False
                if mutation == "no_target_fold_order":
                    return True   # MUTANT: operand prefetch skips the
                    #               previous commit's slot-reuse wait
                return s["ended"] == s["begun"]   # strictly sequential

            def apply(s):
                # capture the committed operand + compute the fold
                s[f"tmp{c}"] = s[vw] + (c + 1)
                s["begun"] = c + 1
                return s
            return Transition(f"f.begin{c}", "folder", guard, apply,
                              frozenset({"begun", "ended", "opc", vw}),
                              frozenset({"begun", f"tmp{c}"}))

        def mke(c=c):
            vw = f"val{cell(c)}"

            def guard(s):
                return s["ended"] == c and s["begun"] > c

            def apply(s):
                s[vw] = s[f"tmp{c}"]   # the commit store lands
                s["ended"] = c + 1
                s["cr"] += 1           # re-grant the slot credit
                return s
            return Transition(f"f.end{c}", "folder", guard, apply,
                              frozenset({"begun", "ended", f"tmp{c}"}),
                              frozenset({"ended", "cr", vw}))
        ts.append(mkb())
        ts.append(mke())

    # ---- the local reader ----------------------------------------------
    # program: lock, read cell 0..W-1, unlock. torn_window_read bypasses
    # the lock protocol entirely (raw loads outside the epoch grammar).
    bypass = mutation == "torn_window_read"

    def r_lock_guard(s):
        if s["rpc"] != 0:
            return False
        if bypass or mutation == "no_lock_wait":
            return True        # MUTANT: no exclusivity wait
        return not s["lo"] and not s["lr"]

    def r_lock_apply(s):
        if not bypass:
            s["lr"] = True
        s["rpc"] = 1
        return s
    ts.append(Transition("r.lock", "reader", r_lock_guard, r_lock_apply,
                         frozenset({"rpc", "lo", "lr"}),
                         frozenset({"rpc", "lr"})))

    for w in range(W):
        def mkr(w=w):
            vw = f"val{w}"

            def guard(s):
                return s["rpc"] == 1 + w

            def apply(s):
                # a cell is mid-commit while any fold targeting it has
                # begun and not ended — a concurrent load tears
                mid = any(cell(c) == w
                          for c in range(s["ended"], s["begun"]))
                s["res"] = s["res"] + (TORN if mid else s[vw],)
                s["rpc"] = 2 + w if w < W - 1 else W + 1
                return s
            return Transition(f"r.read{w}", "reader", guard, apply,
                              frozenset({"rpc", "begun", "ended", vw}),
                              frozenset({"rpc", "res"}))
        ts.append(mkr())

    def r_unlock_guard(s):
        return s["rpc"] == W + 1 and (bypass or s["lr"])

    def r_unlock_apply(s):
        if not bypass:
            s["lr"] = False
        s["rpc"] = W + 2
        return s
    ts.append(Transition("r.unlock", "reader", r_unlock_guard,
                         r_unlock_apply, frozenset({"rpc", "lr"}),
                         frozenset({"rpc", "lr"})))

    # ---- invariants ----------------------------------------------------
    def inv_lock(s):
        if s["lo"] and s["lr"]:
            return ("origin's passive epoch and the local reader hold "
                    "the window lock simultaneously")
        return None

    def inv_torn(s):
        for i, v in enumerate(s["res"]):
            if v is TORN or v == TORN:
                return (f"local load {i} tore a mid-commit window cell "
                        "(fold commit in flight)")
        return None

    def inv_flush(s):
        if s["opc"] > flush_idx:
            if s["ended"] != C:
                return (f"flush returned with {C - s['ended']} fold(s) "
                        "outstanding — MPI_Win_flush must complete all "
                        "outstanding ops")
            if s["cr"] != D:
                return (f"flush returned with credit balance {s['cr']} "
                        f"!= depth {D}")
        return None

    def inv_atomic(s):
        if s["ended"] == C:
            for w in range(W):
                if s[f"val{w}"] != expected[w]:
                    return (f"window cell {w} holds {s[f'val{w}']} != "
                            f"exact sum {expected[w]} — a fold captured "
                            "a stale operand (lost update)")
        return None

    end_o, end_r = len(prog), W + 2

    def final(s):
        return (s["opc"] == end_o and s["rpc"] == end_r
                and s["ended"] == C)

    label = (f"rma-passive(C={C},D={D},W={W},mut={mutation})")
    return Model(label, init, ts,
                 [("lock-exclusive", inv_lock),
                  ("no-torn-window-read", inv_torn),
                  ("flush-completes-all-outstanding", inv_flush),
                  ("acc-atomicity", inv_atomic)],
                 final)
