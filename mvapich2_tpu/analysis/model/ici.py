"""Chunk-credit model of the HBM-streaming ICI ring (ops/pallas_ici.py).

The credit handshake of the chunked remote-DMA engine runs under the
TPU interpreter and on the chip (PR 22), but each such run sees one
interleaving. This model is the handshake's exhaustive verification
net — the device analog of
the seqlock/doorbell/lease models PR 7 built for the host shm
protocols.

The protocol, reduced to its transport skeleton: each rank streams C
chunks per ring direction into its downstream neighbor's D-deep VMEM
slot array, the slot sequence driven by a single **global chunk counter
per direction** — write ``k`` lands in slot ``k % D``, which is exactly
the slot freed by consume ``k - D`` ("write k+D lands in the slot freed
by consume k"). Flow control is ``D`` credits per direction: the sender
takes a credit before the remote DMA of chunk ``k`` and the receiver
re-grants one as it consumes a slot, so a sender runs at most ``D``
chunks ahead and slot reuse needs no per-slot handshake.

Each rank executes the *serialized* program ``stream_step`` actually
runs (one instruction stream per kernel instance): per chunk index
``c`` it issues ``c`` on every direction, then drains ``c-1`` on every
direction. Concurrency comes from rank interleaving and, under the
``signal_before_copy`` mutation, from the split-landing DMA actor. The
clean model lands payload + recv-semaphore signal atomically at issue
time — signal-after-data is a hardware guarantee, and landing as early
as possible is adversarial for the collision invariant (a later landing
only gives the consumer more time), so the abstraction is sound.

What the model proves (exhaustively, within N x C x D bounds, uni- and
bidirectional):

  * **no-slot-collision** — no remote write ever lands in a slot whose
    previous chunk is unconsumed;
  * **no-lost-credit** — per (sender, direction), credits held plus
    chunks in flight always equals exactly D (no leak, no over-grant);
  * **agreement** — every delivered chunk is exactly the upstream
    contribution for that index: no tears, no stale slots, no
    cross-direction mixing;
  * **no-deadlock** — the wave always completes (explorer built-in).

What it cannot prove: the VPU fold arithmetic and the multi-round
reduce-scatter block rotation (interpreter-proven, and bit-exact on
four v5e chips — chip_smoke.py --chips 4), and Mosaic's lowering of the
semaphore ops themselves (tests/test_chip_compile.py asks the chip's
compiler).

Mutations (tests/test_modelcheck.py asserts every one is caught by a
named invariant):

  no_credit_wait        the sender skips the credit take — it runs past
                        D chunks ahead and overwrites an unconsumed slot
  slot_off_by_one       writes land in slot (k+1) % D — the receiver
                        waits forever on slot k % D (the one-counter
                        slot discipline, broken)
  depth_mismatch        sender boots with D+1 credits against D slots
                        (a chunk/depth retune applied to one side only)
  signal_before_copy    recv semaphore signaled before the payload
                        lands — the receiver folds a torn chunk
  bidir_shared_slot     both ring directions mapped onto one slot array
                        (the bidir lanes must be disjoint)
  recv_before_send_wave the receiver consumes without waiting the recv
                        semaphore — it folds a stale/empty slot
  scale_after_payload   (quant wire only) the block scale word lands
                        AFTER the packed codes + recv signal — the
                        receiver dequant-folds with a stale scale,
                        outside the declared block-quant bound

Quantized wire variant (``quant=True`` — ops/pallas_quant.py): each
wire chunk carries a block scale word plus the packed code payload,
and the consumer dequant-folds at drain. The slot/credit schedule is
byte-count-blind, so the shrunken wire chunks (~3.9x smaller than the
f32 chunks they encode) ride the SAME transitions — the clean quant
model proves no-slot-collision / no-lost-credit / no-deadlock hold
unchanged, and the agreement invariant tightens to "every delivered
chunk decodes with exactly its sender's scale word", i.e. within the
declared block-quant bound of the exact fold. The clean model lands
scale + codes + signal atomically (one remote DMA of one wire run —
the packed-single-buffer design choice this model justifies);
``scale_after_payload`` is the seeded break of that atomicity, the
bug a two-buffer scale/payload wire would actually have.
"""

from __future__ import annotations

from typing import Optional

from .explorer import Model, Transition
from .seqlock import TORN

_FREE = -1     # slot occupant sentinel: never written


def _program(C: int, dirs):
    """The serialized per-rank instruction stream of stream_step:
    issue c on every direction, then drain c-1; trailing drains of the
    last chunk close the wave. Since ISSUE 54 the kernel writes a long
    round as its first ``D`` steps, one loop over groups of ``D`` steps
    and a tail (pallas_ici ``_looped_steps``); the chip executes the
    same instructions in this same order on the same slots, so the
    stream modelled here is still the one that runs."""
    prog = []
    for c in range(C):
        for d in dirs:
            prog.append(("issue", c, d))
        if c >= 1:
            for d in dirs:
                prog.append(("drain", c - 1, d))
    for d in dirs:
        prog.append(("drain", C - 1, d))
    return prog


def build_ring(n: int = 2, chunks: int = 2, depth: int = 2,
               bidir: bool = False,
               mutation: Optional[str] = None,
               quant: bool = False) -> Model:
    """``n`` ranks stream ``chunks`` chunks per direction through
    ``depth``-deep slot arrays with ``depth`` credits. ``bidir`` adds
    the counter-clockwise lane (disjoint slots/credits — except under
    the ``bidir_shared_slot`` mutation, where both lanes share array 0
    at every receiver). ``quant`` switches the wire chunk to the
    block-quantized form (scale word + packed codes, dequant-fold at
    consume; see module docstring)."""
    assert n >= 2 and chunks >= 1 and depth >= 1
    C, D = chunks, depth
    dirs = (0, 1) if bidir else (0,)
    if mutation == "bidir_shared_slot":
        assert bidir, "bidir_shared_slot needs the ccw lane"
    if mutation == "scale_after_payload":
        quant = True       # the mutation only exists on the quant wire
    prog = _program(C, dirs)
    # issued/drained counts per (pc, dir) — for the credit invariant
    issued_at = [dict.fromkeys(dirs, 0)]
    drained_at = [dict.fromkeys(dirs, 0)]
    for op, _c, d in prog:
        ni = dict(issued_at[-1])
        nd = dict(drained_at[-1])
        (ni if op == "issue" else nd)[d] += 1
        issued_at.append(ni)
        drained_at.append(nd)

    def dst(r: int, d: int) -> int:
        return (r + 1) % n if d == 0 else (r - 1 + n) % n

    def up(r: int, d: int) -> int:
        return (r - 1 + n) % n if d == 0 else (r + 1) % n

    def slot_arr(d: int) -> int:
        # the mutant collapses both lanes onto one receiver array
        return 0 if mutation == "bidir_shared_slot" else d

    arrays = sorted({slot_arr(d) for d in dirs})

    init = {"collision": 0}
    for r in range(n):
        init[f"pc{r}"] = 0
        for d in dirs:
            init[f"cr{r}_{d}"] = D + 1 if mutation == "depth_mismatch" \
                else D                    # credits held by the sender
            init[f"wp{r}_{d}"] = None     # in-flight write (mutant only)
            init[f"res{r}_{d}"] = ()      # delivered payloads, in order
        for a in arrays:
            for s in range(D):
                # (occupant chunk, payload, signaled, consumed)
                init[f"sl{r}_{a}_{s}"] = (_FREE, frozenset(), False, True)

    def payload(r: int, k: int, d: int) -> frozenset:
        if quant:
            # the quant wire chunk: block scale word + packed codes —
            # both must be the sender's for chunk k, or the dequant
            # fold is outside the declared block-quant bound
            return frozenset({("s", r, k, d), ("q", r, k, d)})
        return frozenset({(r, k, d)})

    ts = []
    for r in range(n):
        for i, (op, c, d) in enumerate(prog):
            def mk(r=r, i=i, op=op, c=c, d=d):
                pc = f"pc{r}"
                peer, upr = dst(r, d), up(r, d)
                a = slot_arr(d)
                cr, wp = f"cr{r}_{d}", f"wp{r}_{d}"
                res = f"res{r}_{d}"
                t = (c + 1) % D if mutation == "slot_off_by_one" \
                    else c % D
                wkey = f"sl{peer}_{a}_{t}"          # issue target
                rkey = f"sl{r}_{a}_{c % D}"          # drain source

                if op == "issue":
                    def guard(s):
                        if s[pc] != i or s[wp] is not None:
                            return False
                        if mutation == "no_credit_wait":
                            return True
                        return s[cr] > 0

                    def apply(s):
                        if mutation != "no_credit_wait":
                            s[cr] -= 1
                        occ, pay, sig, cons = s[wkey]
                        if not cons:
                            s["collision"] = 1       # sticky
                        if mutation == "signal_before_copy":
                            # MUTANT: hand-rolled signal before the
                            # payload is on the wire — readable TORN
                            s[wkey] = (c, TORN, True, False)
                            s[wp] = c
                        elif mutation == "scale_after_payload":
                            # MUTANT: packed codes + recv signal land
                            # first, the block scale word rides a
                            # second landing — readable with the
                            # scale missing/stale
                            s[wkey] = (c, frozenset({("q", r, c, d)}),
                                       True, False)
                            s[wp] = c
                        else:
                            # hardware DMA: payload + signal atomic
                            s[wkey] = (c, payload(r, c, d), True, False)
                        s[pc] = i + 1
                        return s

                    return Transition(
                        f"r{r}.issue{c}.d{d}", f"r{r}", guard, apply,
                        frozenset({pc, wp, cr, wkey}),
                        frozenset({pc, wp, cr, wkey, "collision"}))

                def guard(s):
                    if s[pc] != i:
                        return False
                    if mutation == "recv_before_send_wave":
                        return True          # MUTANT: no recv-sem wait
                    occ, pay, sig, cons = s[rkey]
                    return occ == c and sig and not cons

                def apply(s):
                    occ, pay, sig, cons = s[rkey]
                    s[res] = s[res] + (pay,)
                    s[rkey] = (occ, pay, sig, True)
                    s[f"cr{upr}_{d}"] += 1       # re-grant the credit
                    s[pc] = i + 1
                    return s

                return Transition(
                    f"r{r}.drain{c}.d{d}", f"r{r}", guard, apply,
                    frozenset({pc, rkey}),
                    frozenset({pc, rkey, res, f"cr{upr}_{d}"}))
            ts.append(mk())

        # the async landing actor of the split-write mutants
        if mutation in ("signal_before_copy", "scale_after_payload"):
            for d in dirs:
                def mkland(r=r, d=d):
                    peer = dst(r, d)
                    a = slot_arr(d)
                    wp = f"wp{r}_{d}"
                    skeys = frozenset(f"sl{peer}_{a}_{s}"
                                      for s in range(D))

                    def guard(s):
                        return s[wp] is not None

                    def apply(s):
                        k = s[wp]
                        key = f"sl{peer}_{a}_{k % D}"
                        occ, pay, sig, cons = s[key]
                        if occ == k and pay == TORN:
                            s[key] = (k, payload(r, k, d), sig, cons)
                        elif occ == k \
                                and mutation == "scale_after_payload":
                            # the late scale word finally lands
                            s[key] = (k, pay | {("s", r, k, d)},
                                      sig, cons)
                        s[wp] = None
                        return s

                    return Transition(f"r{r}.land.d{d}", f"dma{r}_{d}",
                                      guard, apply,
                                      frozenset({wp}) | skeys,
                                      frozenset({wp}) | skeys)
                ts.append(mkland())

    # ---- invariants --------------------------------------------------
    end = len(prog)

    def inv_collision(s):
        if s["collision"]:
            return ("a remote write landed in a slot whose previous "
                    "chunk was not consumed")
        return None

    def inv_credit(s):
        for r in range(n):
            for d in dirs:
                issued = issued_at[s[f"pc{r}"]][d]
                outstanding = issued - drained_at[s[f"pc{dst(r, d)}"]][d]
                cr = s[f"cr{r}_{d}"]
                if cr + outstanding != D:
                    return (f"rank {r} dir {d}: credits {cr} + "
                            f"in-flight {outstanding} != depth {D}")
                if cr > D:
                    return (f"rank {r} dir {d}: over-credit {cr} > "
                            f"depth {D}")
        return None

    def inv_agree(s):
        for r in range(n):
            for d in dirs:
                src = up(r, d)
                for i, pay in enumerate(s[f"res{r}_{d}"]):
                    if pay == TORN:
                        return (f"rank {r} dir {d} folded a TORN "
                                f"chunk {i}")
                    if pay != payload(src, i, d):
                        if quant and isinstance(pay, frozenset) \
                                and ("s", src, i, d) not in pay:
                            return (f"rank {r} dir {d} dequant-folded "
                                    f"chunk {i} with a missing/stale "
                                    "scale word — outside the declared "
                                    "block-quant bound of the exact "
                                    "fold")
                        return (f"rank {r} dir {d} chunk {i} delivered "
                                f"{sorted(pay)} != the upstream "
                                "contribution")
        return None

    def final(s):
        return all(s[f"pc{r}"] == end for r in range(n))

    label = (f"ici-ring(n={n},C={C},D={D},"
             f"{'bidir' if bidir else 'uni'}"
             f"{',quant' if quant else ''},mut={mutation})")
    return Model(label, init, ts,
                 [("no-slot-collision", inv_collision),
                  ("no-lost-credit", inv_credit),
                  ("agreement", inv_agree)],
                 final)


_PAD = "PAD"   # wire-padding chunk payload (consumed, never delivered)


def build_alltoallv(n: int, depth: int, counts,
                    mutation: Optional[str] = None) -> Model:
    """Per-peer variable chunk counts on the global-counter slot
    schedule — the MoE-shaped alltoallv wire (ops/pallas_alltoall.py).

    The protocol skeleton: steps ``t = 1..n-1`` of a rotation schedule
    (step ``t``: rank ``r`` streams to ``(r+t) % n`` and receives from
    ``(r-t) % n``). The step-wide wire width ``W_t`` is the MAX chunk
    count over that step's pairs — wire chunks are always full size, so
    a pair below the max streams PADDING chunks that the receiver must
    still consume and credit back (the byte-count-blind slot/credit
    schedule; ``W_t == 0`` steps are skipped mesh-wide). The slot for
    wire chunk ``k`` of step ``t`` is ``G(t,k) % depth`` with ``G`` the
    GLOBAL wire counter (cumulative over steps) — both ends derive it
    from the same counts matrix, never from their local valid-chunk
    tallies. Flow control is a per-step credit wave on the sender's
    per-destination lane: the receiver grants ``depth`` at its step
    entry (so a sender can never run into slots whose previous-step
    occupants the receiver has not drained), re-grants one per consume
    (padding included), and the sender fences its lane back to depth at
    step exit.

    Mutations (tests/test_modelcheck.py asserts each is caught):

      skewed_count_slot      the sender derives the slot from its own
                             VALID-chunk counter (padding chunks do not
                             advance it) — under skewed counts the send
                             and drain slot sequences diverge and a
                             write lands in an unconsumed slot
      zero_count_credit_leak the receiver skips the credit re-grant on
                             padding chunks — the credit window of any
                             below-max pair (a zero-count peer in the
                             extreme) leaks shut and the sender's fence
                             starves
      local_width_wire       the sender sizes its wire from its LOCAL
                             count instead of the step-wide max — no
                             padding chunks on a below-max lane, so the
                             receiver's byte-count-blind drain schedule
                             waits forever on chunks that never launch
                             (the transport-asymmetry deadlock class
                             the pad-to-max wire exists to rule out)
      zero_count_entry_skip  the receiver's step entry skips the
                             depth-D grant when it expects zero VALID
                             chunks from its upstream — but the wire
                             still carries W padding chunks, and the
                             ungranted sender starves at issue
    """
    assert n >= 2 and depth >= 1
    D = depth
    counts = [[int(c) for c in row] for row in counts]
    assert len(counts) == n and all(len(r) == n for r in counts)

    def dst(r: int, t: int) -> int:
        return (r + t) % n

    def src(r: int, t: int) -> int:
        return (r - t + n) % n

    # step-wide wire widths (zero-width steps skipped mesh-wide) and
    # the global wire counter offset of each active step
    steps = []
    G0 = {}
    g = 0
    for t in range(1, n):
        W = max(counts[r][dst(r, t)] for r in range(n))
        if W == 0:
            continue
        steps.append((t, W))
        G0[t] = g
        g += W

    # the serialized per-rank programs: entry grant, issue/drain
    # alternation, exit fence. Identical across ranks (W is step-wide)
    # EXCEPT under the local_width_wire mutant, where a sender streams
    # only its local count and skips the padding issues
    progs = []
    for r in range(n):
        prog = []
        for t, W in steps:
            send_w = W
            if mutation == "local_width_wire":
                send_w = min(W, counts[r][dst(r, t)])
            prog.append(("entry", t, 0))
            for k in range(W):
                if k < send_w:
                    prog.append(("issue", t, k))
                if k >= 1:
                    prog.append(("drain", t, k - 1))
            prog.append(("drain", t, W - 1))
            prog.append(("fence", t, 0))
        progs.append(prog)

    init = {"collision": 0}
    for r in range(n):
        init[f"pc{r}"] = 0
        init[f"vc{r}"] = 0          # valid-chunk tally (mutant's slot)
        init[f"res{r}"] = ()        # delivered valid payloads, in order
        for d in range(n):
            if d != r:
                init[f"cr{r}_{d}"] = 0    # credits held on lane r->d
                init[f"fl{r}_{d}"] = 0    # chunks in flight on r->d
                init[f"win{r}_{d}"] = 0   # receiver-granted window
        for s in range(D):
            init[f"sl{r}_{s}"] = (_FREE, _PAD, True)

    ts = []
    for r in range(n):
        for i, (op, t, k) in enumerate(progs[r]):
            def mk(r=r, i=i, op=op, t=t, k=k):
                pc = f"pc{r}"
                peer, upr = dst(r, t), src(r, t)
                g = G0[t] + k
                cr = f"cr{r}_{peer}"

                if op == "entry":
                    # receiver-side grant: open the upstream's window
                    ucr, uwin = f"cr{upr}_{r}", f"win{upr}_{r}"

                    def guard(s, pc=pc, i=i):
                        return s[pc] == i

                    def apply(s, upr=upr):
                        if not (mutation == "zero_count_entry_skip"
                                and counts[upr][r] == 0):
                            s[ucr] += D
                            s[uwin] += D
                        s[pc] = i + 1
                        return s

                    return Transition(
                        f"r{r}.entry.t{t}", f"r{r}", guard, apply,
                        frozenset({pc}),
                        frozenset({pc, ucr, uwin}))

                if op == "fence":
                    def guard(s, pc=pc, i=i, cr=cr):
                        return s[pc] == i and s[cr] >= D

                    def apply(s, cr=cr, win=f"win{r}_{peer}"):
                        s[cr] -= D
                        s[win] -= D
                        s[pc] = i + 1
                        return s

                    return Transition(
                        f"r{r}.fence.t{t}", f"r{r}", guard, apply,
                        frozenset({pc, cr}),
                        frozenset({pc, cr, f"win{r}_{peer}"}))

                if op == "issue":
                    valid = k < counts[r][peer]
                    fl = f"fl{r}_{peer}"
                    vc = f"vc{r}"
                    skeys = frozenset(f"sl{peer}_{s}" for s in range(D))

                    def guard(s, pc=pc, i=i, cr=cr):
                        return s[pc] == i and s[cr] > 0

                    def apply(s, g=g, valid=valid):
                        s[cr] -= 1
                        s[fl] += 1
                        if mutation == "skewed_count_slot":
                            # MUTANT: slot from the local valid-chunk
                            # tally — pads do not advance it, so skewed
                            # counts desync it from the wire counter
                            slot = s[vc] % D
                        else:
                            slot = g % D
                        if valid:
                            s[vc] += 1
                        wkey = f"sl{peer}_{slot}"
                        occ, pay, cons = s[wkey]
                        if not cons:
                            s["collision"] = 1       # sticky
                        s[wkey] = (g, (r, t, k) if valid else _PAD,
                                   False)
                        s[pc] = i + 1
                        return s

                    return Transition(
                        f"r{r}.issue.t{t}.k{k}", f"r{r}", guard, apply,
                        frozenset({pc, cr, vc}) | skeys,
                        frozenset({pc, cr, fl, vc, "collision"})
                        | skeys)

                # drain: consume wire chunk k of step t from upstream
                rkey = f"sl{r}_{g % D}"
                is_pad = k >= counts[upr][r]
                ucr, ufl = f"cr{upr}_{r}", f"fl{upr}_{r}"
                res = f"res{r}"

                def guard(s, pc=pc, i=i, rkey=rkey, g=g):
                    if s[pc] != i:
                        return False
                    occ, pay, cons = s[rkey]
                    return occ == g and not cons

                def apply(s, rkey=rkey, is_pad=is_pad):
                    occ, pay, cons = s[rkey]
                    if pay != _PAD:
                        s[res] = s[res] + (pay,)
                    s[rkey] = (occ, pay, True)
                    s[ufl] -= 1
                    if not (is_pad
                            and mutation == "zero_count_credit_leak"):
                        s[ucr] += 1      # re-grant (padding included)
                    s[pc] = i + 1
                    return s

                return Transition(
                    f"r{r}.drain.t{t}.k{k}", f"r{r}", guard, apply,
                    frozenset({pc, rkey}),
                    frozenset({pc, rkey, res, ucr, ufl}))
            ts.append(mk())

    # ---- invariants --------------------------------------------------
    ends = [len(p) for p in progs]
    expected = {}
    for r in range(n):
        seq = []
        for t, W in steps:
            u = src(r, t)
            seq += [(u, t, k) for k in range(counts[u][r])]
        expected[r] = tuple(seq)

    def inv_collision(s):
        if s["collision"]:
            return ("a remote write landed in a slot whose previous "
                    "chunk was not consumed")
        return None

    def inv_credit(s):
        for r in range(n):
            for d in range(n):
                if d == r:
                    continue
                cr, fl, win = (s[f"cr{r}_{d}"], s[f"fl{r}_{d}"],
                               s[f"win{r}_{d}"])
                if cr + fl != win:
                    return (f"lane {r}->{d}: credits {cr} + in-flight "
                            f"{fl} != granted window {win}")
                if cr < 0 or win not in (0, D):
                    return (f"lane {r}->{d}: window {win} / credits "
                            f"{cr} outside the depth-{D} discipline")
        return None

    def inv_agree(s):
        for r in range(n):
            got = s[f"res{r}"]
            if got != expected[r][:len(got)]:
                return (f"rank {r} delivered {got} — not a prefix of "
                        f"the counts-matrix order {expected[r]}")
        return None

    def final(s):
        return all(s[f"pc{r}"] == ends[r] for r in range(n))

    label = (f"ici-a2av(n={n},D={D},counts={counts},mut={mutation})")
    return Model(label, init, ts,
                 [("no-slot-collision", inv_collision),
                  ("no-lost-credit", inv_credit),
                  ("agreement", inv_agree)],
                 final)


def build_mesh(px: int = 2, py: int = 2, k: int = 1,
               mutation: Optional[str] = None) -> Model:
    """Multi-axis mesh RS/AG phase model (ops/pallas_ici.py
    ici_all_reduce_mesh + coll/device.py DeviceFoldChannel) at
    contribution-set granularity.

    A ``px`` x ``py`` chip mesh runs the nested phase decomposition the
    multi-axis device allreduce executes: reduce-scatter along x, then
    along y, then all-gather along y, then along x — each axis phase a
    publish/fold wave over that axis's ring. ``k`` ranks per chip adds
    the leaders-per-chip HBM fold in front: co-located member ranks
    stamp their contribution into the chip leader, which folds them
    before any ICI phase runs. Per-chunk slot/credit flow control is
    ``build_ring``'s job — this model carries the PHASE-ORDERING bugs
    of the three-level composition, so payloads are contribution sets
    and each phase is atomic publish + guarded fold.

    The nesting is what makes ordering load-bearing: RS-y operates on
    RS-x's per-column partials, and the axis-k AG gathers sub-shard
    pieces that are only fully reduced once EVERY RS phase has landed.
    A rank that starts an axis's AG before that axis's RS has completed
    on it publishes a cross-axis partial, and the piece its ring peers
    gather is stale forever after.

    Invariants:

      * **axis-phase-order** — no chip starts the AG of an axis (first
        gather-slot publish) before its own RS of that axis completed;
      * **agreement** — every delivered result covers the full px x py
        sub-shard grid and every gathered piece equals the FULL
        contribution set (all chips x all co-located ranks);
      * **no-deadlock** — the wave always completes (explorer built-in).

    Mutations (tests/test_modelcheck.py asserts each is caught):

      ag_before_rs_crossaxis  the chip treats the CROSS axis's RS
                              completion as license to start the axis-y
                              AG — it publishes its gather slot straight
                              after RS-x, before its own RS-y fold, so
                              the slot carries the pre-y row partial
      leader_fold_skipped     the chip leader enters the ICI phases
                              without waiting for (or folding) its
                              co-located members' HBM slots — every
                              delivered shard misses their contributions
    """
    assert px >= 1 and py >= 1 and px * py >= 2 and k >= 1
    if mutation == "leader_fold_skipped":
        assert k >= 2, "leader_fold_skipped needs co-located ranks"
    nc = px * py

    def cx(c: int) -> int:
        return c % px

    def cy(c: int) -> int:
        return c // px

    def xring(c: int):
        return tuple(cy(c) * px + i for i in range(px))

    def yring(c: int):
        return tuple(j * px + cx(c) for j in range(py))

    full = frozenset((c, j) for c in range(nc) for j in range(k))
    shards = frozenset((i, j) for i in range(px) for j in range(py))

    # the serialized per-chip phase program; the mutant hoists the
    # axis-y AG publish to right after the axis-x RS fold
    if mutation == "ag_before_rs_crossaxis":
        steps = ("fold", "rsx_pub", "rsx_fold", "agy_pub", "rsy_pub",
                 "rsy_fold", "agy_fold", "agx_pub", "agx_fold")
    else:
        steps = ("fold", "rsx_pub", "rsx_fold", "rsy_pub", "rsy_fold",
                 "agy_pub", "agy_fold", "agx_pub", "agx_fold")
    end = len(steps)

    init = {}
    for c in range(nc):
        init[f"pc{c}"] = 0
        init[f"acc{c}"] = frozenset({(c, 0)})   # the leader's own share
        init[f"gat{c}"] = frozenset()           # gathered (shard, piece)
        init[f"res{c}"] = None
        for ph in ("rsx", "rsy", "agy", "agx"):
            init[f"{ph}_sl{c}"] = frozenset()
            init[f"{ph}_in{c}"] = 0
        init[f"rsx_done{c}"] = 0
        init[f"rsy_done{c}"] = 0
        for j in range(1, k):
            init[f"min{c}_{j}"] = 0             # member HBM-slot stamp

    ts = []
    for c in range(nc):
        # co-located member ranks: stamp the chip leader's HBM slot.
        # One atomic step — the torn-copy surface is the hbm slot
        # model's job; this model carries the ordering bugs.
        for j in range(1, k):
            def mkm(c=c, j=j):
                key = f"min{c}_{j}"

                def guard(s):
                    return s[key] == 0

                def apply(s):
                    s[key] = 1
                    return s

                return Transition(f"c{c}.m{j}.stamp", f"m{c}_{j}",
                                  guard, apply,
                                  frozenset({key}), frozenset({key}))
            ts.append(mkm())

        for i, stp in enumerate(steps):
            def mk(c=c, i=i, stp=stp):
                pc, acc = f"pc{c}", f"acc{c}"

                if stp == "fold":
                    stamps = [f"min{c}_{j}" for j in range(1, k)]

                    def guard(s):
                        if s[pc] != i:
                            return False
                        if mutation == "leader_fold_skipped":
                            return True      # MUTANT: no member wait
                        return all(s[m] >= 1 for m in stamps)

                    def apply(s):
                        if mutation != "leader_fold_skipped":
                            s[acc] = s[acc] | frozenset(
                                (c, j) for j in range(1, k))
                        s[pc] = i + 1
                        return s

                    return Transition(f"c{c}.fold", f"c{c}", guard,
                                      apply,
                                      frozenset({pc} | set(stamps)),
                                      frozenset({pc, acc}))

                if stp in ("rsx_pub", "rsy_pub"):
                    ph = stp[:3]
                    sl, stamp = f"{ph}_sl{c}", f"{ph}_in{c}"

                    def guard(s):
                        return s[pc] == i

                    def apply(s):
                        s[sl] = s[acc]
                        s[stamp] = 1
                        s[pc] = i + 1
                        return s

                    return Transition(f"c{c}.{stp}", f"c{c}", guard,
                                      apply, frozenset({pc, acc}),
                                      frozenset({pc, sl, stamp}))

                if stp in ("rsx_fold", "rsy_fold"):
                    ph = stp[:3]
                    ring = xring(c) if ph == "rsx" else yring(c)
                    stamps = [f"{ph}_in{p}" for p in ring]
                    slots = [f"{ph}_sl{p}" for p in ring]
                    done = f"{ph}_done{c}"

                    def guard(s):
                        return s[pc] == i \
                            and all(s[m] >= 1 for m in stamps)

                    def apply(s):
                        u = frozenset()
                        for slk in slots:
                            u = u | s[slk]
                        s[acc] = u
                        s[done] = 1
                        s[pc] = i + 1
                        return s

                    return Transition(f"c{c}.{stp}", f"c{c}", guard,
                                      apply,
                                      frozenset({pc} | set(stamps)
                                                | set(slots)),
                                      frozenset({pc, acc, done}))

                if stp == "agy_pub":
                    sl, stamp = f"agy_sl{c}", f"agy_in{c}"

                    def guard(s):
                        return s[pc] == i

                    def apply(s):
                        # publish the (sub-shard, piece) this chip owns
                        s[sl] = frozenset({((cx(c), cy(c)), s[acc])})
                        s[stamp] = 1
                        s[pc] = i + 1
                        return s

                    return Transition(f"c{c}.agy_pub", f"c{c}", guard,
                                      apply, frozenset({pc, acc}),
                                      frozenset({pc, sl, stamp}))

                if stp == "agy_fold":
                    ring = yring(c)
                    stamps = [f"agy_in{p}" for p in ring]
                    slots = [f"agy_sl{p}" for p in ring]
                    gat = f"gat{c}"

                    def guard(s):
                        return s[pc] == i \
                            and all(s[m] >= 1 for m in stamps)

                    def apply(s):
                        u = frozenset()
                        for slk in slots:
                            u = u | s[slk]
                        s[gat] = u
                        s[pc] = i + 1
                        return s

                    return Transition(f"c{c}.agy_fold", f"c{c}", guard,
                                      apply,
                                      frozenset({pc} | set(stamps)
                                                | set(slots)),
                                      frozenset({pc, gat}))

                if stp == "agx_pub":
                    sl, stamp = f"agx_sl{c}", f"agx_in{c}"
                    gat = f"gat{c}"

                    def guard(s):
                        return s[pc] == i

                    def apply(s):
                        s[sl] = s[gat]
                        s[stamp] = 1
                        s[pc] = i + 1
                        return s

                    return Transition(f"c{c}.agx_pub", f"c{c}", guard,
                                      apply, frozenset({pc, gat}),
                                      frozenset({pc, sl, stamp}))

                # agx_fold: gather the row's column-gathers — delivery
                ring = xring(c)
                stamps = [f"agx_in{p}" for p in ring]
                slots = [f"agx_sl{p}" for p in ring]
                res = f"res{c}"

                def guard(s):
                    return s[pc] == i and all(s[m] >= 1 for m in stamps)

                def apply(s):
                    u = frozenset()
                    for slk in slots:
                        u = u | s[slk]
                    s[res] = u
                    s[pc] = i + 1
                    return s

                return Transition(f"c{c}.agx_fold", f"c{c}", guard,
                                  apply,
                                  frozenset({pc} | set(stamps)
                                            | set(slots)),
                                  frozenset({pc, res}))
            ts.append(mk())

    # ---- invariants --------------------------------------------------
    def inv_order(s):
        for c in range(nc):
            if s[f"agy_in{c}"] and not s[f"rsy_done{c}"]:
                return (f"chip {c} started its axis-y AG (published "
                        "the gather slot) before its own axis-y RS "
                        "completed")
            if s[f"agx_in{c}"] and not s[f"rsx_done{c}"]:
                return (f"chip {c} started its axis-x AG before its "
                        "own axis-x RS completed")
        return None

    def inv_agree(s):
        for c in range(nc):
            r = s[f"res{c}"]
            if r is None:
                continue
            got = {sh for sh, _ in r}
            if got != shards:
                return (f"chip {c} delivered shards {sorted(got)} != "
                        f"the full {px}x{py} sub-shard cover")
            for sh, pay in r:
                if pay != full:
                    return (f"chip {c} sub-shard {sh} gathered "
                            f"{sorted(pay)} != the full contribution "
                            "set — a cross-axis partial leaked through "
                            "the AG gather")
        return None

    def final(s):
        return all(s[f"pc{c}"] == end for c in range(nc))

    label = (f"ici-mesh(px={px},py={py},k={k},mut={mutation})")
    return Model(label, init, ts,
                 [("axis-phase-order", inv_order),
                  ("agreement", inv_agree)],
                 final)
