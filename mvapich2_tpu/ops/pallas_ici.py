"""HBM-streaming Pallas ICI collective engine — chunked remote-DMA rings.

The large-message tier of the device path. The hand-scheduled kernels in
ops/pallas_ring.py are VMEM-resident (shard + 2 comm slots must fit in
~16 MiB; the wrapper refuses past ``VMEM_LIMIT_BYTES``), which capped
every device perf round since r3 at the XLA lowering's plateau. These
kernels lift the cap the way the reference lifts the eager->rendezvous
crossover: inputs and outputs stay in HBM (``pl.ANY``) and
the kernel streams fixed-size chunks through double-buffered VMEM
scratch slots —

    HBM block ──local DMA──> send slot ──remote DMA (ICI)──> peer recv slot
    peer recv slot + HBM acc chunk ──VPU reduce──> acc slot ──DMA──> HBM

with the remote DMA of chunk *k+1* overlapping the VPU reduce of chunk
*k* (the ibv_send.c vbuf pipeline, one level up). A ring block is read
from the operand, where the caller left it, until a fold or a gather
round has written it, and from the working or output buffer after: each
round is told where its send chunks and accumulator chunks are loaded
from and where its results are stored, so no whole-operand HBM-to-HBM
copy runs in front of the rounds, the reduce-scatter's last fold lands
in its output, and the operand is only ever read. The fold rounds take
``k`` operands a shard (two ranks a chip hand the ring both their
deposits, coll/device.py ``DeviceFoldChannel``): a chunk read from the
operand is then read from all ``k`` and folded in VMEM with the round's
own reducer as it is used,

    k HBM acc chunks ──k local DMAs──> acc slot + k-1 fold slots
    acc slot (+ fold slots, in operand order) + peer recv slot ──VPU──> acc slot

(round 0's send chunk likewise, in its send slot before the remote DMA
starts), so level 1 of a two-level reduction costs one more load and
one more VPU op a chunk under rounds the links bound, and no kernel,
buffer or pass over HBM of its own. At ``k = 1`` the kernels are, op
for op, what they were before the rounds took ``k``. The allreduce is the
pipelined reduce-scatter + all-gather decomposition (the "Multiple
Processes per GPU" schedule blueprint; EQuARX demonstrates the custom
chunked form beating stock XLA on TPU); where the mesh axis is a
physical ring both directions are driven at once (half of every block
travels clockwise, half counter-clockwise) for full bisection bandwidth.

Flow control on hardware is the per-direction credit handshake of
pallas_ring.py generalized to chunk granularity: each direction starts
with ``depth`` credits (one per VMEM slot) and the receiver re-grants a
credit as it consumes a slot, so a sender can run at most ``depth``
chunks ahead — slot reuse is race-free because the slot sequence is a
single global chunk counter per direction (write *k+D* lands in the slot
freed by consume *k*). The TPU interpreter (pltpu.InterpretParams)
executes remote signals, so CPU tests run the same handshake.

Every kernel opens with a neighbour barrier on the Mosaic barrier
semaphore (``_entry_barrier``): a remote DMA or a remote semaphore
signal may only target a chip that has entered the kernel — before
that its scratch semaphores and VMEM slots belong to whatever ran
there last. The barrier semaphore is the one object that outlives a
kernel, which is what ``collective_id`` names.

Layout (what Mosaic's tiling demands): data moves as ``(rows, 128)``
tiles with ``rows`` a multiple of the dtype's sublane tile (8 for
4-byte types, 16 for 2-byte, 32 for 1-byte); slot, direction and ring
block indices sit on leading, untiled dimensions, so every slice of a
tiled dimension is a tile-aligned range: static in a step that is
written out, and in a loop's body (a long round's or chain's chunk
steps are a ``fori_loop``, ``_looped_steps``) a traced row offset that
is a marked multiple of the tile, of a static size; every other traced
index is a leading one. Wrappers pad each ring block to a whole
number of tiles with the op identity and reshape the HBM operands to
``(p, block_rows, 128)`` before the ``pallas_call``.

Tier selection is one rule, ``planned_tier``, asked once by each
dispatcher here (``ici_all_reduce`` / ``ici_all_gather`` /
``ici_reduce_scatter`` / ``ici_bcast``, which switch on its answer and
nothing else) and by the MPI channel's per-call accounting
(coll/device.py ``_decide_tier``). It is data driven: coll/tuning.py's
``device_tier`` maps shard bytes to vmem (pallas_ring) / hbm (here) / quant
(pallas_quant — the block-scaled quantized wire above the hbm tier,
gated by the MV2T_QUANT_COLL accuracy budget) / xla, with the
boundaries re-measurable by ``bin/measure_crossover --device``; the
rule then names the engine that can carry the call (the flat VMEM ring
takes sums and gathers of a 1-D mesh only; a reduce-scatter, another
op, a ring along one axis of a multi-axis mesh stream through the
engine here; a broadcast has the streaming chain here and nothing
below it). Every fallback to the XLA lowering is counted by the
``dev_coll_fallback_*`` pvar family — the 4 MiB cliff is no longer
silent.

Usage: inside ``shard_map`` over a 1-D mesh axis, or through the
mesh-bound MPI channel (coll/device.py), which routes per-call.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils.config import get_config
from ..utils.mlog import get_logger
from ._compat import (compiler_params, dtype_kind, kernel_name,
                      note_fallback, on_tpu, resolve_interpret)

log = get_logger("pallas_ici")

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# cvars ICI_CHUNK_BYTES / ICI_PIPELINE_DEPTH / ICI_BIDIR / ICI_INTERPRET
# are predeclared in mpit.py (the MPI_T surface enumerates them before
# this module is imported); importing mpit here guarantees they exist
# for direct ops users too.
from .. import mpit  # noqa: F401,E402  — cvar/pvar declarations

_SUPPORTED_OPS = ("sum", "max", "min", "prod")

# Mosaic collective ids: each names the barrier semaphore its kernel's
# entry barrier signals (pallas_ring owns 0/1, pallas_quant 6,
# pallas_rma 7-10, pallas_alltoall 11)
_CID_ALLREDUCE = 2
_CID_ALLGATHER = 3
_CID_SENDRECV = 4
_CID_REDUCE_SCATTER = 5
_CID_BCAST = 12

_LANES = 128


def _cfg_chunk_elems(dtype, chunk_bytes: Optional[int]) -> int:
    if chunk_bytes is None:
        from ..coll.tuning import kernel_param_cv
        chunk_bytes = kernel_param_cv("ici_chunk_bytes",
                                      "ICI_CHUNK_BYTES")
    return max(1, int(chunk_bytes) // np.dtype(dtype).itemsize)


def _sublanes(dtype) -> int:
    """Rows of one VMEM tile of ``dtype``: (8, 128) for 4-byte types,
    (16, 128) for 2-byte, (32, 128) for 1-byte."""
    return max(8, 32 // np.dtype(dtype).itemsize)


def _tile_rows(nelems: int, dtype) -> int:
    """Rows of the smallest whole-tile ``(rows, 128)`` slab holding
    ``nelems`` elements."""
    t = _sublanes(dtype)
    return -(-(-(-int(nelems) // _LANES)) // t) * t


def _cfg_chunk_rows(dtype, chunk_bytes: Optional[int]) -> int:
    """The streaming chunk in rows: ICI_CHUNK_BYTES (or the explicit
    override) rounded down to whole tiles, at least one tile."""
    t = _sublanes(dtype)
    rows = _cfg_chunk_elems(dtype, chunk_bytes) // _LANES
    return max(t, rows // t * t)


def _as_blocks(flat: jax.Array, nblocks: int, block_rows: int,
               fill=0) -> jax.Array:
    """``[nblocks * c]`` -> ``(nblocks, block_rows, 128)``: each of the
    ``nblocks`` equal runs padded with ``fill`` to a whole-tile slab."""
    c = flat.size // nblocks
    width = block_rows * _LANES
    # one block stays flat while it is padded: a (1, c) array of a
    # 2-byte type is tiled (2, 128) at twice its bytes, and the compiler
    # builds it in a loop (PERF.md, PR 29 and PR 34)
    lead = (nblocks,) if nblocks > 1 else ()
    x = flat.reshape(lead + (c,))
    if width > c:
        x = jnp.pad(x, ((0, 0),) * len(lead) + ((0, width - c),),
                    constant_values=fill)
    return x.reshape(nblocks, block_rows, _LANES)


def _from_blocks(blocks: jax.Array, c: int) -> jax.Array:
    """Inverse of :func:`_as_blocks`: drop each block's pad, flatten.
    Padded blocks are cut one by one out of their own tiles and joined:
    a slice of the ``(nb, width)`` view costs the compiler a relayout
    of the whole result and a loop behind it (PERF.md, PR 34)."""
    nb = blocks.shape[0]
    if blocks.size // nb > c:
        return jnp.concatenate([blocks[i].reshape(-1)[:c]
                                for i in range(nb)])
    return blocks.reshape(nb * c)


def _entry_barrier(peers) -> None:
    """Kernel-entry barrier on the Mosaic barrier semaphore: signal
    every device in ``peers`` (traced LOGICAL ids; the relation must be
    symmetric — ring neighbours, an exchange partner, all peers) and
    wait for as many signals. Past it, every device that will write
    into this one's slots or semaphores knows this one is inside the
    kernel, and vice versa."""
    sem = pltpu.get_barrier_semaphore()
    for dev in peers:
        pltpu.semaphore_signal(sem, inc=1, device_id=dev,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(sem, len(peers))


def _cfg_depth(depth: Optional[int]) -> int:
    if depth is None:
        depth = int(get_config()["ICI_PIPELINE_DEPTH"])
    return max(2, int(depth))


def _pad_identity(dtype, op: str):
    """The reduction identity — pad values that cannot perturb the
    result of the padded-tail elements."""
    dt = np.dtype(dtype)
    if op == "sum":
        return 0
    if op == "prod":
        return 1
    if dtype_kind(dt) == "f":
        lo = -np.inf
        hi = np.inf
    else:
        info = np.iinfo(dt)
        lo, hi = info.min, info.max
    return lo if op == "max" else hi


def _reducer(op: str):
    return {"sum": lambda a, b: a + b,
            "max": jnp.maximum,
            "min": jnp.minimum,
            "prod": lambda a, b: a * b}[op]


def _chunks(lo: int, hi: int, chunk: int) -> List[Tuple[int, int]]:
    """Static (offset, size) chunk list covering [lo, hi) — the last
    chunk carries the remainder."""
    out = []
    off = lo
    while off < hi:
        out.append((off, min(chunk, hi - off)))
        off += chunk
    return out


# ---------------------------------------------------------------------------
# the streaming engine (shared by allreduce / all-gather kernels)
# ---------------------------------------------------------------------------

class _RingStreamer:
    """Per-kernel-instance streaming state: scratch refs, DMA handles,
    and the per-direction global chunk counters whose mod-depth sequence
    makes slot reuse collision-free (see module docstring)."""

    def __init__(self, p, ndir, depth, credits, left, right,
                 send_buf, recv_buf, acc_buf,
                 in_sem, acc_sem, st_sem, send_sem, recv_sem, cap_sem,
                 dev_base=0, dev_stride=1, fold_buf=None, fold_sem=None):
        self.p, self.ndir, self.depth, self.credits = p, ndir, depth, credits
        self.left, self.right = left, right
        self.dev_base, self.dev_stride = dev_base, dev_stride
        self.send_buf, self.recv_buf, self.acc_buf = \
            send_buf, recv_buf, acc_buf
        # the fold slots: beside every accumulator slot one more for
        # each further operand of a ring block (``k - 1`` of them; none,
        # and no scratch, where the ring has one operand)
        self.fold_buf, self.fold_sem = fold_buf, fold_sem
        self.nfold = 0 if fold_buf is None else fold_buf.shape[0]
        self.in_sem, self.acc_sem, self.st_sem = in_sem, acc_sem, st_sem
        self.send_sem, self.recv_sem, self.cap_sem = \
            send_sem, recv_sem, cap_sem
        self.gc = [0] * ndir                   # global chunk counter / dir
        self.rc = [0] * ndir                   # chunks ``take`` took / dir
        self.pending_send: Dict = {}           # (d, slot) -> remote handle
        self.pending_acc: Dict = {}
        self.pending_fold: Dict = {}           # (i, d, slot) -> fold load
        self.pending_store: Dict = {}

    def pending(self):
        """The tables of DMA descriptors started and not yet waited
        for, each keyed by the slot its DMA occupies."""
        return (self.pending_send, self.pending_acc, self.pending_fold,
                self.pending_store)

    def _dev(self, idx):
        # logical device id of ring index ``idx``: the identity on a
        # 1-D mesh; on a multi-axis torus the ring runs along ONE axis,
        # so the id is this device's id with that axis' coordinate
        # replaced (base = id with the coordinate zeroed, stride = the
        # axis' row-major stride — see _dev_layout)
        return self.dev_base + idx * self.dev_stride

    def enter(self):
        """Kernel entry: barrier with both ring neighbours, then hand
        out the initial slot credits."""
        _entry_barrier([self._dev(self.left), self._dev(self.right)])
        self.grant_initial_credits()

    def grant_initial_credits(self):          # device: hw-only
        """Each direction starts with ``depth`` slot credits granted to
        the upstream neighbor (the rank that remote-writes into us)."""
        if not self.credits:
            return
        for d in range(self.ndir):
            upstream = self.left if d == 0 else self.right
            pltpu.semaphore_signal(
                self.cap_sem.at[d], inc=self.depth,
                device_id=self._dev(upstream),
                device_id_type=pltpu.DeviceIdType.LOGICAL)

    def drain_stores(self):
        """Step/phase barrier: every outstanding VMEM->HBM store has
        landed (the next step's loads read those addresses)."""
        for key, h in list(self.pending_store.items()):
            h.wait()
            del self.pending_store[key]

    def _load_others(self, d, slot, blocks, off, sz):
        """Start the loads of rows [off, off+sz) of ``blocks``, the
        further operands' copies of one ring block, into the fold slots
        beside slot ``(d, slot)``; ``_fold_others`` waits for them."""
        for i, blk in enumerate(blocks):
            lf = pltpu.make_async_copy(
                blk.at[pl.ds(off, sz)],
                self.fold_buf.at[i, d, slot, pl.ds(0, sz)],
                self.fold_sem.at[i, d, slot])
            lf.start()
            self.pending_fold[(i, d, slot)] = lf

    def _fold_others(self, d, slot, own, sz, red):
        """``own``, the first operand's chunk, folded with the chunks
        ``_load_others`` brings, in operand order, as each lands. The
        VPU reads the fold slots synchronously: they are free after."""
        for i in range(self.nfold):
            self.pending_fold.pop((i, d, slot)).wait()
            own = red(own, self.fold_buf[i, d, slot, :sz])
        return own

    def _remote(self, d, slot, sz):
        """Lane ``d``'s remote copy of ``sz`` rows through slot
        ``slot``: this chip's send slot into the downstream
        neighbour's recv slot of the same index."""
        dst = self.right if d == 0 else self.left
        return pltpu.make_async_remote_copy(
            src_ref=self.send_buf.at[d, slot, pl.ds(0, sz)],
            dst_ref=self.recv_buf.at[d, slot, pl.ds(0, sz)],
            send_sem=self.send_sem.at[d, slot],
            recv_sem=self.recv_sem.at[d, slot],
            device_id=self._dev(dst),
            device_id_type=pltpu.DeviceIdType.LOGICAL)

    def issue(self, d, src, off, sz, acc, red=None):
        """Front half of the chunk pipeline: load the send chunk (and,
        for the reduce phase, prefetch the local accumulator chunk),
        then launch the remote DMA — it flies while the previous
        chunk's reduce runs. ``src``/``acc``: the ``(block_rows, 128)``
        HBM ring blocks the send chunk and the accumulator chunk are
        read from (``acc`` None in the gather phase), wherever they lie
        — the operand until a round has written the block, the working
        buffer after; a tuple each, because a block no round has
        written lies in every one of the ring's ``k`` operands: the
        chunk is then loaded from all of them and is their fold by
        ``red`` (the send chunk in its slot before the remote DMA
        starts; the accumulator chunk in ``drain``). The fold slots
        serve both, so the accumulator's further loads start once the
        send chunk is folded. ``off``/``sz``: static, tile-aligned row
        range inside the block."""
        slot = self.gc[d] % self.depth
        prev = self.pending_send.pop((d, slot), None)
        if prev is not None:
            prev.wait_send()           # send slot free for reload
        prev_st = self.pending_store.pop((d, slot), None)
        if prev_st is not None:
            prev_st.wait()             # acc slot's last store landed
        ld = pltpu.make_async_copy(
            src[0].at[pl.ds(off, sz)],
            self.send_buf.at[d, slot, pl.ds(0, sz)],
            self.in_sem.at[d, slot])
        ld.start()
        self._load_others(d, slot, src[1:], off, sz)
        if acc is not None:
            la = pltpu.make_async_copy(
                acc[0].at[pl.ds(off, sz)],
                self.acc_buf.at[d, slot, pl.ds(0, sz)],
                self.acc_sem.at[d, slot])
            la.start()
            self.pending_acc[(d, slot)] = la
        ld.wait()
        if len(src) > 1:
            self.send_buf[d, slot, :sz] = self._fold_others(
                d, slot, self.send_buf[d, slot, :sz], sz, red)
        if acc is not None:
            self._load_others(d, slot, acc[1:], off, sz)
        self._take_credit(d)
        rdma = self._remote(d, slot, sz)
        rdma.start()
        self.pending_send[(d, slot)] = rdma
        self.gc[d] += 1
        return slot

    def drain(self, d, slot, dst, off, sz, red):
        """Back half: the chunk from upstream has (or is about to have)
        landed — reduce it into the accumulator chunk, itself the fold
        of the ``k`` operands' chunks where the block lay in them (or
        take it verbatim for the gather phase), store the result into
        rows [off, off+sz) of the HBM block ``dst`` and free the slot."""
        self.pending_send[(d, slot)].wait_recv()
        if red is not None:
            self.pending_acc.pop((d, slot)).wait()
            own = self._fold_others(d, slot, self.acc_buf[d, slot, :sz],
                                    sz, red)
            self.acc_buf[d, slot, :sz] = red(
                own, self.recv_buf[d, slot, :sz])
            # the VPU read of recv_buf is synchronous: the slot is free
            self._grant(d)
            st = pltpu.make_async_copy(
                self.acc_buf.at[d, slot, pl.ds(0, sz)],
                dst.at[pl.ds(off, sz)],
                self.st_sem.at[d, slot])
            st.start()
            self.pending_store[(d, slot)] = st
        else:
            self._store_arrival(d, slot, dst, off, sz)

    def _store_arrival(self, d, slot, dst, off, sz):
        """The arrival in recv slot ``(d, slot)`` stored verbatim into
        rows [off, off+sz) of ``dst``; the slot is re-granted once the
        store has landed."""
        st = pltpu.make_async_copy(
            self.recv_buf.at[d, slot, pl.ds(0, sz)],
            dst.at[pl.ds(off, sz)],
            self.st_sem.at[d, slot])
        st.start()
        st.wait()                  # slot must land before re-grant
        self._grant(d)

    def take(self, d, dst, off, sz):
        """``drain``'s gather arm for a chip that need not have sent
        what it receives: wait for the upstream neighbour's next chunk
        on lane ``d`` and store it into rows [off, off+sz) of ``dst``.
        The slot is the receive counter's, ``rc[d] % depth``: a chain's
        root sends and never receives, a lane's last chip receives and
        never sends, so ``issue``'s counter cannot serve both halves;
        upstream's n-th send and this chip's n-th receive are the same
        number, which is all the credit schedule needs."""
        slot = self.rc[d] % self.depth
        self._remote(d, slot, sz).wait_recv()
        self._store_arrival(d, slot, dst, off, sz)
        self.rc[d] += 1

    def _take_credit(self, d):                # device: hw-only
        """Consume one slot credit before the remote DMA — the sender
        half of the chunk-credit handshake (shared with the quantized
        streamer, ops/pallas_quant.py)."""
        if not self.credits:
            return
        pltpu.semaphore_wait(self.cap_sem.at[d], 1)

    def _grant(self, d):                      # device: hw-only
        if not self.credits:
            return
        upstream = self.left if d == 0 else self.right
        pltpu.semaphore_signal(
            self.cap_sem.at[d], inc=1, device_id=self._dev(upstream),
            device_id_type=pltpu.DeviceIdType.LOGICAL)

    def finish(self):
        """Exit barrier: outbound DMAs off the send slots, stores
        landed, and — with credits — both neighbors have consumed
        everything we wrote (the remaining balance is exactly
        ``depth``), so no in-flight write can land after kernel exit."""
        for key, h in list(self.pending_send.items()):
            h.wait_send()
            del self.pending_send[key]
        self.drain_stores()
        if self.credits:                      # device: hw-only
            for d in range(self.ndir):
                pltpu.semaphore_wait(self.cap_sem.at[d], self.depth)

    def stream_step(self, spans_chunks, src, acc, dst, red):
        """One ring step: pipeline every chunk of every direction —
        issue chunk c, then drain chunk c-1 while c is on the wire.
        ``src``/``acc``/``dst`` say, per direction, where the send
        chunk is loaded from, where the accumulator chunk is loaded
        from (None with ``red`` None: the gather phase) and where the
        result is stored, in whatever unit ``issue``/``drain`` take
        (tuples of HBM block refs and a block ref here; the quantized
        streamer still passes flat element offsets) — this loop only
        hands them through.

        The steps differ in nothing but their offsets, so they are not
        unrolled (``_looped_steps``): the first ``depth`` are traced as
        they are, after which every slot of every lane holds a send, a
        store and the loads to wait for; the whole groups of ``depth``
        steps that follow, as long as every lane issues and drains full
        chunks, are one ``fori_loop`` body whose offsets are traced and
        whose slots are static; a lane's short last chunk, the odd
        steps and the last drain are traced after. The chip runs the
        same DMAs in the same order on the same slots; the traced
        program is a few steps a round whatever the payload. A round
        too short for two groups (``_step_plan``) is traced whole."""
        ndir, depth = self.ndir, self.depth
        base = list(self.gc)        # a chunk's slot: (base + c) % depth
        first, groups, steps = plan = _step_plan(spans_chunks, depth, lag=1)

        def step(c, now, before):
            # ``c``: the step, or what it is modulo ``depth``
            for d in range(ndir):
                if now[d] is not None:
                    self.issue(d, src[d], *now[d],
                               acc[d] if acc else None, red)
            for d in range(ndir):
                if before[d] is not None:
                    self.drain(d, (base[d] + c - 1) % depth, dst[d],
                               *before[d], red)

        def static(c):
            def at(j):
                return [chunks[j] if 0 <= j < len(chunks) else None
                        for chunks in spans_chunks]
            step(c, at(c), at(c - 1))

        def looped(g, i):
            # step first + g * depth + i: full chunks on every lane
            chunk = spans_chunks[0][0][1]
            turn = g * (depth * chunk)

            def at(j):
                return [(pl.multiple_of(turn + (lo + j * chunk),
                                        math.gcd(lo, chunk)), chunk)
                        for (lo, _), *_ in spans_chunks]
            step(first + i, at(first + i), at(first + i - 1))

        _looped_steps(self, plan, static, looped)
        self.drain_stores()


def _step_plan(spans_chunks, depth: int, skew=None,
               lag: int = 0) -> Tuple[int, int, int]:
    """``(first, groups, steps)`` of a run of pipeline steps in which
    lane ``d`` handles its chunk ``t - skew[d]`` in step ``t`` (and is
    done with it ``lag`` steps later): the ``first`` steps are traced as
    they are, until every lane has ``depth`` chunks behind it; the
    ``groups`` whole groups of ``depth`` steps that follow, in each of
    which every lane's chunk is a full one, are a loop's body; the
    rest of the ``steps`` is traced after. Decided from the shapes
    alone: fewer than two groups, or a lane with no chunk, and nothing
    is looped (``first`` is ``steps``)."""
    lanes = [d for d, c in enumerate(spans_chunks) if c]
    skew = skew or [0] * len(spans_chunks)
    chunk = spans_chunks[lanes[0]][0][1]
    first = max(skew[d] for d in lanes) + depth
    groups = (min(skew[d] + sum(sz == chunk for _, sz in spans_chunks[d])
                  for d in lanes) - first) // depth
    steps = max(skew[d] + len(spans_chunks[d]) for d in lanes) + lag
    if groups < 2 or len(lanes) < len(spans_chunks):
        first, groups = steps, 0
    return first, groups, steps


def _looped_steps(st, plan, static, looped) -> None:
    """Trace the steps of ``plan`` (``_step_plan``) on streamer ``st``:
    ``static(t)`` traces step ``t`` as it is, ``looped(g, i)`` step
    ``first + g * depth + i`` with ``g`` the loop's traced counter.
    The body's first waits are for descriptors made in front of the
    loop (same slot, same shape, same semaphore: a wait needs no more);
    the ones it leaves behind are its scope's, so after the loop the
    streamer holds again those from before it, which name the same
    slots, and its chunk counters say what the chip has counted."""
    first, groups, steps = plan
    depth = st.depth
    for t in range(first):
        static(t)
    if groups:
        held = [dict(m) for m in st.pending()]
        counted = [list(n) for n in (st.gc, st.rc)]

        def group(g, _):
            for i in range(depth):
                looped(g, i)

        lax.fori_loop(0, groups, group, None)
        for m, was in zip(st.pending(), held):
            m.clear()
            m.update(was)
        for n, was in zip((st.gc, st.rc), counted):
            n[:] = [a + (b - a) * groups for a, b in zip(was, n)]
    for t in range(first + groups * depth, steps):
        static(t)


def _mk_streamer(p, ndir, depth, credits, left, right, scratch,
                 mesh_ctx=None, axis_name=None):
    """The streamer over a kernel's whole ``_scratch_shapes`` list."""
    (send_buf, recv_buf, acc_buf, in_sem, acc_sem, st_sem, send_sem,
     recv_sem, cap_sem, _own_sem, *fold) = scratch
    base, stride = _dev_layout(mesh_ctx, axis_name)
    return _RingStreamer(p, ndir, depth, credits, left, right,
                         send_buf, recv_buf, acc_buf, in_sem, acc_sem,
                         st_sem, send_sem, recv_sem, cap_sem, base, stride,
                         *fold)


def _dev_layout(mesh_ctx, axis_name):
    """(base, stride) of the LOGICAL-device-id line a ring along
    ``axis_name`` walks. ``mesh_ctx`` is the full ordered
    (axis, size) tuple of the surrounding mesh (row-major device
    layout, make_mesh's convention) or None for the classic 1-D case.
    base folds in the traced coordinates of every OTHER axis, so it is
    a traced scalar; stride is static."""
    if not mesh_ctx or len(mesh_ctx) <= 1:
        return 0, 1
    stride, strides = 1, {}
    for name, size in reversed(tuple(mesh_ctx)):
        strides[name] = stride
        stride *= int(size)
    base = 0
    for name, _ in mesh_ctx:
        if name != axis_name:
            base = base + lax.axis_index(name) * strides[name]
    return base, strides[axis_name]


def _scratch_shapes(ndir: int, depth: int, chunk: int, dtype,
                    nfold: int = 0):
    """``chunk`` in rows. Slot and direction lead; the tiled trailing
    (chunk, 128) pair is only ever sliced on whole tiles. ``nfold``:
    the ring's further operands (``k - 1``), each one more slot beside
    every accumulator slot; none adds nothing to the list."""
    fold = [
        pltpu.VMEM((nfold, ndir, depth, chunk, _LANES), dtype),
        pltpu.SemaphoreType.DMA((nfold, ndir, depth)),     # their loads
    ] if nfold else []
    return [
        pltpu.VMEM((ndir, depth, chunk, _LANES), dtype),   # send slots
        pltpu.VMEM((ndir, depth, chunk, _LANES), dtype),   # recv slots
        pltpu.VMEM((ndir, depth, chunk, _LANES), dtype),   # accumulators
        pltpu.SemaphoreType.DMA((ndir, depth)),     # send-chunk loads
        pltpu.SemaphoreType.DMA((ndir, depth)),     # acc-chunk loads
        pltpu.SemaphoreType.DMA((ndir, depth)),     # stores
        pltpu.SemaphoreType.DMA((ndir, depth)),     # remote send
        pltpu.SemaphoreType.DMA((ndir, depth)),     # remote recv
        pltpu.SemaphoreType.REGULAR((ndir,)),       # slot credits
        pltpu.SemaphoreType.DMA(()),                # all-gather: own block
    ] + fold


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _block_spans(nblk: int, ndir: int,
                 tile: int = 1) -> List[Tuple[int, int]]:
    """Row ranges of a block per direction: the clockwise lane carries
    the first half, counter-clockwise the second, cut on a ``tile``
    boundary (a one-tile block leaves the second lane empty)."""
    if ndir == 1:
        return [(0, nblk)]
    h = -(-nblk // (2 * tile)) * tile
    return [(0, h), (h, nblk)]


def _ring_neighbours(axis_name, p):
    my = lax.axis_index(axis_name)
    return my, lax.rem(my - 1 + p, p), lax.rem(my + 1, p)


def _rs_rounds(st, my, p, ndir, spans_chunks, red, xs, w_hbm,
               o_blk=None):
    """Reduce-scatter: cw round s passes the partial of block (my-s-1)
    rightward and folds the arrival into block (my-s-2); the ccw lane
    mirrors with +. After p-1 rounds block ``my`` is fully reduced on
    both lanes (same convention as pallas_ring.py).

    A block is read from the operands ``xs``, ``k`` of them of one
    shape whose fold by ``red`` is this shard's contribution, until a
    fold has written it: each round folds into a block no round has
    touched, so its accumulator chunks come from ``xs``; round 0 sends
    an untouched block too, every later round the partial the round
    before stored into the working buffer ``w_hbm``. A chunk read from
    ``xs`` is read from all ``k`` and folded in VMEM as it is used, so
    over the rounds every block of every operand is read once. The last
    round folds into block ``my`` and stores it into ``o_blk`` where one
    is given, else into ``w_hbm`` like the others. ``xs`` are only ever
    read."""
    for s in range(p - 1):
        sb = [lax.rem(my - s - 1 + 2 * p, p), lax.rem(my + s + 1, p)]
        rb = [lax.rem(my - s - 2 + 2 * p, p), lax.rem(my + s + 2, p)]
        sent = xs if s == 0 else (w_hbm,)
        if o_blk is not None and s == p - 2:
            dst = [o_blk] * ndir
        else:
            dst = [w_hbm.at[b] for b in rb[:ndir]]
        st.stream_step(
            spans_chunks,
            [tuple(x.at[b] for x in sent) for b in sb[:ndir]],
            [tuple(x.at[b] for x in xs) for b in rb[:ndir]], dst, red)


def _ag_rounds(st, my, p, ndir, spans_chunks, o_hbm, x_blk=None):
    """All-gather: cw round s passes block (my-s) rightward, receives
    (my-s-1); ccw mirrors. Round 0 sends the rank's own block: from
    ``x_blk`` where one is given, else from block ``my`` of ``o_hbm``
    like every later round's."""
    for s in range(p - 1):
        sb = [lax.rem(my - s + 2 * p, p), lax.rem(my + s, p)]
        rb = [lax.rem(my - s - 1 + 2 * p, p), lax.rem(my + s + 1, p)]
        if x_blk is not None and s == 0:
            src = [(x_blk,)] * ndir
        else:
            src = [(o_hbm.at[b],) for b in sb[:ndir]]
        st.stream_step(spans_chunks, src, None,
                       [o_hbm.at[b] for b in rb[:ndir]], None)


def _hbm_all_reduce_kernel(axis_name, p, op, k, spans_chunks, depth, ndir,
                           credits, mesh_ctx, *refs):
    """``k`` operands, then o: each (p, block_rows, 128) in HBM, then
    the scratch. ``o_hbm`` is the fold rounds' working buffer: they
    write every block of it but ``my-1`` (``my+1`` on the ccw lane's
    rows), the gather rounds every block but ``my``, so nothing of the
    operands is copied ahead of the rounds."""
    xs, (o_hbm, *scratch) = refs[:k], refs[k:]
    my, left, right = _ring_neighbours(axis_name, p)
    st = _mk_streamer(p, ndir, depth, credits, left, right,
                      scratch, mesh_ctx, axis_name)
    st.enter()
    _rs_rounds(st, my, p, ndir, spans_chunks, _reducer(op), xs, o_hbm)
    _ag_rounds(st, my, p, ndir, spans_chunks, o_hbm)
    st.finish()


def _hbm_reduce_scatter_kernel(axis_name, p, op, k, spans_chunks, depth,
                               ndir, credits, mesh_ctx, *refs):
    """The reduce-scatter phase of the allreduce ring alone — the
    per-axis primitive of the multi-axis mesh decomposition. Streams
    the same p-1 fold rounds over the chunk-credit slot schedule: the
    ``k`` operands (p, block_rows, 128) are read where they lie, the
    partials on their way round live in the working buffer ``w_hbm`` (of
    the same shape), and the last round, which folds block ``my``
    whole, stores straight into the (block_rows, 128) output."""
    xs, (w_hbm, o_hbm, *scratch) = refs[:k], refs[k:]
    my, left, right = _ring_neighbours(axis_name, p)
    st = _mk_streamer(p, ndir, depth, credits, left, right,
                      scratch, mesh_ctx, axis_name)
    st.enter()
    _rs_rounds(st, my, p, ndir, spans_chunks, _reducer(op), xs, w_hbm,
               o_hbm)
    st.finish()


def _hbm_all_gather_kernel(axis_name, p, spans_chunks, depth, ndir,
                           credits, mesh_ctx, x_hbm, o_hbm, *scratch):
    """x: (block_rows, 128); o: (p, block_rows, 128). Round 0 sends the
    shard from ``x_hbm``; its copy into block ``my`` of the output,
    which no round reads or writes, runs under the rounds."""
    my, left, right = _ring_neighbours(axis_name, p)
    st = _mk_streamer(p, ndir, depth, credits, left, right,
                      scratch, mesh_ctx, axis_name)
    own = pltpu.make_async_copy(x_hbm, o_hbm.at[my], scratch[-1])
    own.start()
    st.enter()
    _ag_rounds(st, my, p, ndir, spans_chunks, o_hbm, x_hbm)
    own.wait()
    st.finish()


def _chain(st, spans_chunks, src, dst, sends, skew):
    """A pipelined chain, one chunk of each lane a step, as one chip
    runs it. In step t lane ``d`` handles its chunk ``t - skew[d]``:
    the upstream neighbour's chunk is taken into ``dst`` where one is
    given (``take``) and, where ``sends[d]`` says this chip passes the
    lane on, the same rows of ``src`` go downstream (``issue``). A chip
    that forwards has ``src`` and ``dst`` the same buffer, so it passes
    chunk j on as soon as j has landed, while upstream already sends
    j + 1.

    ``skew[d]`` is how many more hops from the root this chip lies
    along lane ``d`` than along its nearer lane: a chunk takes a step a
    hop, so the chunk of the farther lane that has reached this chip by
    step t is that much older. Without it a chip would wait, in every
    step, for a chunk that its neighbour can only send once it has this
    step's chunk of the other lane from this very chip: two wire times
    a step (the chip read exactly that: PERF.md section 6, PR 51).

    The steps differ in nothing but their row offsets, so they are not
    unrolled (``_looped_steps``, as a ring's round): the first are
    traced as they are (until every lane has ``depth`` chunks behind
    it, so that every send slot has a DMA to wait for), the whole
    groups of ``depth`` steps that follow, as long as every lane's
    chunk is a full one, run as one ``fori_loop`` body whose offsets
    are traced and whose slots are static, and what is left (a lane's
    odd chunk, its short last one, the farther lane's last ``skew``
    chunks) is traced after. The traced program is a few steps whatever
    the payload."""
    depth = st.depth
    lanes = [d for d, c in enumerate(spans_chunks) if c]
    chunk = spans_chunks[lanes[0]][0][1]
    order = sorted(lanes, key=lambda d: not sends[d])
    first, _groups, _steps = plan = _step_plan(spans_chunks, depth, skew)

    def step(chunks):
        for d in order:
            if chunks[d] is not None:
                if dst is not None:
                    st.take(d, dst, *chunks[d])
                if sends[d]:
                    st.issue(d, (src,), *chunks[d], None)

    def static(t):
        step([c[t - skew[d]] if 0 <= t - skew[d] < len(c) else None
              for d, c in enumerate(spans_chunks)])

    def looped(g, i):
        t = first + g * depth + i
        step([(pl.multiple_of(c[0][0] + (t - skew[d]) * chunk,
                              math.gcd(c[0][0], chunk)), chunk)
              for d, c in enumerate(spans_chunks)])

    _looped_steps(st, plan, static, looped)


def _hbm_bcast_kernel(axis_name, p, root, spans_chunks, depth, ndir,
                      credits, mesh_ctx, x_hbm, o_hbm, *scratch):
    """x, o: (rows, 128) in HBM. Lane 0 carries the first half of the
    rows clockwise from the root, lane 1 the second counter-clockwise.
    One straight-line schedule for each distance from the root, chosen
    once, each a few steps and a loop (``_chain``): the root only
    sends, chunk after chunk from its operand (which it alone reads:
    chunk by chunk for the wire, and whole by one HBM-to-HBM copy into
    its own output that runs under the chain); every other chip takes
    each chunk into its output and passes it on from there on the lanes
    it is not the last chip of (a lane ends at the root's neighbour
    against the lane's sense), the lane it lies farther along so many
    chunks behind the other. Which lanes a chip sends and receives on,
    and how far apart, is static inside a schedule, so the streamer's
    slot and credit counters stay plain Python; nobody's operand but
    the root's is touched."""
    my, left, right = _ring_neighbours(axis_name, p)

    def streamer():
        return _mk_streamer(p, ndir, depth, credits, left, right,
                            scratch, mesh_ctx, axis_name)
    streamer().enter()      # the barrier and the credits: every chip's
    dist = lax.rem(my - root + p, p)

    for k, sends, skew in _bcast_roles(p, ndir):
        @pl.when(dist == k)
        def _(k=k, sends=sends, skew=skew):
            st = streamer()     # each schedule counts its own chunks
            if k == 0:
                own = pltpu.make_async_copy(x_hbm, o_hbm, scratch[-1])
                own.start()
                st.pending_store["own"] = own   # drained with the stores
                _chain(st, spans_chunks, x_hbm, None, sends, skew)
            else:
                _chain(st, spans_chunks, o_hbm, o_hbm, sends, skew)
            st.finish()


def _sendrecv_kernel(axis_name, p, src, dst, x_hbm, o_hbm, send_sem,
                     recv_sem):
    """Single remote-DMA point-to-point exchange: HBM to remote HBM, no
    VMEM staging, no ppermute lowering. Every shard runs the same DMA
    (the transfer is a collective under the hood — the symmetric
    routing of rma/device.py's pallas_put), directed by a permutation
    that is identity except src<->dst: src and dst swap buffers, every
    other shard self-copies. One wait pair consumes both semaphores."""
    my = lax.axis_index(axis_name)
    partner = jnp.where(my == src, dst, jnp.where(my == dst, src, my))
    _entry_barrier([partner])
    rdma = pltpu.make_async_remote_copy(
        src_ref=x_hbm, dst_ref=o_hbm, send_sem=send_sem,
        recv_sem=recv_sem, device_id=partner,
        device_id_type=pltpu.DeviceIdType.LOGICAL)
    rdma.start()
    rdma.wait()


# ---------------------------------------------------------------------------
# wrappers
# ---------------------------------------------------------------------------

def _resolve_flags(interpret, credits):
    """(``interpret=`` for the pallas_call, credits on/off). The
    interpret half is the package-wide rule (_compat.resolve_interpret:
    never on a TPU backend, InterpretParams otherwise); the credit
    handshake is on wherever the kernel runs — the TPU interpreter
    executes remote signals, so CPU tests run the schedule the chip
    runs. ``credits=False`` remains for schedule experiments only."""
    return resolve_interpret(interpret), \
        (True if credits is None else bool(credits))


def _resolve_ndir(num_devices: int, bidirectional) -> int:
    if bidirectional is None:
        bidirectional = bool(get_config()["ICI_BIDIR"])
    return 2 if (bidirectional and num_devices > 2) else 1


def _ring_geometry(block_rows: int, dtype, chunk_bytes, depth,
                   num_devices: int, bidirectional):
    """``(chunk rows, depth, lanes, each lane's chunks)`` of a
    streaming ring over blocks of ``block_rows`` rows: what the cvars
    say where the caller says nothing."""
    chunk = min(_cfg_chunk_rows(dtype, chunk_bytes), block_rows)
    ndir = _resolve_ndir(num_devices, bidirectional)
    return chunk, _cfg_depth(depth), ndir, [
        _chunks(lo, hi, chunk) for lo, hi in
        _block_spans(block_rows, ndir, _sublanes(dtype))]


def _bcast_roles(p: int, ndir: int):
    """``(distance from the root, lanes the chip sends on, each lane's
    skew)`` of every schedule of the broadcast chain: the root's, then
    one for each chip after it (``_hbm_bcast_kernel``)."""
    yield 0, [True] * ndir, [0] * ndir
    for k in range(1, p):
        hops = [k, p - k][:ndir]            # from the root, along each lane
        yield k, [h < p - 1 for h in hops], [h - min(hops) for h in hops]


def ring_steps(coll: str, nelems: int, dtype, num_devices: int, *,
               chunk_bytes: Optional[int] = None,
               depth: Optional[int] = None,
               bidirectional: Optional[bool] = None) -> Dict[str, int]:
    """How the streaming kernel ``hbm_ring_<coll>`` ('allreduce',
    'reduce_scatter', 'allgather', 'bcast') is written for a shard of
    ``nelems`` elements (its ring blocks are the shard for a gather or
    a broadcast, a ``p``-th of it for a reduction): ``steps_traced``,
    the chunk steps its trace holds, and ``steps_looped``, the chunk
    steps its loops stand for, each summed over the kernel's rounds
    (over the chain's schedules for a broadcast). A kernel whose rounds
    are too short to loop (``_step_plan``) reads 0 looped."""
    p = num_devices
    block = nelems if coll in ("allgather", "bcast") else -(-nelems // p)
    _, d, ndir, spans_chunks = _ring_geometry(
        _tile_rows(block, dtype), dtype, chunk_bytes, depth, p,
        bidirectional)
    if coll == "bcast":
        plans = [_step_plan(spans_chunks, d, skew)
                 for _, _, skew in _bcast_roles(p, ndir)]
    else:
        rounds = (p - 1) * (2 if coll == "allreduce" else 1)
        plans = [_step_plan(spans_chunks, d, lag=1)] * rounds
    return {"steps_traced": sum(steps - groups * d + (d if groups else 0)
                                for _, groups, steps in plans),
            "steps_looped": sum(groups * d for _, groups, _ in plans)}


def _ring_call(kernel_fn, static, block_rows: int, dtype, cid: int,
               out_shape, interpret, credits, chunk_bytes, depth,
               num_devices: int, bidirectional, mesh_ctx, *operands):
    """The pallas_call every streaming ring shares: resolve the chunk
    geometry in rows, bind the static schedule into ``kernel_fn`` and
    launch with all operands left in HBM. More than one operand is the
    fold rounds' ``k``: one fold slot more for each beyond the first."""
    interpret, credits = _resolve_flags(interpret, credits)
    chunk, d, ndir, spans_chunks = _ring_geometry(
        block_rows, dtype, chunk_bytes, depth, num_devices, bidirectional)
    kernel = functools.partial(kernel_fn, *static, spans_chunks, d, ndir,
                               credits, mesh_ctx)
    multi = isinstance(out_shape, tuple)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        in_specs=[hbm] * len(operands),
        out_specs=(hbm,) * len(out_shape) if multi else hbm,
        scratch_shapes=_scratch_shapes(ndir, d, chunk, dtype,
                                       len(operands) - 1),
        compiler_params=compiler_params(collective_id=cid,
                                        has_side_effects=True),
        interpret=interpret,
        name=kernel_name(kernel_fn),
    )(*operands)


def _operands(x) -> Tuple[jax.Array, ...]:
    """The ring's operands as a tuple: ``x`` is one array, or a tuple
    of ``k`` arrays of one shape and dtype whose fold by the ring's op
    is this shard's contribution."""
    return tuple(x) if isinstance(x, (tuple, list)) else (x,)


def hbm_ring_all_reduce(x, axis_name: str, num_devices: int,
                        op: str = "sum", *,
                        chunk_bytes: Optional[int] = None,
                        depth: Optional[int] = None,
                        bidirectional: Optional[bool] = None,
                        credits: Optional[bool] = None,
                        interpret=None, mesh_ctx=None) -> jax.Array:
    """Allreduce along ``axis_name`` via the chunked HBM-streaming ring
    (pipelined reduce-scatter + all-gather). Any shape/size: the shard
    is flattened and padded to ``p`` whole-tile blocks with the op
    identity. ``x`` may be a tuple of ``k`` same-shaped arrays: the
    shard's contribution is their fold, which the fold rounds make
    chunk by chunk as they read them (each padded like one; a caller
    that minds ``k`` copies folds a ragged length first, as
    ``ici_all_reduce`` does). ``mesh_ctx``: the surrounding mesh's full
    ordered (axis, size) tuple when the ring is one phase of a
    multi-axis decomposition — device ids walk that axis' row-major id
    line instead of 0..p-1."""
    p = num_devices
    xs = _operands(x)
    if p == 1:
        from .collectives import allreduce
        return allreduce(_fold_unless_ring_does(xs, op), axis_name, op)
    shape, dtype = xs[0].shape, xs[0].dtype
    n = int(np.prod(shape)) if shape else 1
    rows = _tile_rows(-(-n // p), dtype)
    n_pad = p * rows * _LANES

    def blocks(a):
        flat = a.reshape(n)
        if n_pad > n:
            flat = jnp.pad(flat, (0, n_pad - n),
                           constant_values=_pad_identity(dtype, op))
        return flat.reshape(p, rows, _LANES)
    out = _ring_call(
        _hbm_all_reduce_kernel, (axis_name, p, op, len(xs)), rows, dtype,
        _CID_ALLREDUCE,
        jax.ShapeDtypeStruct((p, rows, _LANES), dtype),
        interpret, credits, chunk_bytes, depth, p, bidirectional,
        mesh_ctx, *[blocks(a) for a in xs])
    out = out.reshape(n_pad)
    return (out[:n] if n_pad > n else out).reshape(shape)


def hbm_ring_all_gather(x: jax.Array, axis_name: str, num_devices: int,
                        *, chunk_bytes: Optional[int] = None,
                        depth: Optional[int] = None,
                        bidirectional: Optional[bool] = None,
                        credits: Optional[bool] = None,
                        interpret=None, mesh_ctx=None) -> jax.Array:
    """All-gather along ``axis_name`` via the chunked HBM-streaming
    ring. ``x``: this shard's block [m, ...]; returns [p*m, ...]
    (tiled, like lax.all_gather(tiled=True))."""
    p = num_devices
    if p == 1:
        return lax.all_gather(x, axis_name, tiled=True)
    shape = x.shape
    m = int(np.prod(shape)) if shape else 1
    rows = _tile_rows(m, x.dtype)
    out = _ring_call(
        _hbm_all_gather_kernel, (axis_name, p), rows, x.dtype,
        _CID_ALLGATHER,
        jax.ShapeDtypeStruct((p, rows, _LANES), x.dtype),
        interpret, credits, chunk_bytes, depth, p, bidirectional,
        mesh_ctx, _as_blocks(x.reshape(m), 1, rows)[0])
    out = _from_blocks(out, m)
    return out.reshape((p * shape[0],) + shape[1:]) if shape else out


def all_gather_wire_bytes(nelems: int, dtype, num_devices: int) -> int:
    """Bytes one shard sends over ICI in one run of
    ``hbm_ring_all_gather`` on a ``[nelems]`` shard: ``p - 1`` blocks
    (its own, then each one it forwards; both lanes' halves together
    are one block a round), each the shard rounded up to whole tiles.
    The shard's own copy into the output is an HBM-to-HBM DMA that runs
    under the rounds and never reaches the wire. As many bytes
    arrive."""
    return ((num_devices - 1) * _tile_rows(nelems, dtype) * _LANES
            * np.dtype(dtype).itemsize)


def hbm_ring_bcast(x: jax.Array, axis_name: str, num_devices: int,
                   root: int, *, chunk_bytes: Optional[int] = None,
                   depth: Optional[int] = None,
                   bidirectional: Optional[bool] = None,
                   credits: Optional[bool] = None,
                   interpret=None, mesh_ctx=None) -> jax.Array:
    """Broadcast along ``axis_name`` via the chunked HBM-streaming ring:
    a pipelined chain from shard ``root`` (a trace-time constant), half
    the rows each way round where the axis has more than 2 shards.
    ``x``: this shard's block [m, ...]; the root's is the payload, the
    others' are never read. Returns the root's block on every shard, bit
    for bit. A length of whole tiles is handed to the kernel as it
    lies."""
    p = num_devices
    if p == 1:
        return x
    shape = x.shape
    m = int(np.prod(shape)) if shape else 1
    rows = _tile_rows(m, x.dtype)
    out = _ring_call(
        _hbm_bcast_kernel, (axis_name, p, int(root) % p), rows, x.dtype,
        _CID_BCAST,
        jax.ShapeDtypeStruct((rows, _LANES), x.dtype),
        interpret, credits, chunk_bytes, depth, p, bidirectional,
        mesh_ctx, _as_blocks(x.reshape(m), 1, rows)[0])
    return _from_blocks(out[None], m).reshape(shape)


def bcast_wire_bytes(nelems: int, dtype, num_devices: int) -> int:
    """Bytes the root sends over ICI in one run of ``hbm_ring_bcast`` on
    a ``[nelems]`` payload: the payload rounded up to whole tiles, once,
    both lanes' halves together. A chip that forwards sends the half of
    each lane it is not the last chip of (as much as the root where it
    is the last of neither, nothing of a lane it ends); every chip but
    the root receives what the root sends. The root's own copy is an
    HBM-to-HBM DMA that never reaches the wire."""
    del num_devices     # the chain's length moves the time, not the bytes
    return (_tile_rows(nelems, dtype) * _LANES * np.dtype(dtype).itemsize)


def hbm_ring_reduce_scatter(x, axis_name: str,
                            num_devices: int, op: str = "sum", *,
                            chunk_bytes: Optional[int] = None,
                            depth: Optional[int] = None,
                            bidirectional: Optional[bool] = None,
                            credits: Optional[bool] = None,
                            interpret=None, mesh_ctx=None) -> jax.Array:
    """Reduce-scatter along ``axis_name`` via the chunked HBM-streaming
    ring (the RS phase of the allreduce kernel alone). ``x``: this
    shard's full contribution [n], or a tuple of ``k`` such arrays
    whose fold it is (see ``hbm_ring_all_reduce``); returns block
    ``my`` of the folded array, [ceil(n/p)] (tiled; the tail blocks
    carry op-identity pad when p does not divide n)."""
    p = num_devices
    xs = _operands(x)
    if p == 1:
        return _xla_reduce_scatter(_fold_unless_ring_does(xs, op),
                                   axis_name, p, op)
    n, dtype = int(xs[0].size), xs[0].dtype
    nblk = -(-n // p)
    ident = _pad_identity(dtype, op)
    rows = _tile_rows(nblk, dtype)

    def blocks(a):
        flat = a.reshape(n)
        if nblk * p > n:
            flat = jnp.pad(flat, (0, nblk * p - n), constant_values=ident)
        return _as_blocks(flat, p, rows, ident)
    _, out = _ring_call(
        _hbm_reduce_scatter_kernel, (axis_name, p, op, len(xs)), rows,
        dtype, _CID_REDUCE_SCATTER,
        (jax.ShapeDtypeStruct((p, rows, _LANES), dtype),
         jax.ShapeDtypeStruct((rows, _LANES), dtype)),
        interpret, credits, chunk_bytes, depth, p, bidirectional,
        mesh_ctx, *[blocks(a) for a in xs])
    return _from_blocks(out[None], nblk)


def reduce_scatter_wire_bytes(nelems: int, dtype, num_devices: int) -> int:
    """Bytes one shard sends over ICI in one run of
    ``hbm_ring_reduce_scatter`` on a ``[nelems]`` contribution: one
    block in each of the ``p - 1`` fold rounds (both lanes' halves
    together are one block a round), each ``ceil(nelems / p)`` elements
    rounded up to whole tiles: what the all-gather of such blocks
    sends. Nothing else moves but the chunks' own loads and stores: the
    operand is read where it lies and the last fold stores into the
    output. As many bytes arrive."""
    return all_gather_wire_bytes(-(-int(nelems) // num_devices), dtype,
                                 num_devices)


def _xla_reduce_scatter(x: jax.Array, axis_name: str, p: int,
                        op: str) -> jax.Array:
    """The stock lowering of the tiled reduce-scatter: psum_scatter for
    sum (the only op it lowers natively), allreduce + slice otherwise.
    Input length must be a multiple of p (callers pad)."""
    flat = x.reshape(-1)
    if p == 1:
        return flat
    if op == "sum":
        return lax.psum_scatter(flat, axis_name, scatter_dimension=0,
                                tiled=True)
    from .collectives import allreduce
    y = allreduce(flat, axis_name, op)
    nblk = y.size // p
    i = lax.axis_index(axis_name)
    return lax.dynamic_slice(y, (i * nblk,), (nblk,))


def remote_sendrecv(x: jax.Array, axis_name: str, num_devices: int,
                    src: int, dst: int, *, interpret=None) -> jax.Array:
    """The ppermute-free pt2pt lane: one remote DMA exchanges ``x``
    between shards ``src`` and ``dst`` (HBM to HBM over ICI, no VMEM
    staging, no collective lowering) — dst's return is src's buffer and
    vice versa; every other shard returns its own ``x`` unchanged.
    MPI_Sendrecv exchange semantics, not ppermute's zero fill."""
    p = num_devices
    if p == 1 or src == dst:
        return x
    interpret = resolve_interpret(interpret)
    _trace_entry("sendrecv", "hbm", x.size * x.dtype.itemsize,
                 src=src, dst=dst)
    kernel = functools.partial(_sendrecv_kernel, axis_name, p, src, dst)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                        pltpu.SemaphoreType.DMA(())],
        compiler_params=compiler_params(collective_id=_CID_SENDRECV,
                                        has_side_effects=True),
        interpret=interpret,
        name=kernel_name(_sendrecv_kernel),
    )(x)


# ---------------------------------------------------------------------------
# tier dispatch (the device-side tuning-table moment)
# ---------------------------------------------------------------------------

def _kernels_runnable(interpret: Optional[bool]) -> bool:
    """On a TPU backend the kernels compile, always; anywhere else they
    run only under the interpreter (tests, the CPU mesh CI). A backend
    that fails to initialize raises here — it is never read as 'no
    TPU'."""
    if on_tpu():
        return True
    if interpret is None:
        interpret = bool(get_config()["ICI_INTERPRET"])
    return bool(interpret)


def planned_tier(name: str, shard_nbytes: int, dtype, op: Optional[str],
                 interpret=None, num_devices: Optional[int] = None,
                 multi_axis: bool = False) -> Tuple[str, Optional[str]]:
    """(tier, fallback_reason) for one device collective call: the one
    rule. ``ici_all_reduce`` / ``ici_all_gather`` / ``ici_reduce_scatter``
    / ``ici_bcast`` lower what it says, and the channel's per-call
    accounting (coll/device.py ``_decide_tier``) counts what it says, so
    the pvar a call bumps is the kernel its program holds. ``name`` is the MPI
    collective whose lowering asks ('allreduce', 'reduce', 'allgather',
    'reduce_scatter_block', 'bcast'; 'alltoall' through
    pallas_alltoall.planned_a2a_tier); ``shard_nbytes`` what must fit
    the engine (the gather keys on its OUTPUT bytes); ``multi_axis``
    says the ring is one axis of a multi-axis mesh.

    tier is 'vmem' | 'hbm' | 'quant' | 'xla'. reason names the
    dev_coll_fallback_* pvar bucket of an XLA take that is a fallback:
    platform (not a TPU and not interpreting), dtype (op/dtype the
    kernels cannot reduce), shape (degenerate extent), size (past the
    measured XLA crossover). Two XLA takes are no fallback (reason
    None): a broadcast where it has no engine yet (the 'vmem' bin; a
    multi-axis mesh), and a multi-axis mesh under the interpreter,
    whose remote-DMA discharge refuses more than one named axis (the
    decomposition above the phase is the same, which is what the CPU
    sweep pins).

    Past the size bins (coll/tuning.device_tier) the answer is the
    engine that can carry the call, each a bit-exact move, never an XLA
    take: a 'quant' bin the call cannot quantize (non-sum op, int
    dtype, budget below the declared bound for ``num_devices``) is
    'hbm'; the flat VMEM ring carries sums and gathers of a 1-D mesh
    only, so 'vmem' with another op, for a reduce-scatter (no flat
    kernel, and the chunked engine has no size floor: it pads) or on a
    multi-axis mesh (the VMEM and quant engines address devices 1-D)
    is 'hbm' too. A broadcast has the streaming chain and nothing else:
    past the 'vmem' bin on a 1-D mesh it is 'hbm' (a 'quant' bin too:
    it moves bits)."""
    if not _kernels_runnable(interpret):
        return "xla", "platform"
    if multi_axis and not on_tpu():
        return "xla", None
    if op is not None and op not in _SUPPORTED_OPS:
        return "xla", "dtype"
    if dtype_kind(dtype) not in "fiu":
        return "xla", "dtype"
    if shard_nbytes <= 0:
        return "xla", "shape"
    from ..coll.tuning import device_tier
    tier = device_tier(name, shard_nbytes)
    if tier == "xla":
        return "xla", "size"
    if name == "bcast":
        return ("xla" if tier == "vmem" or multi_axis else "hbm"), None
    if tier == "quant":
        from . import pallas_quant
        if multi_axis or not pallas_quant.quant_eligible(
                name, dtype, op, num_devices):
            tier = "hbm"
    elif tier == "vmem" and (multi_axis or name == "reduce_scatter_block"
                             or op not in (None, "sum")):
        tier = "hbm"
    return tier, None


def _trace_entry(coll: str, tier: str, nbytes: int, op=None, ring=None,
                 **extra) -> None:
    """Drop a 'device'-lane instant at an ICI entry point. These
    wrappers execute at TRACE time (once per compiled signature, not
    per call — programs are cached), so the instant records which tier
    a signature LOWERED to and, of one that lowered to the streaming
    ring (``ring``: the shard's ``(nelems, dtype, num_devices)``), how
    many of its chunk steps are written out and how many its loops
    stand for (``ring_steps``); the per-call span lives one level up in
    coll/device.py. One recorder lookup, nothing when untraced."""
    try:
        from ..runtime.universe import current_universe
        u = current_universe()
        rec = u.engine.tracer if u is not None else None
        if rec is not None:
            if ring is not None and tier == "hbm":
                extra.update(ring_steps(coll, *ring))
            rec.record("device", f"ici_{coll}", "i", tier=tier,
                       bytes=int(nbytes), op=op, **extra)
    except Exception:   # tracing must never kill a lowering
        pass


def _multi_axis(mesh_ctx) -> bool:
    """The ring is one axis of a surrounding multi-axis mesh."""
    return bool(mesh_ctx) and len(mesh_ctx) > 1


def ring_folds(tier: str, n: int, dtype, num_devices: int,
               multi_axis: bool = False) -> bool:
    """Whether a reduction of ``tier`` folds a shard's ``k`` flat
    ``[n]`` operands inside its ring's fold rounds: the streaming ring
    of a 1-D mesh, where each operand makes ``p`` whole-tile blocks as
    it lies (a pad would be ``k`` copies). Everywhere else they are
    folded first and the ring takes the one result. Asked by
    ``ici_all_reduce`` / ``ici_reduce_scatter`` of the operands they
    were given and by the fold channel's leader for what it counts
    (coll/device.py ``dev_fold_in_ring``)."""
    return (tier == "hbm" and not multi_axis
            and n % (num_devices * _sublanes(dtype) * _LANES) == 0)


def _fold_unless_ring_does(xs, op: str, tier: Optional[str] = None,
                           p: int = 1, mesh_ctx=None):
    """The dispatchers' operand: ``xs`` as they are where the ring of
    ``tier`` folds them (``ring_folds``) or there is one; else their
    slot reduction, the fold the channel traced in front of the ring
    before the ring could (coll/device.py ``_slot_fold``)."""
    if len(xs) == 1:
        return xs[0]
    if xs[0].ndim == 1 and ring_folds(tier, xs[0].size, xs[0].dtype, p,
                                      _multi_axis(mesh_ctx)):
        return xs
    from ..coll.device import _slot_fold
    return _slot_fold(xs, op)


def ici_all_reduce(x, axis_name: str, num_devices: int,
                   op: str = "sum", interpret=None,
                   mesh_ctx=None) -> jax.Array:
    """Tier-dispatched device allreduce: the engine ``planned_tier``
    names (VMEM-resident flat ring, HBM-streaming chunked ring,
    quantized wire) or the XLA lowering. ``x`` may be a tuple of ``k``
    same-shaped arrays whose fold is the shard's contribution (two
    ranks a chip: their deposits): the streaming ring folds them in its
    rounds (``ring_folds``), every other engine takes their slot
    reduction. The per-call fallback pvar accounting lives in
    coll/device.py; direct shard_map users are counted once per traced
    shape."""
    from .collectives import allreduce
    p = num_devices
    xs = _operands(x)
    if p == 1:
        return allreduce(_fold_unless_ring_does(xs, op), axis_name, op)
    nbytes = xs[0].size * xs[0].dtype.itemsize
    tier, reason = planned_tier("allreduce", nbytes, xs[0].dtype, op,
                                interpret, p, _multi_axis(mesh_ctx))
    x = _fold_unless_ring_does(xs, op, tier, p, mesh_ctx)
    _trace_entry("allreduce", tier, nbytes, op=op,
                 ring=(xs[0].size, xs[0].dtype, p))
    if tier == "quant":
        from . import pallas_quant
        return pallas_quant.quant_ring_all_reduce(x, axis_name, p, op,
                                                  interpret=interpret)
    if tier == "vmem":
        from . import pallas_ring
        return pallas_ring.ring_all_reduce(x, axis_name, p,
                                           interpret=interpret)
    if tier == "hbm":
        return hbm_ring_all_reduce(x, axis_name, p, op,
                                   interpret=interpret,
                                   mesh_ctx=mesh_ctx)
    if reason is not None:
        note_fallback("allreduce", reason, nbytes, x.dtype)
    return allreduce(x, axis_name, op)


def ici_all_gather(x: jax.Array, axis_name: str, num_devices: int,
                   interpret=None, mesh_ctx=None) -> jax.Array:
    """Tier-dispatched device all-gather (tiled). The gather output is
    p times the shard, so tier selection keys on the OUTPUT bytes —
    that is what must fit in VMEM."""
    p = num_devices
    if p == 1:
        return lax.all_gather(x, axis_name, tiled=True)
    out_nbytes = x.size * x.dtype.itemsize * p
    tier, reason = planned_tier("allgather", out_nbytes, x.dtype, None,
                                interpret, p, _multi_axis(mesh_ctx))
    _trace_entry("allgather", tier, out_nbytes, ring=(x.size, x.dtype, p))
    if tier == "vmem":
        from . import pallas_ring
        return pallas_ring.ring_all_gather(x, axis_name, p,
                                           interpret=interpret)
    if tier == "hbm":
        return hbm_ring_all_gather(x, axis_name, p, interpret=interpret,
                                   mesh_ctx=mesh_ctx)
    if reason is not None:
        note_fallback("allgather", reason, out_nbytes, x.dtype)
    return lax.all_gather(x, axis_name, tiled=True)


def ici_bcast(x: jax.Array, axis_name: str, num_devices: int, root: int,
              interpret=None, mesh_ctx=None) -> jax.Array:
    """Tier-dispatched device broadcast from shard ``root`` (static):
    the streaming chain where ``planned_tier`` names it, else the XLA
    lowering (ops/collectives.bcast, a one-hot psum)."""
    p = num_devices
    if p == 1:
        return x
    nbytes = x.size * x.dtype.itemsize
    tier, reason = planned_tier("bcast", nbytes, x.dtype, None, interpret,
                                p, _multi_axis(mesh_ctx))
    _trace_entry("bcast", tier, nbytes, root=root,
                 ring=(x.size, x.dtype, p))
    if tier == "hbm":
        return hbm_ring_bcast(x, axis_name, p, root, interpret=interpret,
                              mesh_ctx=mesh_ctx)
    if reason is not None:
        note_fallback("bcast", reason, nbytes, x.dtype)
    from .collectives import bcast
    return bcast(x, axis_name, root)


def ici_reduce_scatter(x, axis_name: str, num_devices: int,
                       op: str = "sum", interpret=None,
                       mesh_ctx=None) -> jax.Array:
    """Tier-dispatched device reduce-scatter (tiled): this shard's
    block of the axis-folded array, [ceil(n/p)], by the chunked HBM
    engine (the one with a reduce-scatter entry) or the XLA lowering.
    ``x`` may be a tuple of ``k`` arrays, as for ``ici_all_reduce``."""
    p = num_devices
    xs = _operands(x)
    if p == 1:
        return _fold_unless_ring_does(xs, op).reshape(-1)
    nbytes = xs[0].size * xs[0].dtype.itemsize
    tier, reason = planned_tier("reduce_scatter_block", nbytes, xs[0].dtype,
                                op, interpret, p, _multi_axis(mesh_ctx))
    x = _fold_unless_ring_does(xs, op, tier, p, mesh_ctx)
    _trace_entry("reduce_scatter", tier, nbytes, op=op,
                 ring=(xs[0].size, xs[0].dtype, p))
    if tier == "hbm":
        return hbm_ring_reduce_scatter(x, axis_name, p, op,
                                       interpret=interpret,
                                       mesh_ctx=mesh_ctx)
    if reason is not None:
        note_fallback("reduce_scatter", reason, nbytes, x.dtype)
    n = int(x.size)
    flat = x.reshape(n)
    nblk = -(-n // p)
    if nblk * p > n:
        flat = jnp.pad(flat, (0, nblk * p - n),
                       constant_values=_pad_identity(x.dtype, op))
    return _xla_reduce_scatter(flat, axis_name, p, op)


# ---------------------------------------------------------------------------
# multi-axis torus composition (the 2D/3D mesh decomposition)
# ---------------------------------------------------------------------------

def _mesh_axes_min() -> int:
    """The dev_tier_axes_min edge (explicit cvar > measured profile >
    default): shard bytes at or above it take the per-axis RS/AG phase
    decomposition; below it each axis runs a full allreduce in
    sequence. -1 = always decompose."""
    from ..coll.tuning import _dev_tier_edge
    return _dev_tier_edge("DEV_TIER_AXES_MIN", "dev_tier_axes_min")


def _trace_axis(phase: str, axis: str, nbytes: int, op=None) -> None:
    """Per-axis 'device'-lane instant of the multi-axis decomposition
    (ici_axis_rs / ici_axis_ag / ici_axis_ar) — recorded at trace time
    like _trace_entry, one instant per phase per compiled signature."""
    try:
        from ..runtime.universe import current_universe
        u = current_universe()
        rec = u.engine.tracer if u is not None else None
        if rec is not None:
            rec.record("device", f"ici_axis_{phase}", "i", axis=axis,
                       bytes=int(nbytes), op=op)
    except Exception:   # tracing must never kill a lowering
        pass


def ici_all_reduce_mesh(x: jax.Array, axes, op: str = "sum",
                        interpret=None) -> jax.Array:
    """Allreduce over a multi-axis torus mesh, decomposed as per-axis
    ring phases: reduce-scatter down the axis list, all-gather back up
    (RS-x, RS-y, AG-y, AG-x on a 2-D mesh), each phase the chunk-credit
    slot schedule of the single-axis engine on a payload shrunk by the
    axes already folded — every element crosses each axis' ICI links
    once. ``axes``: ordered (axis_name, size) pairs covering the mesh.

    Below the MV2T_DEV_TIER_AXES_MIN edge the decomposition is not
    worth its phase count (4 kernel launches on 2-D vs 2): each axis
    runs a full allreduce in sequence instead — the latency shape.
    Each phase asks ``planned_tier`` as one axis of a multi-axis mesh,
    so it streams through the HBM engine at every size (the flat VMEM
    ring addresses devices 1-D). Unit axes are skipped; a single live
    axis degenerates to the 1-D dispatch."""
    allx = tuple((str(a), int(s)) for a, s in axes)
    live = [(a, s) for a, s in allx if s > 1]
    if not live:
        return x
    # ctx spans EVERY named axis (unit axes included): the interpret
    # discharge counts axis names, not extents, and the hardware id
    # line must fold in every coordinate
    ctx = allx
    if len(live) == 1:
        return ici_all_reduce(x, live[0][0], live[0][1], op,
                              interpret=interpret, mesh_ctx=ctx)
    shape = x.shape
    n = int(x.size)
    nbytes = n * x.dtype.itemsize
    amin = _mesh_axes_min()
    if amin >= 0 and nbytes < amin:
        y = x
        for a, s in live:
            _trace_axis("ar", a, nbytes, op=op)
            y = ici_all_reduce(y, a, s, op, interpret=interpret,
                               mesh_ctx=ctx)
        return y
    ptot = 1
    for _, s in live:
        ptot *= s
    flat = x.reshape(n)
    n_pad = -(-n // ptot) * ptot
    if n_pad > n:
        flat = jnp.pad(flat, (0, n_pad - n),
                       constant_values=_pad_identity(x.dtype, op))
    y = flat
    for a, s in live:
        _trace_axis("rs", a, y.size * y.dtype.itemsize, op=op)
        y = ici_reduce_scatter(y, a, s, op, interpret=interpret,
                               mesh_ctx=ctx)
    for a, s in reversed(live):
        _trace_axis("ag", a, y.size * y.dtype.itemsize * s, op=op)
        y = ici_all_gather(y, a, s, interpret=interpret, mesh_ctx=ctx)
    if n_pad > n:
        y = y[:n]
    return y.reshape(shape)


def ici_all_gather_mesh(x: jax.Array, axes, interpret=None) -> jax.Array:
    """All-gather over a multi-axis mesh (tiled): gather the innermost
    axis first, then outward — with ranks laid out row-major over the
    flattened device order, the blocks land in rank order."""
    ctx = tuple((str(a), int(s)) for a, s in axes)
    live = [(a, s) for a, s in ctx if s > 1]
    y = x.reshape(-1)
    for a, s in reversed(live):
        _trace_axis("ag", a, y.size * y.dtype.itemsize * s)
        y = ici_all_gather(y, a, s, interpret=interpret, mesh_ctx=ctx)
    return y


def ici_reduce_scatter_mesh(x: jax.Array, axes, op: str = "sum",
                            interpret=None) -> jax.Array:
    """Reduce-scatter over a multi-axis mesh (tiled): fold outermost
    axis first, then inward — rank (i, j) of a row-major 2-D mesh ends
    holding block i*py + j, i.e. block ``rank``. Input length must be a
    multiple of the mesh extent for exact tiling (callers pad)."""
    ctx = tuple((str(a), int(s)) for a, s in axes)
    live = [(a, s) for a, s in ctx if s > 1]
    y = x.reshape(-1)
    for a, s in live:
        _trace_axis("rs", a, y.size * y.dtype.itemsize, op=op)
        y = ici_reduce_scatter(y, a, s, op, interpret=interpret,
                               mesh_ctx=ctx)
    return y
