"""Chunked HBM remote-DMA alltoall(v) — the MoE dispatch/combine lane.

The missing workload shape of the device engine: every prior tier moves
one logical payload (allreduce/bcast/gather); MoE serving moves ``p``
per-peer payloads per step (token dispatch to experts, then the
combine), with counts skewed by the router. This module lowers both the
uniform MPI_Alltoall and the variable-count MPI_Alltoallv onto the same
slot/credit streaming engine as ops/pallas_ici.py:

  * **Schedule** — the classic pairwise-permutation exchange: at step
    ``s`` (1..p-1) every shard sends block ``(my+s)%p`` to that peer
    and receives block ``(my-s)%p`` from the opposite one, so each
    receiver has exactly one writer per step and the whole step is a
    fixed permutation (no ring rotation of partials — alltoall payloads
    are distinct, nothing folds). The local block short-circuits as
    HBM-to-HBM DMAs of a chunk's rows, one started with each chunk of
    the wire steps, so that they run under them, all waited for at the
    kernel's end.
  * **Slot discipline** — chunks stream through the same
    double-buffered VMEM slots, addressed by a per-lane *global* chunk
    counter that keeps counting across steps (slot = gc % depth): the
    same collision-free sequence the chunk-credit model proves for the
    ring, now with the writer changing per step.
  * **Flow control** — per-step credit waves: at step entry every
    shard grants ``depth`` slot credits to the shard about to write
    into it; the receiver re-grants per consumed chunk; at step exit
    the sender fences on its credit balance returning to ``depth``
    (its receiver consumed everything), which is exactly the condition
    that makes the next step's writes land in free slots. The kernel
    opens with an all-peers entry barrier (every shard is written by
    every other before it ends, and the first writer differs per
    lane).
  * **alltoallv** — per-peer counts/displs are static at build time
    (the mesh channel knows the full count matrix). The wrapper packs
    each rank's sends into uniform whole-tile per-peer blocks
    ``(p, block_rows, 128)`` on the XLA side (one dynamic slice per
    peer) and scatters the received blocks' valid prefixes to their
    displacements afterwards, so the kernel is the uniform one: a
    single rank-symmetric op sequence with traced peer indices —
    paired shards must meet at the SAME op instance, so nothing that
    rendezvouses may live under a rank conditional. The count matrix
    only sets how many rows each permutation step moves: the step-wide
    maximum (``rows_s = max_r tile_rows(counts[r][(r+s)%p])``), so the
    DMA byte counts — and therefore the send/recv semaphore pairing —
    stay uniform along the whole permutation even when the counts are
    skewed; a step nobody has payload for is skipped mesh-wide, and a
    pair with fewer (or zero) valid elements moves pad rows but still
    runs the full credit wave, so no credit leaks on a zero-count peer
    (the model variant in analysis/model/ici.py seeds exactly that
    bug).
  * **Bidirectional** on >2-shard axes: the step list splits across
    two lanes with disjoint slot arrays (steps 1..ceil((p-1)/2) travel
    "rightward", the rest "leftward"), both pipelines in flight at
    once.

Tier selection collapses onto the streaming tier (there is no VMEM
flat-ring or quantized wire for alltoall yet): coll/tuning's
``device_tier`` answers hbm or xla, every xla take is counted by the
``dev_coll_fallback_*`` family, and the XLA lowering (lax.all_to_all,
plus a scatter-packed emulation for the v-variant) stays the bit-exact
fallback. Usage: inside ``shard_map`` over a 1-D mesh axis, or through
the mesh-bound MPI channel (coll/device.py).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ._compat import compiler_params, kernel_name, note_fallback
from .pallas_ici import (_LANES, _RingStreamer, _as_blocks,
                         _cfg_chunk_rows, _cfg_depth, _chunks,
                         _entry_barrier, _from_blocks, _resolve_flags,
                         _resolve_ndir, _tile_rows, _trace_entry,
                         planned_tier)

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# cvar/pvar declarations (ICI_* knobs are shared with the ring engine)
from .. import mpit  # noqa: F401,E402

# Mosaic collective id (pallas_ring owns 0/1, pallas_ici 2-5,
# pallas_quant 6, pallas_rma 7-10); alltoall and alltoallv are one
# kernel
_CID_ALLTOALL = 11


# ---------------------------------------------------------------------------
# streaming state — the pairwise-permutation form of _RingStreamer
# ---------------------------------------------------------------------------

def _copy(src, dst, sem):
    cp = pltpu.make_async_copy(src, dst, sem)
    cp.start()
    cp.wait()


class _A2AStreamer(_RingStreamer):
    """_RingStreamer with the fixed ring neighbors replaced by per-step
    exchange peers and the single end-of-kernel credit barrier replaced
    by per-step credit waves (grant depth at entry, fence back to depth
    at exit — see module docstring). The pending-handle containers,
    slot counters, and take/grant primitives are inherited unchanged;
    only the peer routing and the load/store halves differ (alltoall
    loads from the *input* buffer and never folds)."""

    def __init__(self, *args):
        super().__init__(*args)
        # per-lane step peers — the ring's shared left/right would let
        # one lane's set_step clobber the other's routing
        self.step_dst = [None] * self.ndir
        self.step_up = [None] * self.ndir
        self.own = None         # the local block's copy: set_own

    def set_own(self, my, chunks, sem):
        """The local block ``x[my] -> o[my]`` is ``chunks``, static
        (row offset, rows), still to be copied, all on DMA semaphore
        ``sem`` (no wave touches it)."""
        self.own = (my, list(chunks), sem)

    def copy_own_piece(self, x_hbm, o_hbm):
        """Start the next piece of the local block's copy, HBM to HBM,
        no wire; False when none was left. Nothing waits for it here:
        it is parked with the stores and drained by ``finish()``, so it
        runs under the wave's chunks. No wave writes ``o[my]`` (drains store
        into ``o[step_up]``) and ``x`` is only read, so the order is
        free. A piece a chunk step and not the block in one DMA: that
        one, started in front of the first wave, held the wave's own
        loads back for as long as it ran (PERF.md §6, PR 52)."""
        my, chunks, sem = self.own
        if not chunks:
            return False
        off, sz = chunks.pop(0)
        cp = pltpu.make_async_copy(x_hbm.at[my, pl.ds(off, sz)],
                                   o_hbm.at[my, pl.ds(off, sz)], sem)
        cp.start()
        self.pending_store["own", off] = cp
        return True

    def set_step(self, d, dst, upstream):
        """Lane ``d`` now sends to ``dst`` and is written by
        ``upstream``."""
        self.step_dst[d] = dst
        self.step_up[d] = upstream

    def grant_step_credits(self, d):          # device: hw-only
        """Step entry: hand ``depth`` slot credits to the shard about
        to write into us this step (our slots are provably free — the
        previous step's fence drained them)."""
        if not self.credits:
            return
        pltpu.semaphore_signal(
            self.cap_sem.at[d], inc=self.depth,
            device_id=self._dev(self.step_up[d]),
            device_id_type=pltpu.DeviceIdType.LOGICAL)

    def _grant(self, d):                      # device: hw-only
        """Per-consume re-grant, targeted at the lane's current step
        writer (the ring's left/right routing does not apply)."""
        if not self.credits:
            return
        pltpu.semaphore_signal(
            self.cap_sem.at[d], inc=1,
            device_id=self._dev(self.step_up[d]),
            device_id_type=pltpu.DeviceIdType.LOGICAL)

    def step_fence(self, d):                  # device: hw-only
        """Step exit: wait for the credit balance to return to
        ``depth`` — our receiver consumed every chunk we wrote — then
        retire the wave's credits so the next step starts from zero."""
        if not self.credits:
            return
        pltpu.semaphore_wait(self.cap_sem.at[d], self.depth)

    def issue_a2a(self, d, x_hbm, off, sz):
        """Front half: retire the slot's previous outbound DMA, load
        rows [off, off+sz) of the block bound for this step's peer into
        the send slot, then launch the remote DMA — the one op both
        sides of the pair rendezvous on, traced once for all ranks
        (the peer index stays traced arithmetic)."""
        slot = self.gc[d] % self.depth
        prev = self.pending_send.pop((d, slot), None)
        if prev is not None:
            prev.wait_send()
        dst = self.step_dst[d]
        _copy(x_hbm.at[dst, pl.ds(off, sz)],
              self.send_buf.at[d, slot, pl.ds(0, sz)],
              self.in_sem.at[d, slot])
        self._take_credit(d)
        rdma = pltpu.make_async_remote_copy(
            src_ref=self.send_buf.at[d, slot, pl.ds(0, sz)],
            dst_ref=self.recv_buf.at[d, slot, pl.ds(0, sz)],
            send_sem=self.send_sem.at[d, slot],
            recv_sem=self.recv_sem.at[d, slot],
            device_id=self._dev(dst),
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        rdma.start()
        self.pending_send[(d, slot)] = rdma
        self.gc[d] += 1
        return slot

    def drain_a2a(self, d, slot, o_hbm, off, sz):
        """Back half: the chunk from this step's writer has landed —
        store it to rows [off, off+sz) of that writer's output block
        and re-grant the slot. The store's wait keeps the slot's
        payload live until it is out."""
        self.pending_send[(d, slot)].wait_recv()
        _copy(self.recv_buf.at[d, slot, pl.ds(0, sz)],
              o_hbm.at[self.step_up[d], pl.ds(off, sz)],
              self.st_sem.at[d, slot])
        self._grant(d)

    def finish(self):
        """Exit barrier: outbound DMAs off the send slots. The per-step
        fences already proved every written chunk was consumed, so
        there is no final credit wait (the balance is zero by
        construction, unlike the ring's resting ``depth``)."""
        for key, h in list(self.pending_send.items()):
            h.wait_send()
            del self.pending_send[key]
        self.drain_stores()


def _mk_a2a_streamer(p, ndir, depth, credits, scratch):
    send_buf, recv_buf, in_sem, st_sem, send_sem, recv_sem, cap_sem = \
        scratch
    return _A2AStreamer(p, ndir, depth, credits, 0, 0,
                        send_buf, recv_buf, None, in_sem, None, st_sem,
                        send_sem, recv_sem, cap_sem)


def _a2a_scratch_shapes(ndir: int, depth: int, chunk: int, dtype):
    return [
        pltpu.VMEM((ndir, depth, chunk, _LANES), dtype),   # send slots
        pltpu.VMEM((ndir, depth, chunk, _LANES), dtype),   # recv slots
        pltpu.SemaphoreType.DMA((ndir, depth)),     # send-chunk loads
        pltpu.SemaphoreType.DMA((ndir, depth)),     # stores
        pltpu.SemaphoreType.DMA((ndir, depth)),     # remote send
        pltpu.SemaphoreType.DMA((ndir, depth)),     # remote recv
        pltpu.SemaphoreType.REGULAR((ndir,)),       # slot credits
        pltpu.SemaphoreType.DMA(()),                # local-block copy
    ]


def _lane_steps(p: int, ndir: int) -> List[List[int]]:
    """Permutation steps 1..p-1 split across lanes: the first lane
    carries the near ("rightward") half, the second the far half —
    both directions of the physical ring are driven at once on >2
    shard axes."""
    steps = list(range(1, p))
    if ndir == 1:
        return [steps]
    h = (len(steps) + 1) // 2
    return [steps[:h], steps[h:]]


def _a2a_wave(st, x_hbm, o_hbm, lanes):
    """One permutation step across the active lanes: grant the step's
    credits, pipeline issue-chunk-c / drain-chunk-(c-1) per lane (and
    start a piece of the local block's copy beside each), then
    fence. ``lanes``: (d, dst, upstream, chunks) with chunks the static
    (row offset, rows) list the step moves."""
    for d, dst, up, _ch in lanes:
        st.set_step(d, dst, up)
        st.grant_step_credits(d)
    cmax = max(len(ch) for _d, _t, _u, ch in lanes)
    slots = {d: [None] * len(ch) for d, _t, _u, ch in lanes}
    for c in range(cmax + 1):
        st.copy_own_piece(x_hbm, o_hbm)
        for d, _t, _u, chunks in lanes:
            if c < len(chunks):
                slots[d][c] = st.issue_a2a(d, x_hbm, *chunks[c])
        for d, _t, _u, chunks in lanes:
            if 1 <= c <= len(chunks):
                st.drain_a2a(d, slots[d][c - 1], o_hbm, *chunks[c - 1])
    for d, _t, _u, _ch in lanes:
        st.step_fence(d)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _hbm_alltoall_kernel(axis_name, p, step_rows, chunk, depth, ndir,
                         credits, x_hbm, o_hbm, *scratch):
    """Alltoall(v): input (p, block_rows, 128) — block j is the payload
    for shard j — output the same shape with block j received from
    shard j. ``step_rows[s]`` is the static row count permutation step
    ``s`` moves (s=0: the local block); uniform alltoall moves whole
    blocks, the v-variant the step-wide maximum. The local block's
    copy runs under the waves: a piece of a chunk's rows is started
    with each chunk step (the uniform block is out with the first
    wave's), and the waits are in ``st.finish()``, with the streamer's
    stores. The chunk schedule is globally uniform, so the whole
    program is symmetric — every shard's k-th outgoing handle pairs
    with its k-th arrival and the peer indices stay traced
    arithmetic."""
    my = lax.axis_index(axis_name)
    st = _mk_a2a_streamer(p, ndir, depth, credits, scratch[:-1])

    # every shard writes into every other before the kernel ends, and
    # the first writer differs per lane: barrier with all peers
    _entry_barrier([lax.rem(my + s, p) for s in range(1, p)])

    # local block: HBM-to-HBM DMAs, no wire, started a piece a chunk
    # step under the waves and waited for in finish()
    st.set_own(my, _chunks(0, step_rows[0], chunk), scratch[-1])

    steps = _lane_steps(p, ndir)
    for q in range(max(len(ls) for ls in steps)):
        lanes = []
        for d in range(ndir):
            if q >= len(steps[d]):
                continue
            s = steps[d][q]
            if step_rows[s] == 0:
                continue                # whole step is empty mesh-wide
            lanes.append((d, lax.rem(my + s, p), lax.rem(my - s + p, p),
                          _chunks(0, step_rows[s], chunk)))
        if lanes:
            _a2a_wave(st, x_hbm, o_hbm, lanes)
    while st.copy_own_piece(x_hbm, o_hbm):
        pass                # what the waves had no chunk step left for
    st.finish()


# ---------------------------------------------------------------------------
# wrappers
# ---------------------------------------------------------------------------

def _a2a_call(blocks: jax.Array, axis_name: str, p: int, step_rows,
              chunk_bytes, depth, bidirectional, credits, interpret):
    """Launch the streaming kernel over (p, block_rows, 128) blocks."""
    interpret, credits = _resolve_flags(interpret, credits)
    rows = blocks.shape[1]
    chunk = min(_cfg_chunk_rows(blocks.dtype, chunk_bytes), rows)
    d = _cfg_depth(depth)
    ndir = _resolve_ndir(p, bidirectional)
    kernel = functools.partial(_hbm_alltoall_kernel, axis_name, p,
                               tuple(step_rows), chunk, d, ndir, credits)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(blocks.shape, blocks.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=_a2a_scratch_shapes(ndir, d, chunk, blocks.dtype),
        compiler_params=compiler_params(collective_id=_CID_ALLTOALL,
                                        has_side_effects=True),
        interpret=interpret,
        name=kernel_name(_hbm_alltoall_kernel),
    )(blocks)


def hbm_alltoall(x: jax.Array, axis_name: str, num_devices: int, *,
                 chunk_bytes: Optional[int] = None,
                 depth: Optional[int] = None,
                 bidirectional: Optional[bool] = None,
                 credits: Optional[bool] = None,
                 interpret=None) -> jax.Array:
    """Uniform alltoall along ``axis_name`` via the chunked streaming
    engine. ``x``: this shard's flat send buffer [p*c] (block j is the
    payload for shard j); returns [p*c] with block j received from
    shard j."""
    p = num_devices
    if p == 1 or x.size == 0:
        return x
    if x.size % p:
        raise ValueError(f"alltoall shard size {x.size} not divisible "
                         f"by {p}")
    c = x.size // p
    rows = _tile_rows(c, x.dtype)
    out = _a2a_call(_as_blocks(x.reshape(-1), p, rows), axis_name, p,
                    (rows,) * p, chunk_bytes, depth, bidirectional,
                    credits, interpret)
    return _from_blocks(out, c)


def wire_bytes(step_rows: Sequence[int], dtype) -> int:
    """Bytes one shard sends over ICI in one run of the kernel with
    this step schedule: every permutation step's rows as whole 128-lane
    tiles, pad included; step 0, the local block, is an HBM-to-HBM DMA
    and never reaches the wire. As many bytes arrive."""
    return sum(step_rows[1:]) * _LANES * np.dtype(dtype).itemsize


def alltoall_wire_bytes(nelems: int, dtype, num_devices: int) -> int:
    """``wire_bytes`` of the schedule ``hbm_alltoall`` runs on a flat
    ``[nelems]`` send buffer: ``p - 1`` blocks of ``nelems / p``
    elements, each rounded up to whole tiles."""
    p = num_devices
    return wire_bytes((_tile_rows(nelems // p, dtype),) * p, dtype)


def packed_displs(counts: Sequence[Sequence[int]]
                  ) -> Tuple[tuple, tuple, int, int]:
    """Canonical packed layout for a count matrix: row-major send
    displacements, column-major receive displacements, and the padded
    per-shard buffer lengths (every shard's buffers are sized to the
    mesh-wide maximum so the shard_map shapes stay uniform)."""
    p = len(counts)
    sd, rd = [], []
    in_len = out_len = 1
    for r in range(p):
        row, col = [], []
        so = ro = 0
        for j in range(p):
            row.append(so)
            col.append(ro)
            so += counts[r][j]
            ro += counts[j][r]
        sd.append(tuple(row))
        rd.append(tuple(col))
        in_len = max(in_len, so)
        out_len = max(out_len, ro)
    return tuple(sd), tuple(rd), in_len, out_len


def hbm_alltoallv(x: jax.Array, axis_name: str, num_devices: int,
                  counts: Sequence[Sequence[int]], *,
                  sdispls=None, rdispls=None, out_len=None,
                  chunk_bytes: Optional[int] = None,
                  depth: Optional[int] = None,
                  bidirectional: Optional[bool] = None,
                  credits: Optional[bool] = None,
                  interpret=None) -> jax.Array:
    """Variable-count alltoall. ``counts`` is the full static p x p
    matrix (counts[r][j] = elements shard r sends shard j — the mesh
    channel assembles it from every rank's scounts); displacements
    default to the canonical packed layout of ``packed_displs``.
    ``x``: flat [in_len] per shard; returns flat [out_len] per shard
    with shard j's payload at rdispls[my][j]."""
    p = num_devices
    csd, crd, in_len, c_out = packed_displs(counts)
    if sdispls is None:
        sdispls = csd
    if rdispls is None:
        rdispls = crd
    if out_len is None:
        out_len = c_out
    if p == 1:
        return x[:out_len]
    total = sum(sum(row) for row in counts)
    if total == 0:
        return _xla_alltoallv(x, axis_name, p, counts, sdispls, rdispls,
                              out_len)
    my = lax.axis_index(axis_name)
    rows = _tile_rows(max(max(row) for row in counts), x.dtype)
    step_rows = [_tile_rows(max(counts[r][(r + s) % p]
                                for r in range(p)), x.dtype)
                 if any(counts[r][(r + s) % p] for r in range(p)) else 0
                 for s in range(p)]
    recv = _a2a_call(
        _pack_blocks(x, my, sdispls, rows * _LANES).reshape(
            p, rows, _LANES),
        axis_name, p, step_rows, chunk_bytes, depth, bidirectional,
        credits, interpret)
    return _unpack_blocks(recv.reshape(p, rows * _LANES), my, counts,
                          rdispls, out_len)


def _pack_blocks(x, my, sdispls, width: int):
    """[in_len] -> [p, width]: row j = the ``width`` elements at this
    rank's send displacement for peer j (the valid prefix is
    counts[my][j]; the tail is whatever follows — dropped again by
    ``_unpack_blocks``)."""
    sd = jnp.asarray(np.asarray(sdispls, dtype=np.int32))
    xp = jnp.pad(x, (0, width))          # slack: a slice never clamps
    return jnp.stack([lax.dynamic_slice(xp, (sd[my, j],), (width,))
                      for j in range(len(sdispls))])


def _unpack_blocks(recv, my, counts, rdispls, out_len: int):
    """[p, width] received blocks -> [out_len]: scatter block j's valid
    prefix (counts[j][my]) to this rank's receive displacement for peer
    j; out-of-range lanes drop."""
    p, width = recv.shape
    c_arr = jnp.asarray(np.asarray(counts, dtype=np.int32))
    rd = jnp.asarray(np.asarray(rdispls, dtype=np.int32))
    lanes = jnp.arange(width, dtype=jnp.int32)
    out = jnp.zeros((out_len,), recv.dtype)
    for j in range(p):
        idx = jnp.where(lanes < c_arr[j, my], rd[my, j] + lanes, out_len)
        out = out.at[idx].set(recv[j], mode="drop")
    return out


def _xla_alltoallv(x, axis_name, p, counts, sdispls, rdispls, out_len):
    """Bit-exact XLA emulation of the v-variant: pad every pair to the
    matrix maximum, run the uniform lax.all_to_all, then scatter each
    received block's valid prefix to its displacement. The padded wire
    is O(p * cmax) — the streaming kernel exists precisely to beat
    this."""
    my = lax.axis_index(axis_name)
    cmax = max(1, max(max(row) for row in counts))
    sent = _pack_blocks(x, my, sdispls, cmax)            # [p, cmax]
    recv = lax.all_to_all(sent, axis_name, split_axis=0, concat_axis=0)
    return _unpack_blocks(recv.reshape(p, cmax), my, counts, rdispls,
                          out_len)


# ---------------------------------------------------------------------------
# tier dispatch
# ---------------------------------------------------------------------------

def planned_a2a_tier(shard_nbytes: int, dtype, interpret=None
                     ) -> Tuple[str, Optional[str]]:
    """(tier, fallback_reason) for one device alltoall(v) call — the
    generic device-tier answer collapsed onto the single streaming
    engine (no VMEM flat ring or quantized wire for alltoall yet):
    'hbm' or 'xla'."""
    tier, reason = planned_tier("alltoall", shard_nbytes, dtype, None,
                                interpret)
    if tier in ("vmem", "quant"):
        tier = "hbm"
    return tier, reason


def ici_all_to_all(x: jax.Array, axis_name: str, num_devices: int,
                   interpret=None) -> jax.Array:
    """Tier-dispatched uniform device alltoall: the chunked streaming
    kernel when the kernels can run, the XLA lowering past the measured
    crossover or off-platform. ``x``: flat [p*c] send buffer."""
    p = num_devices
    if p == 1:
        return x
    nbytes = x.size * x.dtype.itemsize
    tier, reason = planned_a2a_tier(nbytes, x.dtype, interpret)
    _trace_entry("alltoall", tier, nbytes)
    if tier == "hbm":
        return hbm_alltoall(x, axis_name, p, interpret=interpret)
    note_fallback("alltoall", reason or "size", nbytes, x.dtype)
    from .collectives import all_to_all
    c = x.size // p
    return all_to_all(x.reshape(p, c), axis_name, split_axis=0,
                      concat_axis=0).reshape(-1)


def ici_all_to_allv(x: jax.Array, axis_name: str, num_devices: int,
                    counts: Sequence[Sequence[int]], *,
                    out_len: Optional[int] = None,
                    interpret=None) -> jax.Array:
    """Tier-dispatched variable-count device alltoall. Tier selection
    keys on the heaviest shard's send bytes (the wire the busiest
    expert must move)."""
    p = num_devices
    if p == 1:
        _, _, _, c_out = packed_displs(counts)
        return x[:out_len if out_len is not None else c_out]
    itemsize = np.dtype(x.dtype).itemsize
    nbytes = max(sum(row) for row in counts) * itemsize
    tier, reason = planned_a2a_tier(max(1, nbytes), x.dtype, interpret)
    _trace_entry("alltoallv", tier, nbytes)
    if tier == "hbm":
        return hbm_alltoallv(x, axis_name, p, counts, out_len=out_len,
                             interpret=interpret)
    note_fallback("alltoall", reason or "size", nbytes, x.dtype)
    sd, rd, _in, c_out = packed_displs(counts)
    return _xla_alltoallv(x, axis_name, p, counts, sd, rd,
                          out_len if out_len is not None else c_out)
