"""Device one-sided RMA engine — Put/Get/Accumulate over HBM remote DMA.

The kernel half of the KV-cache-shard lane (rma/device.py owns the
window/epoch surface). The reference serves one-sided traffic by
posting verbs work requests straight to the HCA (gen2/rdma_iba_1sc.c);
here a window is a mesh-sharded HBM buffer and the three MPI one-sided
ops become three chunked remote-DMA kernels:

* **Put** — ``remote_sendrecv`` (ops/pallas_ici.py) generalized to an
  arbitrary target offset: each chunk of the origin's source buffer is
  one ``make_async_remote_copy`` into a VMEM landing slot on its
  partner, committed to a landing *segment*; the wrapper writes the
  target's landing segment into its window shard at ``disp`` (the vbuf
  staging model — a direct copy into the window cannot work because
  every device must run the same remote DMA and the non-target
  self-copies would clobber their windows).
* **Get** — the reversed copy: every device stages its OWN window
  segment, the symmetric permutation swaps origin<->target, and the
  wrapper keeps the origin's landing.
* **Accumulate** — streams chunks through the PR 8 slot/credit
  schedule (``_RmaStreamer`` below, the partner-pair form of
  ``_RingStreamer``) with a VPU fold at the target: non-origin devices
  stage the op identity (zeros for sum), so the fold is uniform across
  the mesh — every device folds what lands, and only the target's fold
  changes its window. The optional quantized wire reuses the
  ``pallas_quant`` block codec (encode fused before the remote DMA,
  decode fused into the fold) under the same ``declared_bound`` error
  contract.

Flow control is the chunk-credit handshake of pallas_ici.py with the
ring neighbors replaced by the put partner: each device grants its
partner ``depth`` slot credits up front and re-grants as it consumes a
landing slot, so an origin runs at most ``depth`` chunks ahead of the
target's folds. Passive-target sync in rma/device.py (lock/unlock,
flush, flush_local) rides exactly these DMA semaphores — a flush is
complete when every pending handle in the streamer has been waited and
the credit balance is back to ``depth``. The TPU interpreter executes
remote signals, so CPU tests run the same handshake.

Layout: the kernels never see the element displacement. The wrappers
cut the ``n``-element window segment at ``disp`` out on the XLA side
(and write it back), padded to whole ``(rows, 128)`` tiles, so every
DMA slice inside a kernel is a static, tile-aligned row range with the
slot index on a leading, untiled dimension — what Mosaic's tiling
demands, as in ops/pallas_ici.py. The quantized accumulate keeps the
flat 1-D form its codec is written for (refused by the chip's compiler
with pallas_quant, ROADMAP A3).

Tier selection lives in ``planned_rma_tier``: contiguous ops at or
above the ``dev_rma_rdma_min`` edge run these kernels ('rdma', or
'quant' for an eligible Accumulate above ``dev_rma_quant_min``);
everything else keeps the ppermute epoch compiler ('epoch') with the
fallback reason named for the dev_rma_fallback_* pvar family.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils.mlog import get_logger
from ._compat import compiler_params, kernel_name

log = get_logger("pallas_rma")

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# cvar RMA_CHUNK_BYTES and the dev_rma_* pvar family are predeclared in
# mpit.py (the MPI_T surface enumerates them before this module is
# imported), same early-declaration contract as the ICI_* knobs.
from .. import mpit  # noqa: F401,E402  — cvar/pvar declarations
from .pallas_ici import (_LANES, _as_blocks, _chunks,  # noqa: E402
                         _entry_barrier, _from_blocks, _resolve_flags,
                         _sublanes, _tile_rows)

# Mosaic collective ids — the barrier semaphore of each kernel's entry
# barrier (pallas_ring owns 0/1, pallas_ici 2-5, pallas_quant 6)
_CID_PUT = 7
_CID_GET = 8
_CID_ACC = 9
_CID_ACC_QUANT = 10


def _cfg_chunk_elems(dtype, chunk_bytes: Optional[int]) -> int:
    """RMA chunk size: MV2T_RMA_CHUNK_BYTES, inheriting the ICI chunk
    edge (profile-overridable) when unset (0)."""
    if chunk_bytes is None:
        from ..utils.config import get_config
        chunk_bytes = int(get_config()["RMA_CHUNK_BYTES"])
        if chunk_bytes <= 0:
            from ..coll.tuning import kernel_param_cv
            chunk_bytes = kernel_param_cv("ici_chunk_bytes",
                                          "ICI_CHUNK_BYTES")
    return max(1, int(chunk_bytes) // np.dtype(dtype).itemsize)


def _cfg_depth(depth: Optional[int]) -> int:
    if depth is None:
        from ..utils.config import get_config
        depth = int(get_config()["ICI_PIPELINE_DEPTH"])
    return max(2, int(depth))


# ---------------------------------------------------------------------------
# the streaming state (partner-pair form of pallas_ici._RingStreamer)
# ---------------------------------------------------------------------------

class _RmaStreamer:
    """Per-kernel-instance one-sided streaming state: scratch refs, DMA
    handles, and the global chunk counter whose mod-depth sequence makes
    landing-slot reuse collision-free. The ring neighbors of
    ``_RingStreamer`` collapse to the single put partner — the device
    the symmetric origin<->target permutation pairs us with — and the
    per-direction credit semaphore to one."""

    def __init__(self, partner, depth, credits, stage_buf, landing_buf,
                 fold_buf, in_sem, fold_sem, st_sem, send_sem, recv_sem,
                 cap_sem):
        self.partner, self.depth, self.credits = partner, depth, credits
        self.stage_buf, self.landing_buf, self.fold_buf = \
            stage_buf, landing_buf, fold_buf
        self.in_sem, self.fold_sem, self.st_sem = in_sem, fold_sem, st_sem
        self.send_sem, self.recv_sem, self.cap_sem = \
            send_sem, recv_sem, cap_sem
        self.gc = 0                            # global chunk counter
        self.pending_send: Dict = {}           # slot -> remote handle
        self.pending_fold: Dict = {}           # slot -> window-chunk load
        self.pending_store: Dict = {}          # slot -> commit store

    def enter(self):
        """Kernel entry: barrier with the partner (no signal or DMA may
        reach a device that has not entered the kernel), then the
        initial credits."""
        _entry_barrier([self.partner])
        self.grant_initial_credits()

    def grant_initial_credits(self):          # device: hw-only
        """Grant the partner (the device whose remote DMAs land in our
        slots) one credit per landing slot."""
        if not self.credits:
            return
        pltpu.semaphore_signal(
            self.cap_sem, inc=self.depth, device_id=self.partner,
            device_id_type=pltpu.DeviceIdType.LOGICAL)

    def _take_credit(self):                   # device: hw-only
        """Consume one landing-slot credit before the remote DMA — the
        sender half of the chunk-credit handshake."""
        if not self.credits:
            return
        pltpu.semaphore_wait(self.cap_sem, 1)

    def _grant(self):                         # device: hw-only
        """Landing slot consumed: re-grant the credit to the partner."""
        if not self.credits:
            return
        pltpu.semaphore_signal(
            self.cap_sem, inc=1, device_id=self.partner,
            device_id_type=pltpu.DeviceIdType.LOGICAL)

    def issue(self, stage_fill, fold_load):
        """Front half of the chunk pipeline: fill the stage slot
        (``stage_fill(slot)`` — source chunk, window chunk, or encoded
        wire words), optionally prefetch the target-side fold operand
        (``fold_load(slot)`` starts the window-chunk load and parks the
        handle in ``pending_fold``; None for put/get), then launch the
        remote DMA — it flies while the previous chunk drains."""
        slot = self.gc % self.depth
        prev = self.pending_send.pop(slot, None)
        if prev is not None:
            prev.wait_send()           # stage slot free for refill
        prev_st = self.pending_store.pop(slot, None)
        if prev_st is not None:
            prev_st.wait()             # fold slot's last commit landed
        stage_fill(slot)
        if fold_load is not None:
            fold_load(slot)
        self._take_credit()
        rdma = pltpu.make_async_remote_copy(
            src_ref=self.stage_buf.at[slot],
            dst_ref=self.landing_buf.at[slot],
            send_sem=self.send_sem.at[slot],
            recv_sem=self.recv_sem.at[slot],
            device_id=self.partner,
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        rdma.start()
        self.pending_send[slot] = rdma
        self.gc += 1
        return slot

    def drain(self, slot, consume, commit):
        """Back half: the partner's chunk has landed — ``consume(slot)``
        performs every read of the landing slot (the VPU fold, or the
        direct window commit), then the credit is re-granted (the slot
        is free for the partner's next write) and ``commit(slot)``
        starts any post-slot store (fold slot -> window HBM, parked in
        ``pending_store``; None for put/get)."""
        self.pending_send[slot].wait_recv()
        pf = self.pending_fold.pop(slot, None)
        if pf is not None:
            pf.wait()
        consume(slot)
        # every landing-slot read above is synchronous: slot is free
        self._grant()
        if commit is not None:
            commit(slot)

    def finish(self):
        """Completion wave (= flush): outbound DMAs off the stage
        slots, commit stores landed, and — with credits — the partner
        has consumed everything we wrote (the balance is back to
        ``depth``), so no in-flight write can land after kernel exit.
        Passive-target flush/unlock and active-target fence both close
        on exactly this wave."""
        for key, h in list(self.pending_send.items()):
            h.wait_send()
            del self.pending_send[key]
        for skey, sh in list(self.pending_store.items()):
            sh.wait()
            del self.pending_store[skey]
        if self.credits:                      # device: hw-only
            pltpu.semaphore_wait(self.cap_sem, self.depth)


def _rma_scratch_shapes(depth: int, chunk: int, dtype, wire_chunk=None,
                        lanes=()):
    """Stage/landing/fold VMEM slots + the semaphore set. ``chunk`` is
    in rows of ``lanes=(128,)`` for the tiled (exact) kernels — the slot
    index leads, the tiled (chunk, 128) pair is only sliced on whole
    tiles — and in elements with ``lanes=()`` for the quantized wire,
    whose stage/landing slots carry int32 wire words (``wire_chunk`` per
    slot) while the fold slot stays the window dtype."""
    wdt = jnp.int32 if wire_chunk is not None else dtype
    wck = wire_chunk if wire_chunk is not None else chunk
    return [
        pltpu.VMEM((depth, wck, *lanes), wdt),        # stage slots
        pltpu.VMEM((depth, wck, *lanes), wdt),        # landing slots
        pltpu.VMEM((depth, chunk, *lanes), dtype),    # fold slots
        pltpu.SemaphoreType.DMA((depth,)),    # stage loads
        pltpu.SemaphoreType.DMA((depth,)),    # fold-operand loads
        pltpu.SemaphoreType.DMA((depth,)),    # commit stores
        pltpu.SemaphoreType.DMA((depth,)),    # remote send
        pltpu.SemaphoreType.DMA((depth,)),    # remote recv
        pltpu.SemaphoreType.REGULAR(()),      # landing-slot credits
    ]


def _mk_streamer(partner, depth, credits, scratch):
    (stage_buf, landing_buf, fold_buf, in_sem, fold_sem, st_sem,
     send_sem, recv_sem, cap_sem) = scratch
    return _RmaStreamer(partner, depth, credits, stage_buf, landing_buf,
                        fold_buf, in_sem, fold_sem, st_sem, send_sem,
                        recv_sem, cap_sem)


def _partner(me, origin, target):
    """The symmetric routing permutation: identity except
    origin<->target — every device runs the same (collective) remote
    DMA, only the pair actually exchanges foreign data."""
    return jnp.where(me == origin, target,
                     jnp.where(me == target, origin, me))


# ---------------------------------------------------------------------------
# kernels
#
# Every kernel works on a SEGMENT: the n elements of the window at the
# op's displacement, cut out (and put back) on the XLA side by the
# wrappers, so the kernel never sees an unaligned element offset. Exact
# ops get the segment as whole (rows, 128) tiles; the quantized
# accumulate keeps the flat 1-D form its codec is written for. The
# bodies are layout-agnostic: ``chunks`` are (offset, size) along the
# leading dim in either form.
# ---------------------------------------------------------------------------

def _stream(st, chunks, fill, consume, fload=None, commit=None):
    """The chunk pipeline every one-sided op shares: issue chunk c,
    then drain chunk c-1 while c is on the wire."""
    live: List[Optional[int]] = [None] * len(chunks)
    for c in range(len(chunks) + 1):
        if c < len(chunks):
            off, sz = chunks[c]
            live[c] = st.issue(
                functools.partial(fill, off=off, sz=sz),
                functools.partial(fload, off=off, sz=sz)
                if fload is not None else None)
        if c >= 1:
            off, sz = chunks[c - 1]
            st.drain(live[c - 1],
                     functools.partial(consume, off=off, sz=sz),
                     functools.partial(commit, off=off, sz=sz)
                     if commit is not None else None)
    st.finish()


def _put_kernel(axis, origin, target, chunks, depth, credits,
                src_hbm, out_hbm, *scratch):
    """Chunked one-sided put: per chunk one remote DMA of the origin's
    stage slot into the partner's landing slot, committed to the
    landing segment ``out_hbm`` (the target's copy of it is what the
    wrapper writes into the window; everyone else's is their own
    zeros)."""
    me = lax.axis_index(axis)
    st = _mk_streamer(_partner(me, origin, target), depth, credits,
                      scratch)
    st.enter()

    def fill(slot, off, sz):
        @pl.when(me == origin)
        def _():
            pltpu.sync_copy(src_hbm.at[pl.ds(off, sz)],
                            st.stage_buf.at[slot, pl.ds(0, sz)])

        @pl.when(me != origin)
        def _():
            st.stage_buf[slot, :sz] = jnp.zeros_like(
                st.stage_buf[slot, :sz])

    def consume(slot, off, sz):
        # landing -> segment commit: one local DMA, waited before the
        # slot's credit goes back
        pltpu.sync_copy(st.landing_buf.at[slot, pl.ds(0, sz)],
                        out_hbm.at[pl.ds(off, sz)])

    _stream(st, chunks, fill, consume)


def _get_kernel(axis, origin, target, chunks, depth, credits,
                seg_hbm, out_hbm, *scratch):
    """Chunked one-sided get — the reversed put: every device stages
    its OWN window segment (so the non-pair self-copies and the
    origin->target lane carry harmless data) and commits what lands;
    the origin's landing is the target's segment (the wrapper keeps
    only that one)."""
    me = lax.axis_index(axis)
    st = _mk_streamer(_partner(me, origin, target), depth, credits,
                      scratch)
    st.enter()

    def fill(slot, off, sz):
        pltpu.sync_copy(seg_hbm.at[pl.ds(off, sz)],
                        st.stage_buf.at[slot, pl.ds(0, sz)])

    def consume(slot, off, sz):
        pltpu.sync_copy(st.landing_buf.at[slot, pl.ds(0, sz)],
                        out_hbm.at[pl.ds(off, sz)])

    _stream(st, chunks, fill, consume)


def _acc_kernel(axis, origin, target, chunks, depth, credits,
                quant_block, wire, src_hbm, seg_hbm, out_hbm, *scratch):
    """Chunked one-sided accumulate (MPI_SUM): the origin streams
    source chunks through the slot/credit schedule; every device folds
    what lands into its own window segment (the fold is uniform — only
    the target receives nonzero data, everyone else folds the identity
    it was sent), so no device diverges on the collective DMA sequence.
    With ``quant_block`` set the stage slot carries the pallas_quant
    block-scaled int32 wire (encode fused here, decode fused into the
    fold) under the same declared_bound contract."""
    me = lax.axis_index(axis)
    del seg_hbm     # aliased to out_hbm: the segment is folded in place
    st = _mk_streamer(_partner(me, origin, target), depth, credits,
                      scratch)
    st.enter()
    if quant_block is not None:
        from .pallas_quant import _decode_f32, _encode_f32

        def _ww(sz):
            # int32 wire words for a block-multiple chunk of sz elems
            return (sz // quant_block) * (1 + quant_block // 4)

    def fill(slot, off, sz):
        # the fold slot is free here (its last commit was waited in
        # issue, its next segment prefetch starts after this): borrow
        # it to bring the source chunk into VMEM
        pltpu.sync_copy(src_hbm.at[pl.ds(off, sz)],
                        st.fold_buf.at[slot, pl.ds(0, sz)])
        val = st.fold_buf[slot, :sz]
        val = jnp.where(me == origin, val, jnp.zeros_like(val))
        if quant_block is not None:
            st.stage_buf[slot, :_ww(sz)] = _encode_f32(
                val, quant_block, wire)
        else:
            st.stage_buf[slot, :sz] = val

    def fload(slot, off, sz):
        ld = pltpu.make_async_copy(
            out_hbm.at[pl.ds(off, sz)],
            st.fold_buf.at[slot, pl.ds(0, sz)],
            st.fold_sem.at[slot])
        ld.start()
        st.pending_fold[slot] = ld

    def consume(slot, off, sz):
        if quant_block is not None:
            add = _decode_f32(st.landing_buf[slot, :_ww(sz)],
                              quant_block, wire)
        else:
            add = st.landing_buf[slot, :sz]
        st.fold_buf[slot, :sz] = st.fold_buf[slot, :sz] + add

    def commit(slot, off, sz):
        w = pltpu.make_async_copy(
            st.fold_buf.at[slot, pl.ds(0, sz)],
            out_hbm.at[pl.ds(off, sz)],
            st.st_sem.at[slot])
        w.start()
        st.pending_store[slot] = w

    _stream(st, chunks, fill, consume, fload, commit)


# ---------------------------------------------------------------------------
# wrappers (call inside shard_map over the window's mesh axis)
# ---------------------------------------------------------------------------

def _tiles(flat, rows: int):
    """[n] -> (rows, 128), zero-padded."""
    return _as_blocks(flat, 1, rows)[0]


def _untile(tiles, n: int):
    return _from_blocks(tiles[None], n)


def _rma_call(kern_fn, static, cid: int, operands, out_like, aliases,
              rows: int, dtype, chunk_bytes, depth, credits, interpret,
              tail=()):
    """Launch one exact (tiled) one-sided kernel over (rows, 128)
    segments: chunk geometry in rows, all operands left in HBM."""
    interpret, credits = _resolve_flags(interpret, credits)
    t = _sublanes(dtype)
    chunk = min(max(t, _cfg_chunk_elems(dtype, chunk_bytes)
                    // _LANES // t * t), rows)
    d = _cfg_depth(depth)
    kern = functools.partial(kern_fn, *static, _chunks(0, rows, chunk), d,
                             credits, *tail)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kern,
        in_specs=[hbm] * len(operands),
        out_specs=hbm,
        out_shape=jax.ShapeDtypeStruct(out_like.shape, out_like.dtype),
        scratch_shapes=_rma_scratch_shapes(d, chunk, dtype,
                                           lanes=(_LANES,)),
        input_output_aliases=aliases,
        compiler_params=compiler_params(collective_id=cid,
                                        has_side_effects=True),
        interpret=interpret,
        name=kernel_name(kern_fn),
    )(*operands)


def rma_put(src, win_shard, axis: str, num_devices: int, origin: int,
            target: int, disp: int = 0, *,
            chunk_bytes: Optional[int] = None,
            depth: Optional[int] = None,
            credits: Optional[bool] = None, interpret=None):
    """One-sided contiguous put over remote DMA: origin pushes ``src``
    into the target's window shard at element offset ``disp``. Returns
    the updated shard (the target's segment replaced, every other
    shard as it was)."""
    n = src.shape[0]
    rows = _tile_rows(n, src.dtype)
    s_t = _tiles(src, rows)
    landed = _rma_call(_put_kernel, (axis, origin, target), _CID_PUT,
                       (s_t,), s_t, {}, rows, src.dtype, chunk_bytes,
                       depth, credits, interpret)
    me = lax.axis_index(axis)
    seg = jnp.where(me == target, _untile(landed, n),
                    win_shard[disp:disp + n])
    return win_shard.at[disp:disp + n].set(seg)


def rma_get(win_shard, n: int, axis: str, num_devices: int, origin: int,
            target: int, disp: int = 0, *,
            chunk_bytes: Optional[int] = None,
            depth: Optional[int] = None,
            credits: Optional[bool] = None, interpret=None):
    """One-sided contiguous get — the reversed remote copy: origin
    pulls ``n`` elements of the target's window shard at ``disp``.
    Returns the (n,) result — the data on the origin's shard, zeros
    elsewhere."""
    rows = _tile_rows(n, win_shard.dtype)
    seg_t = _tiles(win_shard[disp:disp + n], rows)
    got = _rma_call(_get_kernel, (axis, origin, target), _CID_GET,
                    (seg_t,), seg_t, {}, rows, win_shard.dtype,
                    chunk_bytes, depth, credits, interpret)
    me = lax.axis_index(axis)
    return jnp.where(me == origin, _untile(got, n),
                     jnp.zeros((n,), win_shard.dtype))


def rma_accumulate(src, win_shard, axis: str, num_devices: int,
                   origin: int, target: int, disp: int = 0, *,
                   quantized: bool = False,
                   chunk_bytes: Optional[int] = None,
                   depth: Optional[int] = None,
                   credits: Optional[bool] = None, interpret=None):
    """One-sided accumulate (MPI_SUM) streamed through the slot/credit
    schedule with the fold at the target. ``quantized=True`` carries
    each chunk as the pallas_quant block-scaled int32 wire (f32 only;
    the caller owns the declared_bound budget check — acc_quant_ok)."""
    n = src.shape[0]
    seg = win_shard[disp:disp + n]
    if not quantized:
        rows = _tile_rows(n, src.dtype)
        s_t, seg_t = _tiles(src, rows), _tiles(seg, rows)
        out = _rma_call(_acc_kernel, (axis, origin, target), _CID_ACC,
                        (s_t, seg_t), seg_t, {1: 0}, rows, src.dtype,
                        chunk_bytes, depth, credits, interpret,
                        tail=(None, None))     # exact: no quant codec
        return win_shard.at[disp:disp + n].set(_untile(out, n))
    # quantized wire: the flat 1-D form the block codec is written for
    interpret, credits = _resolve_flags(interpret, credits)
    from ..coll.tuning import quant_params
    from .pallas_quant import quant_block_elems, wire_words
    quant_block = min(quant_block_elems(src.dtype), n)
    wire, _budget = quant_params()
    if n % quant_block:
        raise ValueError("quantized accumulate needs a block-"
                         f"multiple count (n={n}, block="
                         f"{quant_block})")
    # wire slots carry whole blocks: chunk snaps to a block multiple
    chunk = min(_cfg_chunk_elems(src.dtype, chunk_bytes), n)
    chunk = max(quant_block, (chunk // quant_block) * quant_block)
    d = _cfg_depth(depth)
    kern = functools.partial(_acc_kernel, axis, origin, target,
                             _chunks(0, n, chunk), d, credits)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(kern, quant_block, wire),
        in_specs=[hbm, hbm],
        out_specs=hbm,
        out_shape=jax.ShapeDtypeStruct(seg.shape, seg.dtype),
        scratch_shapes=_rma_scratch_shapes(
            d, chunk, src.dtype, wire_words(chunk, quant_block)),
        input_output_aliases={1: 0},
        compiler_params=compiler_params(collective_id=_CID_ACC_QUANT,
                                        has_side_effects=True),
        interpret=interpret,
        name=kernel_name(_acc_kernel) + "_quant",
    )(src, seg)
    return win_shard.at[disp:disp + n].set(out)


# ---------------------------------------------------------------------------
# tier selection (the one-sided tuning-table moment)
# ---------------------------------------------------------------------------

def acc_quant_ok(dtype, count: int, num_devices: int) -> bool:
    """Whether an accumulate sized for the quant bin may actually run
    quantized: f32 sum into a block-multiple extent, with the user's
    MV2T_QUANT_COLL budget covering the one-quantization-per-hop bound
    (an RMA accumulate is a single hop: declared_bound(1, wire))."""
    dt = np.dtype(dtype)
    if dt.kind != "f" or dt.itemsize != 4:
        return False
    from ..coll.tuning import quant_params
    from .pallas_quant import declared_bound, quant_block_elems
    wire, budget = quant_params()
    if budget <= 0 or budget < declared_bound(1, wire):
        return False
    return count % quant_block_elems(dtype) == 0


def planned_rma_tier(kind: str, nbytes: int, dtype, contiguous: bool,
                     interpret=None, num_devices: Optional[int] = None,
                     count: int = 0) -> Tuple[str, Optional[str]]:
    """(tier, fallback_reason) for one one-sided op. tier is 'rdma' |
    'quant' | 'epoch'; reason is None unless the ppermute epoch
    compiler was taken, in which case it names the dev_rma_fallback_*
    pvar bucket: noncontig (strided/derived datatype — the epoch
    compiler's home turf), platform (no pallas / not a TPU and not
    interpreting), size (below the dev_rma_rdma_min edge), dtype (a
    kind the kernels cannot carry). A 'quant' bin the accumulate
    cannot actually quantize degrades to the exact 'rdma' tier."""
    from .pallas_ici import _kernels_runnable
    if not _kernels_runnable(interpret):
        return "epoch", "platform"
    if not contiguous:
        return "epoch", "noncontig"
    if np.dtype(dtype).kind not in "fiu":
        return "epoch", "dtype"
    if nbytes <= 0:
        return "epoch", "size"
    from ..coll.tuning import _dev_tier_edge
    rmin = _dev_tier_edge("DEV_RMA_RDMA_MIN", "dev_rma_rdma_min")
    if rmin < 0 or nbytes < rmin:
        return "epoch", "size"
    if kind == "acc":
        qmin = _dev_tier_edge("DEV_RMA_QUANT_MIN", "dev_rma_quant_min")
        if qmin >= 0 and nbytes >= qmin and \
                acc_quant_ok(dtype, count, num_devices):
            return "quant", None
    return "rdma", None


def note_rma_fallback(kind: str, reason: str, nbytes: int) -> None:
    """Count one one-sided fallback to the epoch compiler (pvar family
    dev_rma_fallback_*, predeclared in mpit.py)."""
    mpit.pvar(f"dev_rma_fallback_{reason}").inc()
    log.dbg(1, "device RMA %s fell back to the epoch compiler "
            "(%s, %d bytes)", kind, reason, nbytes)
