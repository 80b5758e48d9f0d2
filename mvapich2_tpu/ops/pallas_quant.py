"""Block-scaled quantized device allreduce — the ``quant`` tier.

EQuARX's lesson applied to the PR 8 substrate: for large device
messages, ML-serving allreduce traffic (gradients, activations)
tolerates bounded error, so shrink the bytes BEFORE they touch ICI —
the "Multiple Processes per GPU" fold-before-the-slow-fabric rule, one
fabric down. The chunked HBM-streaming engine of ops/pallas_ici.py is
reused wholesale; what changes is the wire format of each VMEM-staged
chunk:

    HBM f32 chunk ──local DMA──> stage slot
    stage slot ──VPU block-scaled encode──> int32 wire slot
    wire slot ──remote DMA (ICI)──> peer wire slot        (~3.9x smaller)
    peer wire slot ──VPU decode + accumulate──> acc slot ──DMA──> HBM

Wire format: the shard is cut into fixed blocks of ``MV2T_QUANT_BLOCK``
bytes (profile key ``quant_block_bytes``); each block travels as ONE
packed run of int32 words — word 0 is the block's f32 absmax scale
(bitcast), the rest carry 4 codes per word. Two code flavors:

  * ``q8``  — absmax int8: code = round(x * 127 / absmax), error per
    quantization <= absmax/254 per element;
  * ``fp8`` — e4m3 with per-block scale: code = fp8(x * 448 / absmax),
    3-bit mantissa, error per quantization <= absmax/28 worst-case but
    relative precision held across the block's dynamic range.

For f32 at the default 512-byte block the wire run is 132 bytes per
512-byte block — the same chunk credits carry ~3.9x more payload.

Schedule: pipelined reduce-scatter with per-chunk encode/decode fused
into the ``_RingStreamer`` issue/drain halves (``_QuantStreamer``
below; slot sequence, credit handshake and DMA overlap identical to
the exact kernel), then the rank's fully-reduced block is encoded ONCE
and the final all-gather pass carries the quantized partials over the
UNCHANGED ``hbm_ring_all_gather`` engine — int32 wire blocks are just
bytes to it. Because every rank decodes the same code words, all ranks
produce bit-identical results, and each element suffers at most p
quantizations (p-1 reduce-scatter hops + 1 gather encode):
``declared_bound(p, wire)`` is that contract, checked against the
user's ``MV2T_QUANT_COLL`` budget at tier selection.

Exact-mode fallbacks (never an error): integer dtypes, non-sum ops,
budget 0/unset, and budgets below the declared bound all keep the
exact hbm tier. Interpreter-proven correctness (like PR 8); the
effective-bandwidth half of the EQuARX ~2x claim waits for the ROADMAP
item 1 TPU host run — the wire-byte accounting (``wire_stats``) is the
hardware-independent half and is pinned by
tests/test_pallas_quant.py::test_wire_stats_ratio_under_bound.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils.mlog import get_logger
from ._compat import compiler_params, kernel_name

log = get_logger("pallas_quant")

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# cvars QUANT_COLL / QUANT_BLOCK are predeclared in mpit.py (the MPI_T
# surface enumerates them before this module is imported), same
# early-declaration contract as the ICI_* knobs.
from .. import mpit  # noqa: F401,E402  — cvar/pvar declarations
from .pallas_ici import (_cfg_chunk_elems, _cfg_depth, _chunks,  # noqa: E402
                         _resolve_flags, _resolve_ndir, _RingStreamer,
                         hbm_ring_all_gather)

WIRE_FORMATS = ("q8", "fp8")
_Q8_MAX = 127.0
_FP8_MAX = 448.0          # float8_e4m3fn finite max

# Mosaic collective id (pallas_ring owns 0/1, pallas_ici 2-5)
_CID_QUANT_RS = 6


# ---------------------------------------------------------------------------
# wire-format geometry + the error-bound contract
# ---------------------------------------------------------------------------

def quant_block_elems(dtype=jnp.float32) -> int:
    """Elements per quantization block: MV2T_QUANT_BLOCK bytes of the
    unquantized dtype (profile key ``quant_block_bytes`` overrides),
    floored to the 4-code packing granularity."""
    from ..coll.tuning import kernel_param_cv
    bb = kernel_param_cv("quant_block_bytes", "QUANT_BLOCK")
    b = max(8, int(bb) // np.dtype(dtype).itemsize)
    return (b // 4) * 4


def wire_words(nelems: int, block: int) -> int:
    """int32 wire words for ``nelems`` (a block multiple): one scale
    word plus 4 packed codes per word, per block."""
    assert nelems % block == 0
    return (nelems // block) * (1 + block // 4)


def declared_bound(num_devices: int, wire: str = "q8") -> float:
    """The error-bound contract: max relative error of the quantized
    allreduce vs the exact fold, counted against the largest partial's
    block absmax. Each element suffers at most ``p`` quantizations
    (p-1 reduce-scatter folds + the final gather encode), each within
    half a code step of its block scale."""
    per = 1.0 / 254.0 if wire == "q8" else 1.0 / 28.0
    return num_devices * per


def wire_stats(count: int, dtype, num_devices: int,
               block_bytes: Optional[int] = None) -> Tuple[int, int]:
    """(exact_wire_bytes, quant_wire_bytes) one rank puts on ICI for a
    ring allreduce of ``count`` elements — the hardware-independent
    half of the quant-tier claim, and the dev_coll_quant_bytes_saved
    pvar's accounting. Both counts cover the full reduce-scatter +
    all-gather round trip: 2*(p-1) blocks per rank."""
    p = num_devices
    dt = np.dtype(dtype)
    if block_bytes is None:
        blk = quant_block_elems(dtype)
    else:
        blk = max(8, (int(block_bytes) // dt.itemsize) // 4 * 4)
    nblk = -(-(-(-count // p)) // blk) * blk     # per-block-padded
    exact = 2 * (p - 1) * nblk * dt.itemsize
    quant = 2 * (p - 1) * wire_words(nblk, blk) * 4
    return exact, quant


def quant_eligible(name: str, dtype, op: Optional[str],
                   num_devices: Optional[int] = None) -> bool:
    """Whether a call the tuning table binned ``quant`` may actually
    run quantized: sum-shaped reduce on a float dtype, with the user's
    budget covering the declared bound for this ring width. Everything
    else keeps the exact hbm tier (bit-exact fallback, not an error)."""
    if name not in ("allreduce", "reduce") or op != "sum":
        return False
    dt = np.dtype(dtype)
    if dt.kind != "f" or dt.itemsize > 4:
        return False
    from ..coll.tuning import quant_params
    wire, budget = quant_params()
    if budget <= 0:
        return False
    if num_devices is not None and budget < declared_bound(num_devices,
                                                           wire):
        return False
    return True


# ---------------------------------------------------------------------------
# the block codec (plain jnp — runs on the VPU inside the kernel and at
# the jax level for the final decode)
# ---------------------------------------------------------------------------

def _encode_f32(v: jax.Array, block: int, wire: str) -> jax.Array:
    """[m] f32 (m a block multiple) -> [wire_words(m)] int32: per block
    one bitcast f32 absmax scale word, then 4 packed codes per word."""
    x = v.astype(jnp.float32).reshape(-1, block)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    if wire == "q8":
        scale = amax / _Q8_MAX
        safe = jnp.where(scale > 0, scale, 1.0)
        q = jnp.clip(jnp.round(x / safe), -_Q8_MAX, _Q8_MAX)
        u = (q.astype(jnp.int32) + 128).reshape(x.shape[0], -1, 4)
    else:
        scale = amax / _FP8_MAX
        safe = jnp.where(scale > 0, scale, 1.0)
        y = jnp.clip(x / safe, -_FP8_MAX, _FP8_MAX) \
            .astype(jnp.float8_e4m3fn)
        u = lax.bitcast_convert_type(y, jnp.uint8).astype(jnp.int32) \
            .reshape(x.shape[0], -1, 4)
    words = (u[..., 0] | (u[..., 1] << 8) | (u[..., 2] << 16)
             | (u[..., 3] << 24))
    sw = lax.bitcast_convert_type(scale, jnp.int32)
    return jnp.concatenate([sw, words], axis=1).reshape(-1)


def _decode_f32(w: jax.Array, block: int, wire: str) -> jax.Array:
    """Inverse of _encode_f32: [wire_words(m)] int32 -> [m] f32."""
    ww = w.reshape(-1, 1 + block // 4)
    scale = lax.bitcast_convert_type(ww[:, :1], jnp.float32)
    words = ww[:, 1:]
    b = jnp.stack([(words >> (8 * k)) & 0xFF for k in range(4)],
                  axis=-1)
    if wire == "q8":
        q = b.reshape(b.shape[0], -1).astype(jnp.float32) - 128.0
    else:
        u8 = b.reshape(b.shape[0], -1).astype(jnp.uint8)
        q = lax.bitcast_convert_type(u8, jnp.float8_e4m3fn) \
            .astype(jnp.float32)
    return (q * scale).reshape(-1)


# ---------------------------------------------------------------------------
# the quantized streamer: encode fused before the remote DMA, decode
# fused into the accumulate — slot/credit schedule inherited unchanged
# ---------------------------------------------------------------------------

class _QuantStreamer(_RingStreamer):
    """_RingStreamer with a block-scaled codec fused into the chunk
    pipeline: ``issue`` stages the exact f32 chunk, encodes it on the
    VPU into the int32 wire slot and remote-DMAs the SHRUNKEN run;
    ``drain`` decodes the arrived wire run and folds it into the f32
    accumulator chunk. The global-chunk-counter slot sequence and the
    credit handshake are the parent's, untouched — the wire chunks are
    just smaller."""

    def __init__(self, p, ndir, depth, credits, left, right, o_hbm,
                 scratch, block: int, wire: str):
        (stage_buf, send_buf, recv_buf, acc_buf, in_sem, acc_sem,
         st_sem, send_sem, recv_sem, cap_sem) = scratch
        super().__init__(p, ndir, depth, credits, left, right,
                         send_buf, recv_buf, acc_buf, in_sem, acc_sem,
                         st_sem, send_sem, recv_sem, cap_sem)
        self.o_hbm = o_hbm
        self.stage_buf = stage_buf
        self.block = block
        self.wire = wire

    def _wlen(self, sz: int) -> int:
        return wire_words(sz, self.block)

    def issue(self, d, sb_off, off, sz, rb_off, red=None):
        slot = self.gc[d] % self.depth
        prev = self.pending_send.pop((d, slot), None)
        if prev is not None:
            prev.wait_send()           # wire send slot free for reload
        prev_st = self.pending_store.pop((d, slot), None)
        if prev_st is not None:
            prev_st.wait()             # acc slot's last store landed
        ld = pltpu.make_async_copy(
            self.o_hbm.at[pl.ds(sb_off + off, sz)],
            self.stage_buf.at[d, slot, pl.ds(0, sz)],
            self.in_sem.at[d, slot])
        ld.start()
        if rb_off is not None:
            la = pltpu.make_async_copy(
                self.o_hbm.at[pl.ds(rb_off + off, sz)],
                self.acc_buf.at[d, slot, pl.ds(0, sz)],
                self.acc_sem.at[d, slot])
            la.start()
            self.pending_acc[(d, slot)] = la
        ld.wait()
        # fold the bytes down BEFORE they touch the slow fabric: the
        # wire run is ~3.9x smaller than the staged f32 chunk
        wsz = self._wlen(sz)
        self.send_buf[d, slot, :wsz] = _encode_f32(
            self.stage_buf[d, slot, :sz], self.block, self.wire)
        self._take_credit(d)
        dst = self.right if d == 0 else self.left
        rdma = pltpu.make_async_remote_copy(
            src_ref=self.send_buf.at[d, slot, pl.ds(0, wsz)],
            dst_ref=self.recv_buf.at[d, slot, pl.ds(0, wsz)],
            send_sem=self.send_sem.at[d, slot],
            recv_sem=self.recv_sem.at[d, slot],
            device_id=self._dev(dst),
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        rdma.start()
        self.pending_send[(d, slot)] = rdma
        self.gc[d] += 1
        return slot

    def drain(self, d, slot, rb_off, off, sz, red):
        self.pending_send[(d, slot)].wait_recv()
        wsz = self._wlen(sz)
        dec = _decode_f32(self.recv_buf[d, slot, :wsz], self.block,
                          self.wire)
        self.pending_acc.pop((d, slot)).wait()
        self.acc_buf[d, slot, :sz] = red(self.acc_buf[d, slot, :sz],
                                         dec)
        # the VPU read of recv_buf is synchronous: the slot is free
        self._grant(d)
        st = pltpu.make_async_copy(
            self.acc_buf.at[d, slot, pl.ds(0, sz)],
            self.o_hbm.at[pl.ds(rb_off + off, sz)],
            self.st_sem.at[d, slot])
        st.start()
        self.pending_store[(d, slot)] = st


def _quant_scratch(ndir: int, depth: int, chunk: int, wchunk: int):
    return [
        pltpu.VMEM((ndir, depth, chunk), jnp.float32),   # f32 stage
        pltpu.VMEM((ndir, depth, wchunk), jnp.int32),    # wire send
        pltpu.VMEM((ndir, depth, wchunk), jnp.int32),    # wire recv
        pltpu.VMEM((ndir, depth, chunk), jnp.float32),   # accumulator
        pltpu.SemaphoreType.DMA((ndir, depth)),          # stage loads
        pltpu.SemaphoreType.DMA((ndir, depth)),          # acc loads
        pltpu.SemaphoreType.DMA((ndir, depth)),          # stores
        pltpu.SemaphoreType.DMA((ndir, depth)),          # remote send
        pltpu.SemaphoreType.DMA((ndir, depth)),          # remote recv
        pltpu.SemaphoreType.REGULAR((ndir,)),            # slot credits
        pltpu.SemaphoreType.DMA(()),                     # init + encode
    ]


def _quant_spans(nblk: int, ndir: int, block: int):
    """Per-direction element ranges of a block, cut on quantization-
    block boundaries so every chunk encodes whole blocks."""
    if ndir == 1:
        return [(0, nblk)]
    nb = nblk // block
    h = ((nb + 1) // 2) * block
    return [(0, h), (h, nblk)]


# ---------------------------------------------------------------------------
# the kernel: quantized reduce-scatter + own-block encode
# ---------------------------------------------------------------------------

def _quant_rs_kernel(axis_name, p, nblk, chunk, depth, ndir, credits,
                     block, wire, x_hbm, o_hbm, w_hbm, *scratch):
    """Phase 1 of the quantized allreduce: the pipelined reduce-scatter
    rotation of _hbm_all_reduce_kernel with the codec fused in, then
    the rank's fully-reduced block is encoded once into the wire
    output ``w_hbm`` — the payload the (unchanged, exact) all-gather
    pass carries."""
    my = lax.axis_index(axis_name)
    right = lax.rem(my + 1, p)
    left = lax.rem(my - 1 + p, p)
    init_sem = scratch[-1]
    st = _QuantStreamer(p, ndir, depth, credits, left, right, o_hbm,
                        scratch[:-1], block=block, wire=wire)

    cp = pltpu.make_async_copy(x_hbm, o_hbm, init_sem)
    cp.start()
    cp.wait()
    st.enter()

    spans = _quant_spans(nblk, ndir, block)
    spans_chunks = [_chunks(lo, hi, chunk) for lo, hi in spans]

    def red(a, b):
        return a + b

    # reduce-scatter: same block rotation as the exact kernel — cw
    # round s passes the partial of block (my-s-1) rightward and folds
    # the arrival into block (my-s-2); ccw mirrors with +.
    for s in range(p - 1):
        sb = [lax.rem(my - s - 1 + 2 * p, p), lax.rem(my + s + 1, p)]
        rb = [lax.rem(my - s - 2 + 2 * p, p), lax.rem(my + s + 2, p)]
        rb_offs = [rb[d] * nblk for d in range(ndir)]
        st.stream_step(spans_chunks,
                       [sb[d] * nblk for d in range(ndir)],
                       rb_offs, rb_offs, red)
    st.finish()

    # block ``my`` is fully reduced on both lanes: encode it once into
    # the wire output (the quantized partial every peer will decode —
    # one codec pass, so all ranks land bit-identical results)
    wpb = 1 + block // 4
    for off, sz in _chunks(0, nblk, chunk):
        ld = pltpu.make_async_copy(
            o_hbm.at[pl.ds(my * nblk + off, sz)],
            st.stage_buf.at[0, 0, pl.ds(0, sz)], init_sem)
        ld.start()
        ld.wait()
        wsz = (sz // block) * wpb
        woff = (off // block) * wpb
        st.send_buf[0, 0, :wsz] = _encode_f32(
            st.stage_buf[0, 0, :sz], block, wire)
        stw = pltpu.make_async_copy(
            st.send_buf.at[0, 0, pl.ds(0, wsz)],
            w_hbm.at[pl.ds(woff, wsz)], init_sem)
        stw.start()
        stw.wait()


# ---------------------------------------------------------------------------
# wrapper
# ---------------------------------------------------------------------------

def quant_ring_all_reduce(x: jax.Array, axis_name: str,
                          num_devices: int, op: str = "sum", *,
                          wire: Optional[str] = None,
                          block_bytes: Optional[int] = None,
                          chunk_bytes: Optional[int] = None,
                          depth: Optional[int] = None,
                          bidirectional: Optional[bool] = None,
                          credits: Optional[bool] = None,
                          interpret=None) -> jax.Array:
    """Block-scaled quantized allreduce along ``axis_name``: quantized
    reduce-scatter (codec fused into the chunk pipeline), then the
    exact chunk-credit all-gather engine carries the quantized
    partials, decoded once at the end. Non-sum ops and integer dtypes
    take the exact hbm kernel (bit-exact fallback)."""
    p = num_devices
    if op != "sum" or np.dtype(x.dtype).kind != "f":
        # exact-mode fallback: min/max/prod and integer data never
        # quantize (the contract MV2T_QUANT_COLL documents)
        from .pallas_ici import hbm_ring_all_reduce
        return hbm_ring_all_reduce(
            x, axis_name, p, op, chunk_bytes=chunk_bytes, depth=depth,
            bidirectional=bidirectional, credits=credits,
            interpret=interpret)
    if p == 1:
        from .collectives import allreduce
        return allreduce(x, axis_name, op)
    if wire is None:
        from ..coll.tuning import quant_params
        wire, _budget = quant_params()
    if wire not in WIRE_FORMATS:
        raise ValueError(f"unknown quant wire format {wire!r}")
    interpret, credits = _resolve_flags(interpret, credits)
    blk = quant_block_elems(jnp.float32) if block_bytes is None else \
        max(8, (int(block_bytes) // 4) // 4 * 4)
    shape = x.shape
    n = int(np.prod(shape)) if shape else 1
    flat = x.reshape(n).astype(jnp.float32)
    nblk = -(-(-(-n // p)) // blk) * blk      # block-aligned ring block
    n_pad = nblk * p
    if n_pad > n:
        flat = jnp.pad(flat, (0, n_pad - n))  # 0 = the sum identity
    chunk = min(max(blk, _cfg_chunk_elems(jnp.float32, chunk_bytes)
                    // blk * blk), nblk)
    d = _cfg_depth(depth)
    ndir = _resolve_ndir(p, bidirectional)
    wblk = wire_words(nblk, blk)
    wchunk = wire_words(chunk, blk)
    kernel = functools.partial(_quant_rs_kernel, axis_name, p, nblk,
                               chunk, d, ndir, credits, blk, wire)
    _, own_wire = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((n_pad,), jnp.float32),
                   jax.ShapeDtypeStruct((wblk,), jnp.int32)],
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=_quant_scratch(ndir, d, chunk, wchunk),
        compiler_params=compiler_params(collective_id=_CID_QUANT_RS,
                                        has_side_effects=True),
        interpret=interpret,
        name=kernel_name(_quant_rs_kernel),
    )(flat)
    # the final all-gather pass carries the quantized partials over the
    # UNCHANGED chunk-credit engine — int32 wire blocks are just bytes
    wall = hbm_ring_all_gather(own_wire, axis_name, p,
                               chunk_bytes=chunk_bytes, depth=depth,
                               bidirectional=bidirectional,
                               credits=credits, interpret=interpret)
    out = _decode_f32(wall, blk, wire).astype(x.dtype)
    return out[:n].reshape(shape)
