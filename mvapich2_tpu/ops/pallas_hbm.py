"""HBM slot-segment collectives — the on-chip shared-memory phase.

When several ranks' buffers are co-resident in one chip's HBM — host
ranks sharing a device (mpirun on one chip), or the intra-chip stage of
a hierarchical collective — the chip plays the role the mmap'd slotted
shared-memory segment plays in the reference
(``src/mpi/coll/ch3_shmem_coll.c:527-528``: one slot per rank, slot
length tuned): every rank deposits into its slot, ONE fused pass
produces the result, and ranks read the result back. Two kernels:

``fused_reduce_to_slot`` — the product's allreduce/reduce/
reduce_scatter phase: read all ``R`` slots, reduce across the rank axis
on the VPU, write the result **once**. The broadcast is zero-copy: the
result slot is shared, every rank's result handle is a view of it (jax
arrays are immutable, so sharing is safe) — host ranks copy out of it
into their private recvbufs on the untimed host side, exactly as the
reference's on-node ranks copy out of the shm segment. Device traffic
is ``R*m`` read + ``m`` written — the information floor for the
reduction — instead of the ``2*R*m`` of a materialized per-rank
broadcast; since the read stream dominates, it also runs near the HBM
read-bandwidth peak rather than the lower mixed read/write stream
ceiling.

``fused_allreduce`` — the materialized variant (every rank row written
with the result, ``2*R*m`` traffic) for callers that require private
per-rank device outputs.

Layouts: *planar* ``(R, M, 128)`` (slot r contiguous) or *interleaved*
``(M, R, 128)`` (each ``(R, 128)`` tile holds one 128-lane slice of
every rank, so each grid block is one contiguous HBM slab). Measured on
TPU v5e the two are within noise of each other for the reduction. A
third form needs no slot array at all: ``hbm_slot_allreduce_operands``
takes the ``R`` deposited ``(n,)`` buffers as ``R`` operands of the same
kernel and reads each where it lies (a 1-D buffer and its ``(n/128,
128)`` view hold the same bytes in the same order, so the program is
the kernel and bitcasts). That is what HBMSlotChannel runs on
device-resident deposits: no stack, no copy. Host deposits are one
``np.stack`` + one transfer into the planar form.

Block sizes are a measured, not guessed, crossover (the
``allreduce_osu.c:3015-3400`` tuned-path discipline): the tuning
profile key ``hbm_slot_block_m`` / ``hbm_fused_block_m`` overrides the
defaults (autotune.py measures them).
"""

from __future__ import annotations

import functools
import operator
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ._compat import compiler_params, resolve_interpret

from jax.experimental import pallas as pl

# Measured-best defaults on TPU v5e (64 MiB/rank, 8 ranks); a committed
# tuning profile overrides them via the kernel-param keys below.
DEFAULT_SLOT_BLOCK_M = 1024
DEFAULT_FUSED_BLOCK_M = 512


def _tuned_default(key: str, fallback: int) -> int:
    from ..coll.tuning import kernel_param   # lazy: ops must not pull
    return kernel_param(key, fallback)       # coll in at import time


def _pick_block(M: int, bm: int) -> int:
    while M % bm:
        bm //= 2
    if bm < 1:
        raise ValueError(f"M={M} has no power-of-two block divisor")
    return bm


def _slot_reduce_call(operands, in_specs, terms, M: int, L: int, bm: int,
                      scale: float, side_effects: bool, interpret):
    """The one ``mv2t_slot_reduce`` pallas_call. ``terms(x_refs)`` gives
    the block's ``(bm, L)`` addends out of the input refs, whatever form
    the slots came in; they are added in that order (in f32 for
    narrower floats, as ``jnp.sum`` accumulates), so every form of the
    same slots returns the same bits."""
    dtype = operands[0].dtype
    wide = (jnp.float32 if jnp.issubdtype(dtype, jnp.floating)
            and dtype.itemsize < 4 else dtype)

    def krnl(*refs):
        *x_refs, o_ref = refs
        s = functools.reduce(
            operator.add, (t.astype(wide) for t in terms(x_refs)))
        if scale != 1.0:
            s = s * scale
        o_ref[...] = s.astype(o_ref.dtype)

    return pl.pallas_call(
        krnl, grid=(M // bm,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, L), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, L), dtype),
        compiler_params=compiler_params(
            dimension_semantics=("arbitrary",),
            has_side_effects=side_effects),
        interpret=resolve_interpret(interpret, local=True),
        name="mv2t_slot_reduce",
    )(*operands)


def fused_reduce_to_slot(x: jax.Array, *, layout: str = "planar",
                         block_m: Optional[int] = None,
                         mean: bool = False,
                         side_effects: bool = False,
                         interpret=None) -> jax.Array:
    """Reduce ``R`` co-resident rank slots into one ``(M, 128)`` result
    slot in a single fused HBM pass (read ``R*m``, write ``m``).

    ``x`` is ``(R, M, 128)`` planar or ``(M, R, 128)`` interleaved.
    ``side_effects`` marks the call effectful so repeated identical
    calls inside one program are not CSE'd away (benchmark harnesses
    that time K back-to-back executions). ``interpret``: see
    _compat.resolve_interpret (None = compiled on a TPU, interpreted
    anywhere else).
    """
    if layout == "planar":
        R, M, L = x.shape
        in_spec = lambda bm: pl.BlockSpec((R, bm, L), lambda i: (0, i, 0))
        terms = lambda refs: (refs[0][r] for r in range(R))
    elif layout == "interleaved":
        M, R, L = x.shape
        in_spec = lambda bm: pl.BlockSpec((bm, R, L), lambda i: (i, 0, 0))
        terms = lambda refs: (refs[0][...].sum(axis=1),)
    else:
        raise ValueError(f"bad layout {layout!r}")
    bm = _pick_block(M, block_m or _tuned_default(
        "hbm_slot_block_m", DEFAULT_SLOT_BLOCK_M))
    return _slot_reduce_call(
        (x,), [in_spec(bm)], terms, M, L, bm, (1.0 / R) if mean else 1.0,
        side_effects, interpret)


def fused_allreduce(x: jax.Array, *, block_m: Optional[int] = None,
                    mean: bool = False, donate: bool = False,
                    parallel: bool = True, interpret=None) -> jax.Array:
    """Materialized allreduce over interleaved ``(M, R, 128)`` slots:
    sum across the rank axis and write the broadcast rows back into
    every rank's rows from registers, one fused pass (``2*R*m``
    traffic; the reduced row is never re-read — XLA's fused
    sum+broadcast re-reads it per output row and measures ~15% slower).
    """
    M, R, L = x.shape
    bm = _pick_block(M, block_m or _tuned_default(
        "hbm_fused_block_m", DEFAULT_FUSED_BLOCK_M))
    scale = (1.0 / R) if mean else 1.0

    def krnl(x_ref, o_ref):
        s = x_ref[...].sum(axis=1, keepdims=True)
        if scale != 1.0:
            s = s * scale
        o_ref[...] = jnp.broadcast_to(s, o_ref.shape)

    kw = {"input_output_aliases": {0: 0}} if donate else {}
    return pl.pallas_call(
        krnl, grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, R, L), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((bm, R, L), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=compiler_params(
            dimension_semantics=("parallel" if parallel else "arbitrary",)),
        interpret=resolve_interpret(interpret, local=True),
        name="mv2t_fused_allreduce",
        **kw,
    )(x)


# ---------------------------------------------------------------------------
# (R, n) rank-buffer convenience wrappers
# ---------------------------------------------------------------------------

def _pad_to_lanes(bufs: jax.Array) -> Tuple[jax.Array, int]:
    R, n = bufs.shape
    pad = (-n) % 128
    if pad:
        bufs = jnp.pad(bufs, ((0, 0), (0, pad)))
    return bufs, n


def hbm_slot_allreduce(bufs: jax.Array, *, mean: bool = False,
                       block_m: Optional[int] = None,
                       interpret=None) -> jax.Array:
    """Allreduce ``(R, n)`` co-resident rank buffers through the HBM
    slot segment; returns the single shared ``(n,)`` result (the
    zero-copy broadcast — hand every rank this same array)."""
    bufs, n = _pad_to_lanes(bufs)
    R, npad = bufs.shape
    out = fused_reduce_to_slot(bufs.reshape(R, npad // 128, 128),
                               layout="planar", mean=mean,
                               block_m=block_m, interpret=interpret)
    return out.reshape(npad)[:n]


def hbm_slot_allreduce_operands(bufs: Sequence[jax.Array], *,
                                mean: bool = False,
                                block_m: Optional[int] = None,
                                interpret=None) -> jax.Array:
    """:func:`hbm_slot_allreduce` for ``R`` separate ``(n,)`` rank
    buffers, ``n`` a multiple of 128: each is an operand of the kernel
    under its own ``(bm, 128)`` block spec, so nothing stacks or copies
    them first. Returns the shared ``(n,)`` result, bit-equal to the
    stacked entry's on the same buffers."""
    R, (n,) = len(bufs), bufs[0].shape
    if n % 128:
        raise ValueError(f"n={n} is not whole 128-lane rows "
                         f"(hbm_slot_allreduce pads a stacked array)")
    M = n // 128
    bm = _pick_block(M, block_m or _tuned_default(
        "hbm_slot_block_m", DEFAULT_SLOT_BLOCK_M))
    out = _slot_reduce_call(
        [b.reshape(M, 128) for b in bufs],
        [pl.BlockSpec((bm, 128), lambda i: (i, 0))] * R,
        lambda refs: (r[...] for r in refs),
        M, 128, bm, (1.0 / R) if mean else 1.0, False, interpret)
    return out.reshape(n)


def pack_interleaved(bufs: jax.Array) -> jax.Array:
    """``(R, n)`` per-rank buffers -> interleaved ``(M, R, 128)`` slots
    (n must be a multiple of 128)."""
    R, n = bufs.shape
    return jnp.transpose(bufs.reshape(R, n // 128, 128), (1, 0, 2))


def unpack_interleaved(slots: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_interleaved` -> ``(R, n)``."""
    M, R, L = slots.shape
    return jnp.transpose(slots, (1, 0, 2)).reshape(R, M * L)


# ---------------------------------------------------------------------------
# bench / autotune candidate set
# ---------------------------------------------------------------------------

def bench_candidates(M: int, R: int, L: int = 128) -> List[
        Tuple[str, Callable, int, bool]]:
    """``(name, op, bytes_moved_per_op, chains)`` for the
    measured-crossover selection the bench and autotuner perform (the
    runtime analog of the reference's per-arch tuning tables). ``op``
    maps the interleaved ``(M, R, L)`` slot array to either the shared
    result slot (slot-reduce, ``(R+1)*m`` traffic) or the materialized
    broadcast (``2*R*m``). ``chains`` is True when the op is
    shape-preserving (out feeds in for a timed chain); chains=False ops
    are marked effectful so repeated calls are not CSE'd."""
    m = M * L * 4
    cands: List[Tuple[str, Callable, int, bool]] = []
    for bm in (512, 1024):
        if M % bm == 0:
            cands.append((
                f"hbm_slot_reduce_b{bm}",
                functools.partial(fused_reduce_to_slot,
                                  layout="interleaved", mean=True,
                                  block_m=bm, side_effects=True),
                (R + 1) * m, False))
    for bm in (128, 512):
        if M % bm == 0:
            cands.append((
                f"hbm_fused_bcast_b{bm}",
                functools.partial(fused_allreduce, mean=True, block_m=bm),
                2 * R * m, True))
    return cands
