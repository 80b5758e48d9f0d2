"""Pallas ring collectives — hand-scheduled ICI kernels.

The pallas analog of the mrail RDMA fast path (SURVEY §3.2:
MPIDI_CH3I_MRAILI_Fast_rdma_send_complete, gen2/ibv_send_inline.h:493):
where the reference RDMA-writes into the peer's paired vbuf ring and polls
head/tail flags, these kernels `make_async_remote_copy` into the neighbor's
double-buffered VMEM slots and wait on DMA semaphores. Flow control is a
per-direction credit handshake (the vbuf credit-return of ibv_send.c:
320-360): each round a shard grants one credit to each neighbor and
consumes one from each, bounding ring skew to ±1 round so double buffering
is race-free (verified with the pallas interpret-mode race detector).
Every kernel opens with the neighbour barrier of ops/pallas_ici.py
(``_entry_barrier``): no signal or DMA targets a chip that has not
entered the kernel.

They exist (1) as the explicit, schedulable form of the ring collectives
for cases XLA's fused lowering can't express — fusing the reduction into
the transfer loop, custom communication/compute interleaving — and (2) as
the skeleton the ring-attention kernel in models/ follows.

Both kernels are VMEM-resident (shard + out + 2 comm slots must fit in
~16 MiB); callers fall back to lax.psum / lax.all_gather beyond that — the
eager->rendezvous style crossover, chosen by the tuning layer.

Layout: the shard is flattened and padded to ``p`` whole-tile blocks,
``(p, block_rows, 128)`` with ``block_rows`` a multiple of the dtype's
sublane tile; the ring block and the comm slot are leading, untiled
indices, so the only traced indices Mosaic sees are leading ones.

Usage: inside shard_map over a 1-D mesh axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.mlog import get_logger
from ._compat import (compiler_params, kernel_name, note_fallback,
                      resolve_interpret)
from .pallas_ici import (_LANES, _as_blocks, _entry_barrier, _from_blocks,
                         _tile_rows)

log = get_logger("pallas")

# VMEM budget guard: shard + out + 2 slots, leave headroom
VMEM_LIMIT_BYTES = 4 * 1024 * 1024

# Mosaic collective ids (the barrier semaphore of each kernel)
_CID_ALLGATHER = 0
_CID_ALLREDUCE = 1

FROM_LEFT = 0   # credit slots, indexed by which neighbor granted it
FROM_RIGHT = 1


def _grant_credits(cap_sem, left, right):
    """Grant one slot-credit to each neighbor (I am my left neighbor's
    RIGHT, so I bump its FROM_RIGHT slot, and vice versa)."""
    pltpu.semaphore_signal(cap_sem.at[FROM_RIGHT], inc=1, device_id=left,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_signal(cap_sem.at[FROM_LEFT], inc=1, device_id=right,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)


def _take_credits(cap_sem):
    """Consume one credit from each direction — blocks until both
    neighbors granted this round's slot."""
    pltpu.semaphore_wait(cap_sem.at[FROM_LEFT], 1)
    pltpu.semaphore_wait(cap_sem.at[FROM_RIGHT], 1)


def _ring_step(comm_buf, send_sem, recv_sem, step, right):
    """One ring hop: remote-DMA slot ``step % 2`` into the right
    neighbor's other slot; returns the slot the left neighbor's block
    landed in."""
    send_slot = step % 2
    recv_slot = (step + 1) % 2
    rdma = pltpu.make_async_remote_copy(
        src_ref=comm_buf.at[send_slot],
        dst_ref=comm_buf.at[recv_slot],
        send_sem=send_sem.at[send_slot],
        recv_sem=recv_sem.at[recv_slot],
        device_id=right,
        device_id_type=pltpu.DeviceIdType.LOGICAL,
    )
    rdma.start()
    rdma.wait()
    return recv_slot


def _ring_all_gather_kernel(axis_name, p, x_ref, out_ref, comm_buf,
                            send_sem, recv_sem, cap_sem):
    """x: (rows, 128); out: (p, rows, 128); comm_buf: (2, rows, 128)."""
    my_id = lax.axis_index(axis_name)
    right = lax.rem(my_id + 1, p)
    left = lax.rem(my_id - 1 + p, p)

    _entry_barrier([left, right])
    _grant_credits(cap_sem, left, right)   # initial slot availability
    out_ref[my_id] = x_ref[...]
    comm_buf[0] = x_ref[...]

    for step in range(p - 1):
        _take_credits(cap_sem)
        recv_slot = _ring_step(comm_buf, send_sem, recv_sem, step, right)
        src_dev = lax.rem(my_id - step - 1 + p, p)
        out_ref[src_dev] = comm_buf[recv_slot]
        _grant_credits(cap_sem, left, right)   # slot consumed: return credit
    # consume the final grants: also a completion barrier so no neighbor
    # still has an in-flight write into our buffers at kernel exit
    _take_credits(cap_sem)


def _ring_scratch(rows: int, dtype):
    return [
        pltpu.VMEM((2, rows, _LANES), dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.REGULAR((2,)),
    ]


def ring_all_gather(x: jax.Array, axis_name: str, num_devices: int,
                    interpret=None) -> jax.Array:
    """All-gather along ``axis_name`` via an explicit RDMA ring.
    ``x``: this shard's block [chunk, ...]; returns [p*chunk, ...]."""
    p = num_devices
    if p == 1:
        return lax.all_gather(x, axis_name, tiled=True)
    if p * x.nbytes > VMEM_LIMIT_BYTES:
        # the gathered output + comm slots must be VMEM-resident; larger
        # buffers belong to the HBM-streaming tier (ops/pallas_ici) —
        # counted, never silent (the r5 4 MiB cliff lesson)
        note_fallback("allgather", "size", p * x.nbytes, x.dtype)
        return lax.all_gather(x, axis_name, tiled=True)
    shape = x.shape
    m = int(np.prod(shape)) if shape else 1
    rows = _tile_rows(m, x.dtype)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_ring_all_gather_kernel, axis_name, p),
        out_shape=jax.ShapeDtypeStruct((p, rows, _LANES), x.dtype),
        in_specs=[vmem],
        out_specs=vmem,
        scratch_shapes=_ring_scratch(rows, x.dtype),
        compiler_params=compiler_params(collective_id=_CID_ALLGATHER),
        interpret=resolve_interpret(interpret),
        name=kernel_name(_ring_all_gather_kernel),
    )(_as_blocks(x.reshape(m), 1, rows)[0])
    out = _from_blocks(out, m)
    return out.reshape((p * shape[0],) + shape[1:]) if shape else out


def _ring_all_reduce_kernel(axis_name, p, x_ref, out_ref, comm_buf,
                            send_sem, recv_sem, cap_sem):
    """Reduce-scatter ring + all-gather ring with the reduction fused into
    the receive path (the SHARP-style in-transit reduce, done in VMEM).
    x/out: (p, rows, 128); comm_buf: (2, rows, 128)."""
    my_id = lax.axis_index(axis_name)
    right = lax.rem(my_id + 1, p)
    left = lax.rem(my_id - 1 + p, p)

    _entry_barrier([left, right])
    _grant_credits(cap_sem, left, right)
    out_ref[...] = x_ref[...]

    # Phase 1 (rounds 0..p-2): reduce-scatter — round s passes the partial
    # of block (my-s-1) rightward and folds the arriving partial into block
    # (my-s-2); after p-1 rounds block `my_id` is fully reduced (same
    # convention as reduce_scatter_ring in coll/algorithms.py).
    for step in range(p - 1):
        send_blk = lax.rem(my_id - step - 1 + 2 * p, p)
        recv_blk = lax.rem(my_id - step - 2 + 2 * p, p)
        _take_credits(cap_sem)
        comm_buf[step % 2] = out_ref[send_blk]
        recv_slot = _ring_step(comm_buf, send_sem, recv_sem, step, right)
        out_ref[recv_blk] = out_ref[recv_blk] + comm_buf[recv_slot]
        _grant_credits(cap_sem, left, right)

    # Phase 2 (rounds p-1..2p-3): all-gather — round s passes block (my-s)
    # rightward and receives block (my-s-1). Slot parity continues from
    # phase 1 so credits and buffers stay consistent.
    for step in range(p - 1):
        send_blk = lax.rem(my_id - step + 2 * p, p)
        recv_blk = lax.rem(my_id - step - 1 + 2 * p, p)
        _take_credits(cap_sem)
        comm_buf[(p - 1 + step) % 2] = out_ref[send_blk]
        recv_slot = _ring_step(comm_buf, send_sem, recv_sem,
                               p - 1 + step, right)
        out_ref[recv_blk] = comm_buf[recv_slot]
        _grant_credits(cap_sem, left, right)
    _take_credits(cap_sem)   # drain final grants; exit-time completion barrier


def ring_all_reduce(x: jax.Array, axis_name: str, num_devices: int,
                    interpret=None) -> jax.Array:
    """Sum-allreduce along ``axis_name`` via an explicit fused ring.
    Any shape (flattened, zero-padded to p whole-tile blocks) up to the
    VMEM-resident size; larger shards fall back to lax.psum (the
    tuning-layer crossover), counted."""
    p = num_devices
    if p == 1:
        return lax.psum(x, axis_name)
    if x.nbytes > VMEM_LIMIT_BYTES:
        # observable, not silent: the tuning layer's tier dispatch
        # (ops/pallas_ici.ici_all_reduce) streams these through HBM
        # instead; a direct caller landing here is counted per traced
        # shape via the dev_coll_fallback_* family
        note_fallback("allreduce", "size", x.nbytes, x.dtype)
        return lax.psum(x, axis_name)
    shape = x.shape
    n = int(np.prod(shape)) if shape else 1
    rows = _tile_rows(-(-n // p), x.dtype)
    n_pad = p * rows * _LANES
    flat = x.reshape(n)
    if n_pad > n:
        flat = jnp.pad(flat, (0, n_pad - n))     # 0 = the sum identity
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_ring_all_reduce_kernel, axis_name, p),
        out_shape=jax.ShapeDtypeStruct((p, rows, _LANES), x.dtype),
        in_specs=[vmem],
        out_specs=vmem,
        scratch_shapes=_ring_scratch(rows, x.dtype),
        compiler_params=compiler_params(collective_id=_CID_ALLREDUCE),
        interpret=resolve_interpret(interpret),
        name=kernel_name(_ring_all_reduce_kernel),
    )(flat.reshape(p, rows, _LANES))
    out = out.reshape(n_pad)
    return (out[:n] if n_pad > n else out).reshape(shape)
