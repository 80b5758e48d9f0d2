"""XLA-native collectives over mesh axes — the ICI data path.

This is the TPU-first replacement for the reference's entire L2 transport
stack (SURVEY §5.8): where mrail posts verbs work requests and polls CQs
(ibv_send.c, ibv_channel_manager.c), here every collective is a traced XLA
op over a named mesh axis — XLA schedules it onto ICI links, fuses
surrounding elementwise work, and overlaps communication with compute.
Mapping table (reference -> here):

    MPIR_Allreduce_MV2 (allreduce_osu.c:3720)  -> allreduce/psum
    MPIR_Bcast_MV2 (bcast_osu.c:3347)          -> bcast (all_gather of root)
    MPIR_Allgather_MV2 (allgather_osu.c:2593)  -> all_gather
    alltoall_osu.c zoo                         -> all_to_all (ICI all2all)
    MPI_Sendrecv ring shifts (§5.7)            -> ppermute ring_shift
    halo exchange over MPI_Cart                -> halo_exchange
    MPIR_Scan                                  -> scan_axis (associative)

All functions must be called inside ``shard_map``/``pjit`` with the axis
name bound (use mvapich2_tpu.parallel.MeshComm for the wrapping).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

AxisName = Union[str, Tuple[str, ...]]


def axis_size(axis: AxisName) -> int:
    """Static size of the bound axis (MPI_Comm_size analog)."""
    return lax.axis_size(axis)


def axis_rank(axis: AxisName):
    """This shard's rank along the axis (MPI_Comm_rank analog)."""
    return lax.axis_index(axis)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def allreduce(x, axis: AxisName, op: str = "sum"):
    """MPI_Allreduce -> one fused in-network reduction over ICI.

    XLA's AllReduce over ICI is the analog of SHARP in-switch reduction
    (rdma/ibv_sharp.c) — the reduction happens *in the interconnect
    fabric*, no host staging, at near-wire bandwidth."""
    if op == "sum":
        return lax.psum(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    if op == "prod":
        # exact product (ints, zeros, negatives): gather the axis and
        # reduce locally — log/exp tricks are positive-float-only
        return jnp.prod(lax.all_gather(x, axis), axis=0)
    if op == "mean":
        return lax.pmean(x, axis)
    raise ValueError(f"unsupported device op {op!r}")


def reduce_scatter(x, axis: AxisName, scatter_dimension: int = 0,
                   op: str = "sum", tiled: bool = True):
    """MPI_Reduce_scatter_block -> psum_scatter (ring reduce-scatter on
    ICI; the first phase of the bandwidth-optimal allreduce)."""
    assert op == "sum", "reduce_scatter lowers natively for sum"
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_dimension,
                            tiled=tiled)


def scan_axis(x, axis: AxisName):
    """Inclusive prefix sum over the axis (MPI_Scan for MPI_SUM).

    Lowered as a masked matmul against the gathered axis — O(p) compute on
    the MXU but a single all_gather of comm (fine for p <= 256 shards)."""
    p = axis_size(axis)
    idx = lax.axis_index(axis)
    gathered = lax.all_gather(x, axis)            # [p, ...]
    mask = (jnp.arange(p) <= idx).astype(x.dtype)
    return jnp.tensordot(mask, gathered, axes=1)


# ---------------------------------------------------------------------------
# data movement
# ---------------------------------------------------------------------------

def all_gather(x, axis: AxisName, tiled: bool = False, gather_axis: int = 0):
    """MPI_Allgather -> ICI ring all-gather."""
    return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def bcast(x, axis: AxisName, root: int = 0):
    """MPI_Bcast: select the root's shard everywhere.

    Implemented as a one-hot psum — XLA lowers this to a broadcast from
    the root over ICI (the mcast analog, common/src/mcast/ibv_mcast.c)."""
    idx = lax.axis_index(axis)
    contrib = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(contrib, axis)


def all_to_all(x, axis: AxisName, split_axis: int = 0, concat_axis: int = 0,
               tiled: bool = True):
    """MPI_Alltoall -> single fused ICI all-to-all (the MoE dispatch/return
    shuffle; alltoall_osu.c's entire zoo collapses to this)."""
    return lax.all_to_all(x, axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


def ppermute(x, axis: AxisName, perm: Sequence[Tuple[int, int]]):
    """MPI_Sendrecv with an arbitrary (src, dst) pattern -> lax.ppermute.
    This is the pt2pt primitive of the device path: each (src, dst) pair is
    one ICI neighbor transfer (the vbuf-ring RDMA fast path analog)."""
    return lax.ppermute(x, axis, perm)


def ring_shift(x, axis: AxisName, shift: int = 1):
    """Rotate shards around the axis ring by ``shift`` (+ = to higher
    ranks). The building block of ring collectives and ring attention."""
    p = axis_size(axis)
    perm = [(i, (i + shift) % p) for i in range(p)]
    return lax.ppermute(x, axis, perm)


def sendrecv_shift(x, axis: AxisName, shift: int = 1):
    """Bidirectional neighbor exchange: returns (from_left, from_right)
    for the 1-D halo pattern."""
    return ring_shift(x, axis, shift), ring_shift(x, axis, -shift)


def halo_exchange(x, axis: AxisName, halo: int, dim: int = 0,
                  periodic: bool = True):
    """3D-stencil halo exchange (BASELINE config 4): each shard sends its
    boundary slabs of width ``halo`` along ``dim`` to both neighbors and
    returns the array padded with received halos.

    Host analog: Isend/Irecv pairs over an MPI_Cart (src/mpi/topo/); here
    both directions are two ppermutes that XLA can run concurrently on the
    two ICI ports of the axis."""
    lo = lax.slice_in_dim(x, 0, halo, axis=dim)
    hi = lax.slice_in_dim(x, x.shape[dim] - halo, x.shape[dim], axis=dim)
    from_left = ring_shift(hi, axis, 1)    # left neighbor's high slab
    from_right = ring_shift(lo, axis, -1)  # right neighbor's low slab
    if not periodic:
        p = axis_size(axis)
        idx = lax.axis_index(axis)
        from_left = jnp.where(idx == 0, jnp.zeros_like(from_left), from_left)
        from_right = jnp.where(idx == p - 1, jnp.zeros_like(from_right),
                               from_right)
    return jnp.concatenate([from_left, x, from_right], axis=dim)


def barrier(axis: AxisName):
    """MPI_Barrier: a 1-element psum forces a cross-axis sync point."""
    return lax.psum(jnp.zeros((), jnp.float32), axis)


# ---------------------------------------------------------------------------
# composed patterns (SURVEY §5.7 — the sequence-parallel primitive set)
# ---------------------------------------------------------------------------

def moe_shuffle(tokens, axis: AxisName):
    """Ulysses/MoE-style reshard: tokens [E_local_groups, ...] distributed
    by expert -> all_to_all so each shard holds its experts' tokens
    (BASELINE config 3)."""
    return all_to_all(tokens, axis, split_axis=0, concat_axis=0, tiled=True)


def ring_allreduce_manual(x, axis: AxisName):
    """Reduce-scatter + all-gather allreduce spelled out with ppermutes —
    the explicit form of MPIR_Allreduce_pt2pt_ring_MV2 (allreduce_osu.c:
    3824). Exists for the tuning layer to benchmark against the fused
    lax.psum lowering (and as the skeleton pallas kernels follow)."""
    p = axis_size(axis)
    if p == 1:
        return x
    idx = lax.axis_index(axis)
    n = x.shape[0]
    xpad = x if n % p == 0 else jnp.pad(x, [(0, p - n % p)] +
                                        [(0, 0)] * (x.ndim - 1))
    blocks = xpad.reshape((p, -1) + xpad.shape[1:])

    # reduce-scatter: p-1 ring steps
    def rs_step(s, acc_blocks):
        # pass partial for block (idx - s - 1) to the right; it arrives as
        # the partial for block (idx - s - 2) from the left
        send_blk = (idx - s - 1) % p
        chunk = jnp.take(acc_blocks, send_blk, axis=0, mode="wrap")
        recvd = ring_shift(chunk, axis, 1)
        recv_blk = (idx - s - 2) % p
        mine = jnp.take(acc_blocks, recv_blk, axis=0, mode="wrap")
        upd = mine + recvd
        return acc_blocks.at[recv_blk].set(upd)

    acc = blocks
    for s in range(p - 1):
        acc = rs_step(s, acc)

    # all-gather: p-1 ring steps propagating the reduced blocks. After the
    # reduce-scatter phase my fully-reduced block is block `idx` (same
    # convention as reduce_scatter_ring in coll/algorithms.py): at step s I
    # pass block (idx - s) rightward and receive block (idx - s - 1).
    def ag_step(s, acc_blocks):
        send_blk = (idx - s) % p
        chunk = jnp.take(acc_blocks, send_blk, axis=0, mode="wrap")
        recvd = ring_shift(chunk, axis, 1)
        recv_blk = (idx - s - 1) % p
        return acc_blocks.at[recv_blk].set(recvd)

    for s in range(p - 1):
        acc = ag_step(s, acc)
    out = acc.reshape((-1,) + xpad.shape[1:])[:n]
    return out
