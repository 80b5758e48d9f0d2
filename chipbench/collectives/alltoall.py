"""alltoall (MPI_Alltoall, uniform blocks): the call, its plain
reference, its lower-precision control, and the arithmetic of its
bandwidth numbers. The same five functions as ``allreduce.py``; the
harness loads this one when a traffic file names ``alltoall``.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import ml_dtypes
import numpy as np

NAME = "alltoall"

# the nearest precision below each payload type a configuration states
_ONE_LOWER = {np.dtype(ml_dtypes.bfloat16): np.dtype(ml_dtypes.float8_e5m2),
              np.dtype(np.float32): np.dtype(ml_dtypes.bfloat16)}


def call(comm, x):
    """The served call: what a user of the library writes."""
    return comm.alltoall(x)


def reference(inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """What each rank must hold afterwards, by plain numpy on the host
    inputs: rank ``r`` receives, in sender order, block ``r`` of every
    sender's buffer (a buffer is ``len(inputs)`` equal blocks). The
    payload is moved, never computed on, so the comparison is of bits."""
    p = len(inputs)
    c = inputs[0].size // p
    return [np.concatenate([x[r * c:(r + 1) * c] for x in inputs])
            for r in range(p)]


def lower_precision(inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """The control: the same exchange with the payload carried one
    precision lower and handed back in its own type (float8_e5m2 for
    bfloat16, bfloat16 for float32): what a lossy or quantized wire
    would give a caller in the program's place."""
    wire = _ONE_LOWER[inputs[0].dtype]
    return [got.astype(wire).astype(got.dtype) for got in reference(inputs)]


def bus_factor(ranks: int) -> float:
    """OSU/NCCL bus-bandwidth factor of an alltoall over ``ranks``: of
    the ``m`` bytes a rank hands in, the block for itself never leaves
    the chip."""
    return (ranks - 1.0) / ranks


def least_bytes(kind: str, ranks: int, bytes_per_rank: int) -> Tuple[float, str]:
    """The fewest bytes one chip has to move for one call, and the peak
    (a key of peaks.json) they move over.

    ``pairwise``: one rank per chip; every block but the chip's own has
    to leave it, (p-1)/p x m, over that chip's ICI ports, and as many
    arrive. The peak is all ports together, every link driven one way
    at full rate. Nothing the kernel does can send fewer bytes (tile
    padding and retransmission only add), so the share cannot pass
    100 %. It reads low on a 2x2 for a reason the kernel cannot mend:
    in the 1-D order jax.devices()[:4] the step-2 partner of the
    pairwise schedule is the diagonal chip, which no link reaches, so a
    third of the bytes cross two links and take a neighbour's port on
    the way.

    ``slot``: all ranks on one chip; the exchange is a transpose of the
    (R, R, c) slot array through HBM: R x m read and R x m written.
    Staging copies are the program's choice and are not counted.
    """
    if kind == "pairwise":
        return bus_factor(ranks) * bytes_per_rank, "ici_GBps"
    if kind == "slot":
        return 2.0 * ranks * bytes_per_rank, "hbm_GBps"
    raise KeyError(f"alltoall has no least-bytes rule for {kind!r}")
