"""allgather (MPI_Allgather, equal blocks): the call, its plain
reference, its lower-precision control, and the arithmetic of its
bandwidth numbers. The same five functions as ``allreduce.py`` and
``alltoall.py``; the harness loads this one when a traffic file names
``allgather``. ``bytes_per_rank`` is the shard a rank hands in; every
rank gets ``ranks`` times as much back.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import ml_dtypes
import numpy as np

NAME = "allgather"

# the nearest precision below each payload type a configuration states
_ONE_LOWER = {np.dtype(ml_dtypes.bfloat16): np.dtype(ml_dtypes.float8_e5m2),
              np.dtype(np.float32): np.dtype(ml_dtypes.bfloat16)}


def call(comm, x):
    """The served call: what a user of the library writes."""
    return comm.allgather(x)


def reference(inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """What every rank must hold afterwards, by plain numpy on the host
    inputs: every rank's block, in rank order (the same array for every
    rank). The payload is moved, never computed on, so the comparison
    is of bits."""
    return [np.concatenate(inputs)] * len(inputs)


def lower_precision(inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """The control: the same gather with the payload carried one
    precision lower and handed back in its own type (float8_e5m2 for
    bfloat16, bfloat16 for float32): what a lossy or quantized wire
    would give a caller in the program's place."""
    wire = _ONE_LOWER[inputs[0].dtype]
    return [got.astype(wire).astype(got.dtype) for got in reference(inputs)]


def bus_factor(ranks: int) -> float:
    """OSU/NCCL bus-bandwidth factor of an allgather whose message size
    is the shard: a rank receives the ``ranks - 1`` blocks that are not
    its own. (NCCL's ``(p-1)/p`` is over the gathered size, ``ranks``
    times the shard: the same number.)"""
    return ranks - 1.0


def least_bytes(kind: str, ranks: int, bytes_per_rank: int) -> Tuple[float, str]:
    """The fewest bytes one chip has to move for one call, and the peak
    (a key of peaks.json) they move over.

    ``ring``: one rank per chip; every chip has to receive the p - 1
    blocks it lacks, and in a ring as many leave it (its own, then each
    one it passes on): (p-1) x m out of every chip, over that chip's
    ICI ports. The peak is all ports together; a 1-D ring drives at
    most two of a v5e chip's four, so the share reads low and cannot
    pass 100 %. Nothing the kernel does can send fewer bytes (tile
    padding only adds).

    ``slot``: all ranks on one chip; the R deposited blocks are read
    once and the gathered array written once through HBM: R x m read
    and R x m written. Staging copies are the program's choice and are
    not counted.
    """
    if kind == "ring":
        return bus_factor(ranks) * bytes_per_rank, "ici_GBps"
    if kind == "slot":
        return 2.0 * ranks * bytes_per_rank, "hbm_GBps"
    raise KeyError(f"allgather has no least-bytes rule for {kind!r}")
