"""bcast (MPI_Bcast from rank 0): the call, its plain reference, its
lower-precision control, and the arithmetic of its bandwidth numbers.
The same five functions as ``allreduce.py``, ``alltoall.py`` and
``allgather.py``; the harness loads this one when a traffic file names
``bcast``. ``bytes_per_rank`` is the message: the root's buffer, which
every rank holds afterwards. Every rank hands a buffer in (a non-root's
is MPI's receive buffer); the harness makes each rank's other, so a
rank handed its own buffer back, or another non-root's, shows.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import ml_dtypes
import numpy as np

NAME = "bcast"
ROOT = 0

# the nearest precision below each payload type a configuration states
_ONE_LOWER = {np.dtype(ml_dtypes.bfloat16): np.dtype(ml_dtypes.float8_e5m2),
              np.dtype(np.float32): np.dtype(ml_dtypes.bfloat16)}


def call(comm, x):
    """The served call: what a user of the library writes."""
    return comm.bcast(x, root=ROOT)


def reference(inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """What every rank must hold afterwards, by plain numpy on the host
    inputs: the root's buffer (the same array for every rank). The
    payload is moved, never computed on, so the comparison is of
    bits."""
    return [inputs[ROOT]] * len(inputs)


def lower_precision(inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """The control: the same broadcast with the payload carried one
    precision lower and handed back in its own type (float8_e5m2 for
    bfloat16, bfloat16 for float32): what a lossy or quantized wire
    would give a caller in the program's place."""
    wire = _ONE_LOWER[inputs[0].dtype]
    return [got.astype(wire).astype(got.dtype) for got in reference(inputs)]


def bus_factor(ranks: int) -> float:
    """OSU/NCCL bus-bandwidth factor of a broadcast: 1. The message has
    to leave the root once and reach every rank once; its bus bandwidth
    is the message over the time, whatever the number of ranks."""
    return 1.0


def least_bytes(kind: str, ranks: int, bytes_per_rank: int) -> Tuple[float, str]:
    """The fewest bytes one chip has to move for one call, and the peak
    (a key of peaks.json) they move over.

    ``ring``: one rank per chip; the message has to leave the root,
    over that chip's ICI ports, whatever the algorithm (a chain, a
    tree, scatter and gather): m out of the root, and m into every
    other chip. The peak is all ports together; a chip of a 2x2 has two
    links where the peak counts four ports, so the share cannot pass
    about half. Nothing the kernel does can send fewer bytes (tile
    padding only adds).
    """
    if kind == "ring":
        return float(bytes_per_rank), "ici_GBps"
    raise KeyError(f"bcast has no least-bytes rule for {kind!r}")
