"""sendrecv (MPI_Sendrecv between two ranks, device buffers): the call,
its plain reference, its lower-precision control, and the arithmetic of
its bandwidth numbers. The same five functions as ``allreduce.py``; the
harness loads this one when a traffic file names ``sendrecv``. Not a
collective: the two ranks of the configuration swap one message each
way, which is what makes the call fit the harness's closed loop (the
ranks stay in lockstep by their receives) and its count of the lane's
per-message pvars (one message sent and one received a rank a call).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import ml_dtypes
import numpy as np

NAME = "sendrecv"

# the nearest precision below each payload type a configuration states
_ONE_LOWER = {np.dtype(ml_dtypes.bfloat16): np.dtype(ml_dtypes.float8_e5m2),
              np.dtype(np.float32): np.dtype(ml_dtypes.bfloat16)}


def call(comm, x):
    """The served call: what a user of the library writes. The plane
    itself describes the receive (its size and type; it is neither read
    nor written as a receive buffer), and the call returns the received
    device array."""
    other = 1 - comm.rank
    return comm.sendrecv(x, other, 0, x, other, 0)


def reference(inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """What each rank must hold afterwards, by plain numpy on the host
    inputs: the other rank's buffer. The payload is moved, never
    computed on, so the comparison is of bits."""
    if len(inputs) != 2:
        raise ValueError(f"sendrecv is between 2 ranks, not {len(inputs)}")
    return [inputs[1], inputs[0]]


def lower_precision(inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """The control: the same exchange with the payload carried one
    precision lower and handed back in its own type (bfloat16 for
    float32): what a lossy or quantized wire would give a caller in the
    program's place."""
    wire = _ONE_LOWER[inputs[0].dtype]
    return [got.astype(wire).astype(got.dtype) for got in reference(inputs)]


def bus_factor(ranks: int) -> float:
    """A rank's ``m`` bytes leave it and ``m`` arrive: OSU's pt2pt
    bandwidth counts the message as it is."""
    return 1.0


def least_bytes(kind: str, ranks: int, bytes_per_rank: int) -> Tuple[float, str]:
    """The fewest bytes the chip has to move for one call, and the peak
    (a key of peaks.json) they move over.

    ``slot``: both ranks on one chip. The configuration guarantees that
    the received array is the receiver's own (the sender may delete or
    donate its array once the send has completed), so each of the
    ``ranks`` messages is read once and written once through HBM:
    ranks x 2 x m. Were the result allowed to alias the sender's array
    it would be 0, which is why the guarantee is written into the
    configuration.
    """
    if kind == "slot":
        return 2.0 * ranks * bytes_per_rank, "hbm_GBps"
    raise KeyError(f"sendrecv has no least-bytes rule for {kind!r}")
