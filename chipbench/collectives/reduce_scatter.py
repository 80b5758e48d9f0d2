"""reduce_scatter (MPI_Reduce_scatter_block, sum): the call, its plain
reference, its lower-precision control, and the arithmetic of its
bandwidth numbers. The same five functions as ``allreduce.py`` and
``allgather.py``; the harness loads this one when a traffic file names
``reduce_scatter``. ``bytes_per_rank`` is the send buffer a rank hands
in (OSU's message size for ``osu_reduce_scatter``); every rank gets a
``ranks``-th of it back.

Its own copy of the five functions: nothing here imports the program or
``allreduce.py``.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

# the program's name for the call: its spans are ``mpi:<NAME>`` and
# ``dev_<NAME>``, which the per-layer readers look for (the traffic
# file's ``collective`` is this file's name, OSU's)
NAME = "reduce_scatter_block"


def call(comm, x):
    """The served call: what a user of the library writes."""
    return comm.reduce_scatter_block(x)


def _blocks(total: np.ndarray, ranks: int) -> List[np.ndarray]:
    c = total.size // ranks
    return [total[r * c:(r + 1) * c] for r in range(ranks)]


def reference(inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """What every rank must hold afterwards, by plain numpy on the host
    inputs: rank r's array is block r of the element-wise sum of all
    ranks' buffers. Summed rank by rank in the inputs' own dtype; the
    traffic's integer values make every f32 partial sum exact, so the
    order (the ring folds each block in another) cannot matter."""
    total = inputs[0].copy()
    for x in inputs[1:]:
        total += x
    return _blocks(total, len(inputs))


def lower_precision(inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """The control: the same sum carried in bfloat16 (the nearest
    precision below the configuration's float32) on jax's default
    device, cut into the ranks' blocks and handed back as float32 on
    the host. What a bf16 accumulator or a quantized wire would give a
    caller in the program's place."""
    import jax
    import jax.numpy as jnp
    total = jnp.asarray(inputs[0]).astype(jnp.bfloat16)
    for x in inputs[1:]:
        total = total + jnp.asarray(x).astype(jnp.bfloat16)
    got = np.asarray(jax.block_until_ready(total.astype(jnp.float32)))
    return _blocks(got, len(inputs))


def bus_factor(ranks: int) -> float:
    """nccl-tests' bus-bandwidth factor of a reduce-scatter, over the
    send buffer's bytes: of its ``ranks`` blocks a rank's own never has
    to leave its chip."""
    return (ranks - 1.0) / ranks


def least_bytes(kind: str, ranks: int, bytes_per_rank: int) -> Tuple[float, str]:
    """The fewest bytes one chip has to move for one call, and the peak
    (a key of peaks.json) they move over.

    ``ring``: one rank per chip; a bandwidth-optimal reduce-scatter
    sends (p-1)/p x m out of every chip (a partial of every block but
    its own), over that chip's ICI ports, and as many arrive. The peak
    is all ports together; a 1-D ring drives at most two of a v5e
    chip's four, so the share reads low and cannot pass 100 %. Nothing
    the kernel does can send fewer bytes (tile padding only adds).

    ``slot``: all ranks on one chip; the R deposited buffers are read
    once and the R blocks of the sum, together one buffer, written once
    through HBM: R x m read and m written. Staging copies are the
    program's choice and are not counted.
    """
    if kind == "ring":
        return bus_factor(ranks) * bytes_per_rank, "ici_GBps"
    if kind == "slot":
        return (ranks + 1.0) * bytes_per_rank, "hbm_GBps"
    raise KeyError(f"reduce_scatter has no least-bytes rule for {kind!r}")
