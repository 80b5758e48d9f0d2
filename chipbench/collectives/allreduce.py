"""allreduce (sum): the call, its plain reference, its lower-precision
control, and the arithmetic of its bandwidth numbers.

One module per collective; ``run.py`` loads the one the cell's traffic
file names. A later PR adds ``<collective>.py`` beside this one with the
same five functions and edits nothing here.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

NAME = "allreduce"


def call(comm, x):
    """The served call: what a user of the library writes."""
    return comm.allreduce(x)


def reference(inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """What every rank must hold afterwards, by plain numpy on the host
    inputs: one array per rank (the same one for an allreduce). Summed
    rank by rank in the inputs' own dtype; the traffic's integer values
    make every f32 partial sum exact, so the order cannot matter."""
    total = inputs[0].copy()
    for x in inputs[1:]:
        total += x
    return [total] * len(inputs)


def lower_precision(inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """The control: the same sum carried in bfloat16 (the nearest
    precision below the configuration's float32) on jax's default
    device, handed back as float32 on the host. What a bf16 accumulator
    or a quantized wire would give a caller in the program's place."""
    import jax
    import jax.numpy as jnp
    total = jnp.asarray(inputs[0]).astype(jnp.bfloat16)
    for x in inputs[1:]:
        total = total + jnp.asarray(x).astype(jnp.bfloat16)
    got = np.asarray(jax.block_until_ready(total.astype(jnp.float32)))
    return [got] * len(inputs)


def bus_factor(ranks: int) -> float:
    """OSU/NCCL bus-bandwidth factor of an allreduce over ``ranks``."""
    return 2.0 * (ranks - 1) / ranks


def least_bytes(kind: str, ranks: int, bytes_per_rank: int) -> Tuple[float, str]:
    """The fewest bytes one chip has to move for one call, and the peak
    (a key of peaks.json) they move over.

    ``slot``: all ranks on one chip; the fused slot reduce reads the R
    deposited buffers once and writes the shared result once, through
    HBM: (R + 1) x m. Staging copies are the program's choice, not the
    algorithm's need, and are not counted.

    ``ring``: one rank per chip; a bandwidth-optimal allreduce sends
    2(p-1)/p x m out of every chip, over that chip's ICI ports. The
    peak is all ports together; a 1-D ring drives at most two of a v5e
    chip's four, so this share reads low and cannot pass 100 %.
    """
    if kind == "slot":
        return (ranks + 1.0) * bytes_per_rank, "hbm_GBps"
    if kind == "ring":
        return bus_factor(ranks) * bytes_per_rank, "ici_GBps"
    raise KeyError(f"allreduce has no least-bytes rule for {kind!r}")
