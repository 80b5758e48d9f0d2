"""allreduce (sum) where ranks outnumber chips: the call, its plain
reference, its lower-precision control, and the arithmetic of its
bandwidth numbers. The call and the answer are ``allreduce.py``'s; what
differs is the roofline, which counts chips and not ranks: ``k`` ranks a
chip fold in that chip's HBM first (level 1), and only the ``p = ranks /
k`` chips ride the ICI ring (level 2).

Its own copy of the five functions: nothing here imports the program or
``allreduce.py``.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

NAME = "allreduce"          # the spans' name: mpi:allreduce, dev_allreduce

FOLD_RULE = "fold_k"        # least_bytes rules are named fold_k<k>


def call(comm, x):
    """The served call: what a user of the library writes."""
    return comm.allreduce(x)


def reference(inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """What every rank must hold afterwards, by plain numpy on the host
    inputs: one array per rank (the same one for an allreduce). Summed
    rank by rank in the inputs' own dtype; the traffic's integer values
    make every f32 partial sum exact, so neither the order nor the
    grouping by chip can matter."""
    total = inputs[0].copy()
    for x in inputs[1:]:
        total += x
    return [total] * len(inputs)


def lower_precision(inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """The control: the same sum carried in bfloat16 (the nearest
    precision below the configuration's float32) on jax's default
    device, handed back as float32 on the host."""
    import jax
    import jax.numpy as jnp
    total = jnp.asarray(inputs[0]).astype(jnp.bfloat16)
    for x in inputs[1:]:
        total = total + jnp.asarray(x).astype(jnp.bfloat16)
    got = np.asarray(jax.block_until_ready(total.astype(jnp.float32)))
    return [got] * len(inputs)


def bus_factor(ranks: int) -> float:
    """OSU/NCCL bus-bandwidth factor of an allreduce over ``ranks``:
    OSU's number goes by the ranks of the job, not by its chips."""
    return 2.0 * (ranks - 1) / ranks


def ranks_per_chip(kind: str) -> int:
    """``k`` of a rule named ``fold_k<k>``."""
    if not kind.startswith(FOLD_RULE) or not kind[len(FOLD_RULE):].isdigit():
        raise KeyError(f"allreduce_2level has no least-bytes rule for "
                       f"{kind!r}")
    k = int(kind[len(FOLD_RULE):])
    if k < 1:
        raise KeyError(f"{kind!r}: at least one rank a chip")
    return k


def fold_bytes(k: int, bytes_per_rank: int) -> float:
    """Level 1 alone, per chip: the fused slot reduce reads the chip's
    ``k`` deposits once and writes their sum once, through HBM:
    ``(k + 1) x m``. What ``fold_kernel_roofline_pct`` divides."""
    return (k + 1.0) * bytes_per_rank


def least_bytes(kind: str, ranks: int, bytes_per_rank: int) -> Tuple[float, str]:
    """The fewest bytes one chip has to move for one call, and the peak
    (a key of peaks.json) they move over.

    ``fold_k<k>``: ``k`` ranks a chip, ``p = ranks / k`` chips. A
    bandwidth-optimal allreduce over ``p`` chips sends ``2(p-1)/p x m``
    out of every chip over that chip's ICI ports: the ICI phase alone.
    It bounds the call from below whatever the fold does: the fold's
    ``(k + 1) x m`` through HBM (``fold_bytes``) is a few times shorter
    and could run under the ring, so it is not added, and the share
    cannot pass 100 %. The peak is all ports together, where a chip of a
    2x2 has two links: the share reads low, as ``allreduce.py``'s
    ``ring``."""
    k = ranks_per_chip(kind)
    if ranks % k:
        raise ValueError(f"{ranks} ranks do not fill chips of {k}")
    p = ranks // k
    return 2.0 * (p - 1) / p * bytes_per_rank, "ici_GBps"
