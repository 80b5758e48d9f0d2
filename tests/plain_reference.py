"""Plain numpy references for MPI collectives and point-to-point: what
every rank must hold afterwards, computed on the host from all ranks'
inputs with nothing but numpy. Independent of ops/, coll/device.py and
pt2pt/ (it imports none of them), so a test may hold the device path
to it bit for bit.

``inputs`` is one flat array per rank, in rank order; each function
returns one array per rank (``None`` where the rank receives nothing).
"""

from typing import List, Optional, Sequence, Tuple

import numpy as np

_FOLD = {"sum": np.add, "max": np.maximum, "min": np.minimum,
         "prod": np.multiply}


def _fold(inputs: Sequence[np.ndarray], op: str) -> np.ndarray:
    """Rank 0's buffer folded with every later rank's, rank by rank, in
    the inputs' own dtype."""
    total = inputs[0].copy()
    for x in inputs[1:]:
        total = _FOLD[op](total, x).astype(total.dtype)
    return total


def alltoall(inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """MPI_Alltoall: a send buffer is ``p`` equal blocks; rank ``r``
    receives, in sender order, block ``r`` of every sender's buffer."""
    p = len(inputs)
    c = inputs[0].size // p
    return [np.concatenate([x[r * c:(r + 1) * c] for x in inputs])
            for r in range(p)]


def alltoallv(inputs: Sequence[np.ndarray],
              counts: Sequence[Sequence[int]]) -> List[np.ndarray]:
    """MPI_Alltoallv with dense displacements on both sides:
    ``counts[s][r]`` elements go from sender ``s`` to rank ``r``."""
    p = len(inputs)
    starts = [np.concatenate([[0], np.cumsum(counts[s])]) for s in range(p)]
    return [np.concatenate([inputs[s][starts[s][r]:starts[s][r + 1]]
                            for s in range(p)])
            for r in range(p)]


def allreduce(inputs: Sequence[np.ndarray], op: str = "sum"
              ) -> List[np.ndarray]:
    total = _fold(inputs, op)
    return [total] * len(inputs)


def reduce(inputs: Sequence[np.ndarray], root: int, op: str = "sum"
           ) -> List[Optional[np.ndarray]]:
    total = _fold(inputs, op)
    return [total if r == root else None for r in range(len(inputs))]


def bcast(inputs: Sequence[np.ndarray], root: int) -> List[np.ndarray]:
    return [inputs[root]] * len(inputs)


def allgather(inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
    return [np.concatenate(list(inputs))] * len(inputs)


def reduce_scatter_block(inputs: Sequence[np.ndarray], op: str = "sum"
                         ) -> List[np.ndarray]:
    """Rank ``r`` keeps block ``r`` of the folded buffer."""
    p = len(inputs)
    total = _fold(inputs, op)
    c = total.size // p
    return [total[r * c:(r + 1) * c] for r in range(p)]


def on_groups(collective, inputs: Sequence[np.ndarray],
              groups: Sequence[Sequence[int]], *args, **kw
              ) -> List[Optional[np.ndarray]]:
    """The derived forms: ``collective`` (one of the six above) called
    at once on every communicator of a partition of the world.
    ``inputs`` is every world rank's buffer; ``groups`` the partition,
    each group its members' world ranks in the order of their ranks in
    the new communicator (what ``MPI_Comm_split``'s keys, a group's
    order or a ``dup`` give). A group's result is the world's reference
    applied to that group's inputs in group order, so a ``root`` counts
    within the group; a world rank in no group gets ``None``, as does a
    rank its group's collective hands nothing. Knows nothing of
    communicators, context ids or channels."""
    out: List[Optional[np.ndarray]] = [None] * len(inputs)
    for group in groups:
        got = collective([inputs[w] for w in group], *args, **kw)
        for rank, w in enumerate(group):
            out[w] = got[rank]
    return out


def sendrecv(inputs: Sequence[np.ndarray],
             pairs: Sequence[Tuple[Optional[int], Optional[int]]]
             ) -> List[Optional[np.ndarray]]:
    """MPI_Sendrecv on every rank at once: ``pairs[r]`` is rank ``r``'s
    ``(dest, source)``, ``None`` for MPI_PROC_NULL. Rank ``r`` holds
    afterwards what its source sent, which is the source's input if the
    source's dest is ``r`` (anything else would hang, not answer)."""
    out = []
    for r, (_dest, source) in enumerate(pairs):
        if source is None:
            out.append(None)
            continue
        if pairs[source][0] != r:
            raise ValueError(f"rank {r} receives from {source}, which "
                             f"sends to {pairs[source][0]}")
        out.append(inputs[source])
    return out


def deliver(messages: Sequence[Tuple[int, int, int, np.ndarray]],
            receives: Sequence[Tuple[int, Optional[int], Optional[int]]]
            ) -> List[Tuple[int, int, np.ndarray]]:
    """MPI's matching rule, by a list. ``messages`` are ``(src, dst,
    tag, payload)`` in the order they were sent; ``receives`` are
    ``(dst, source, tag)`` in the order they were posted, ``None`` a
    wildcard. Each receive takes the earliest message to its rank that
    its envelope matches and that no earlier receive took (messages of
    one sender do not overtake one another). Returns ``(source, tag,
    payload)`` for every receive. With wildcards over several senders
    MPI leaves the order between senders open: give such messages in
    the order the test forces."""
    left = list(messages)
    got = []
    for dst, source, tag in receives:
        for i, (s, d, t, payload) in enumerate(left):
            if d == dst and source in (None, s) and tag in (None, t):
                got.append((s, t, payload))
                del left[i]
                break
        else:
            raise ValueError(f"no message for receive {(dst, source, tag)}")
    return got
