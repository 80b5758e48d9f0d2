"""The one traffic generator: from a traffic file's parameters and the
seed to each rank's input buffer. The loop that offers the traffic is
run.py's; the only loop kind today is ``closed`` (every rank calls back
to back after one barrier, OSU's loop)."""

from __future__ import annotations

import numpy as np

LOOPS = ("closed",)


def elements(traffic: dict, dtype: np.dtype) -> int:
    return int(traffic["bytes_per_rank"]) // dtype.itemsize


def make_input(traffic: dict, seed: int, rank: int, nelems: int,
               dtype: np.dtype) -> np.ndarray:
    """Rank ``rank``'s buffer: the same seed gives the same values.

    ``uniform_int``: whole numbers drawn uniformly from [lo, hi]. With
    |value| <= 2**20 and at most 8 ranks every float32 partial sum stays
    under 2**24 and is exact, in any order; a sum carried in bfloat16 or
    over a quantized wire is not."""
    values = traffic["values"]
    if values["kind"] != "uniform_int":
        raise KeyError(f"no generator for values of kind {values['kind']!r}")
    rng = np.random.default_rng([int(seed), int(rank)])
    return rng.integers(int(values["lo"]), int(values["hi"]), size=nelems,
                        dtype=np.int32, endpoint=True).astype(dtype)
