"""Arithmetic from samples to the end-to-end metrics. No jax, no program."""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np


def iteration_latency_us(per_rank_s: Sequence[Sequence[float]]) -> np.ndarray:
    """One latency per iteration, in microseconds: the max over ranks of
    each rank's own ``t0 -> block_until_ready`` time (a collective is
    done when its last rank has its result; OSU's "Max Latency")."""
    n = min(len(r) for r in per_rank_s)
    a = np.asarray([list(r[:n]) for r in per_rank_s], dtype=np.float64)
    return a.max(axis=0) * 1e6


def percentile(values: np.ndarray, q: float) -> float:
    """Linear-interpolated percentile of all the window's samples."""
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def bus_bandwidth_GBps(factor: float, bytes_per_rank: int, iterations: int,
                       window_s: float) -> float:
    """OSU/NCCL bus bandwidth over the whole window: all the work the
    window completed over all the time it took, so a stall shows."""
    return factor * bytes_per_rank * iterations / window_s / 1e9


def end_to_end(lat_us: np.ndarray, factor: float, bytes_per_rank: int,
               window_s: float, setup_s: float) -> Dict[str, float]:
    """Every end-to-end metric this harness can report, by name; run.py
    prints the ones BENCHMARK.json lists for the cell."""
    return {
        "lat_us_p50": percentile(lat_us, 50),
        "lat_us_p95": percentile(lat_us, 95),
        "busbw_GBps": bus_bandwidth_GBps(factor, bytes_per_rank,
                                         len(lat_us), window_s),
        "setup_s": setup_s,
    }
