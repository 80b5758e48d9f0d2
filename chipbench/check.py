"""The comparison that decides ``correct``. No jax, no program.

Every number compared is printed beside its limit by ``report``. The
limits are exact (0): the traffic's values are integers whose float32
sums are exact in any order, so a sound run differs from the plain
reference in no element, and the bfloat16 control in nearly all of them
(PERF.md section 2 has the readings).
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


class Compared:
    """One number held against its limit."""

    def __init__(self, name: str, value, limit, ok: bool):
        self.name, self.value, self.limit, self.ok = name, value, limit, ok

    def line(self) -> str:
        return (f"correct: {self.name} = {self.value} (limit {self.limit}) "
                f"{'ok' if self.ok else 'FAILED'}")


def differing_elements(got: np.ndarray, want: np.ndarray) -> int:
    """Elements of ``got`` whose bits differ from ``want``'s; a shape or
    dtype mismatch counts every element."""
    if got.shape != want.shape or got.dtype != want.dtype:
        return int(max(got.size, want.size, 1))
    bits = np.dtype(f"u{got.dtype.itemsize}")
    return int(np.count_nonzero(got.view(bits) != want.view(bits)))


def largest_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest |got - want|, read only where the two differ."""
    if got.shape != want.shape:
        return float("inf")
    off = got != want
    if not off.any():
        return 0.0
    return float(np.max(np.abs(got[off].astype(np.float64)
                               - want[off].astype(np.float64))))


def compare_results(label: str, results: Sequence[np.ndarray],
                    reference: Sequence[np.ndarray]) -> List[Compared]:
    """Bit equality of every rank's result with the reference."""
    if any(r is None for r in results):
        return [Compared(f"{label}: ranks with no result",
                         sum(r is None for r in results), 0, False)]
    counts = [differing_elements(g, w) for g, w in zip(results, reference)]
    worst = max(counts)
    gap = max((largest_gap(g, w)
               for g, w, c in zip(results, reference, counts) if c),
              default=0.0)
    return [Compared(f"{label}: elements differing from the numpy reference, "
                     f"worst of {len(results)} ranks", worst, 0, worst == 0),
            Compared(f"{label}: largest |result - reference|", gap, 0.0,
                     gap == 0.0)]


def compare_counts(ranks: int, calls_per_rank: int,
                   level_rise: Dict[str, int], fallback_rise: Dict[str, int],
                   compiles_in_window: int, cache_before: int,
                   cache_after: int, off_device: int) -> List[Compared]:
    """The guards: the calls took the device path, on the rank's own
    device, with nothing compiled inside the window."""
    want = ranks * calls_per_rank
    out = [Compared(f"{name} rose by (ranks x calls issued = {want})",
                    rose, want, rose == want)
           for name, rose in sorted(level_rise.items())]
    if not level_rise:
        out.append(Compared("level pvars read", 0, ">= 1", False))
    fb = sum(fallback_rise.values())
    out.append(Compared(f"dev_coll_fallback_* rose by (sum of "
                        f"{len(fallback_rise)} pvars)", fb, 0,
                        fb == 0 and len(fallback_rise) > 0))
    out.append(Compared("results not on the rank's own device", off_device,
                        0, off_device == 0))
    out.append(Compared("compilations inside the window", compiles_in_window,
                        0, compiles_in_window == 0))
    out.append(Compared(f"compile-cache entries added in the window "
                        f"({cache_before} before)", cache_after - cache_before,
                        0, cache_after == cache_before))
    return out


def verdict(compared: Sequence[Compared]) -> bool:
    return all(c.ok for c in compared)


def report(compared: Sequence[Compared], say=print) -> None:
    for c in compared:
        say(c.line())
