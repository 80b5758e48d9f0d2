"""Platform / accelerator detection.

Analog of the reference's arch/HCA detection (SURVEY §2.5:
common/src/detect/arch/mv2_arch_detect.c) which keys the collective tuning
tables. Here the "arch × HCA" key becomes "tpu generation × topology", and we
detect it from JAX lazily (JAX import is deferred so that host-only rank
processes never touch the accelerator runtime).
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass


def env_asks_for_cpu() -> bool:
    """True when the caller's environment pins jax to the CPU backend
    (``JAX_PLATFORMS=cpu``, as tests/conftest.py and every CPU recipe
    in the README do). Reads the environment only — safe before jax is
    imported, and in processes that must never touch it."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"


@dataclass(frozen=True)
class PlatformInfo:
    platform: str          # "tpu" | "cpu" | "gpu"
    device_kind: str       # as jax reports it, e.g. "TPU v5 lite"
    num_devices: int
    num_processes: int
    # Per-chip interconnect and HBM bandwidth in GB/s: published peaks on
    # a TPU (``_TPU_PEAKS``), nominal placeholders on the virtual CPU
    # test mesh (``_CPU_MESH_NOMINAL`` — not a peak of anything).
    ici_bw_gbps: float
    hbm_bw_gbps: float


# Published per-chip peaks, keyed by the ``device_kind`` jax reports
# (lower-cased). They play the role of the per-arch constant tables in
# ibv_param.c:2354-2361 — they seed tuning defaults and bench's
# vs_baseline; measured profiles override them. Source: Google Cloud TPU
# documentation, the "System architecture" page of each generation
# ("Interchip Interconnect BW" in Gbit/s per chip, divided by 8; "HBM
# bandwidth per chip"). A TPU that is not in this table is an error, not
# a default.
_TPU_PEAKS = {
    # device_kind: (ICI GB/s per chip, HBM GB/s per chip)
    "tpu v5 lite": (200.0, 819.0),    # v5e: 1,600 Gbit/s, 819 GB/s
    "tpu v5e": (200.0, 819.0),
    "tpu v5p": (600.0, 2765.0),       # 4,800 Gbit/s, 2,765 GB/s
    "tpu v5": (600.0, 2765.0),
    "tpu v4": (300.0, 1228.0),        # 2,400 Gbit/s, 1,228 GB/s
    "tpu v6 lite": (448.0, 1640.0),   # v6e: 3,584 Gbit/s, 1,640 GB/s
    "tpu v6e": (448.0, 1640.0),
}

# The virtual CPU mesh the test-suite runs on has no interconnect; these
# keep ratio-based code (bench's vs_baseline, tuning seeds) finite there.
_CPU_MESH_NOMINAL = (10.0, 50.0)


def _tpu_peaks(device_kind: str):
    try:
        return _TPU_PEAKS[device_kind.lower()]
    except KeyError:
        raise RuntimeError(
            f"unknown TPU device_kind {device_kind!r}: add its published "
            f"peaks to utils/detect._TPU_PEAKS (known: "
            f"{sorted(_TPU_PEAKS)})") from None


@functools.lru_cache(maxsize=1)
def detect() -> PlatformInfo:
    """What jax runs on. A jax that fails to initialize raises here —
    it is never reported as 'a CPU'."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    kind = devs[0].device_kind
    ici, hbm = _tpu_peaks(kind) if platform == "tpu" \
        else _CPU_MESH_NOMINAL
    return PlatformInfo(platform=platform, device_kind=kind,
                        num_devices=len(devs),
                        num_processes=jax.process_count(),
                        ici_bw_gbps=ici, hbm_bw_gbps=hbm)


def arch_key() -> str:
    """Tuning-table key, analog of mv2_arch_hca_type."""
    info = detect()
    return f"{info.platform}:{info.device_kind}:{info.num_devices}"
