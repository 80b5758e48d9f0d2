"""chipbench's own tests run on the CPU: eight virtual devices, Pallas
kernels interpreted, no persistent compile cache. Run them by hand:

    python -m pytest chipbench/tests -q

The environment is fixed before jax is first imported."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
