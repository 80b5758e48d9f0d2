"""Test harness configuration.

Multi-chip paths are tested on a virtual 8-device CPU mesh
(xla_force_host_platform_device_count) — the analog of the reference suite
running N ranks on localhost (SURVEY §4: "no fake backend; N processes on
localhost"). Env must be set before jax is first imported.
"""

import os

# The suite always runs on the CPU backend, whatever the session
# environment points at: tests need the virtual 8-device mesh. The chip is
# exercised by chip_smoke.py, not from here.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# keep CI deterministic and quiet
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# The device entry points place jax's persistent compilation cache
# (utils/compile_cache.py); tests compile afresh every run, so a stale or
# half-written entry can never decide a result.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy multi-process / C-compile / large-model tests — "
        "skipped by default so the suite finishes in minutes on a "
        "1-core host; run everything with MV2T_TEST_FULL=1")
    config.addinivalue_line(
        "markers",
        "lint: static-analysis gate (bin/mv2tlint --strict) and the "
        "runtime lock-order detector smoke — tier-1 by default; run "
        "only these with -m lint")
    config.addinivalue_line(
        "markers",
        "chaos: full fault-injection matrix (site x kind chaos sweeps, "
        "mid-collective kills, churn) — a small seeded subset runs in "
        "tier-1 unmarked; run the full matrix with -m chaos or "
        "bin/runtests --chaos (or MV2T_TEST_FULL=1)")
    config.addinivalue_line(
        "markers",
        "modelcheck: full-depth shm-protocol model exploration (np=4 "
        "seqlock waves, long-horizon lease) — a small-bound subset "
        "runs in tier-1 unmarked; run the full depth with "
        "-m modelcheck (or MV2T_TEST_FULL=1)")


def pytest_collection_modifyitems(config, items):
    if os.environ.get("MV2T_TEST_FULL"):
        return
    markexpr = config.getoption("-m", default="") or ""
    skip = pytest.mark.skip(reason="slow lane: set MV2T_TEST_FULL=1")
    skip_chaos = pytest.mark.skip(
        reason="chaos lane: run with -m chaos (or MV2T_TEST_FULL=1)")
    skip_model = pytest.mark.skip(
        reason="modelcheck lane: run with -m modelcheck (or "
               "MV2T_TEST_FULL=1)")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
        if "chaos" in item.keywords and "chaos" not in markexpr:
            item.add_marker(skip_chaos)
        if "modelcheck" in item.keywords and "modelcheck" not in markexpr:
            item.add_marker(skip_model)
