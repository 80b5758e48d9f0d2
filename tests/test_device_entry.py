"""The rules that keep the device in plain sight (ISSUE 22): one
interpret rule for ops/, a peaks table without defaults, a compile cache
that can be placed from outside, and a --vpod front door that leaves the
accelerator only when the caller asks for the CPU."""

import os

import jax
import pytest
from jax.experimental.pallas import tpu as pltpu

from mvapich2_tpu.ops import _compat, pallas_ici
from mvapich2_tpu.utils import compile_cache, detect

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    keep = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", keep[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", keep[1])


@pytest.fixture
def accelerator_env(monkeypatch):
    """The environment of a TPU host: nothing pins jax to the CPU (the
    helper reads the environment only; no backend is touched)."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)


def test_compile_cache_defaults_to_the_checkout(monkeypatch, accelerator_env,
                                                restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.ensure_compile_cache() == \
        os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == \
        os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path,
                                           accelerator_env,
                                           restore_cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.ensure_compile_cache() == str(tmp_path)
    # jax reads the variable by itself: no directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_no_cache_is_placed_when_the_cpu_is_asked_for(monkeypatch,
                                                      restore_cache_config):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.ensure_compile_cache() == ""
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_entries_counts_programs_not_side_files(tmp_path):
    assert compile_cache.cache_entries(str(tmp_path / "absent")) == 0
    for name in ("jit_f-abc-cache", "jit_f-abc-atime", "jit_g-def-cache"):
        (tmp_path / name).write_bytes(b"x")
    assert compile_cache.cache_entries(str(tmp_path)) == 2


@pytest.mark.parametrize("kind,peaks", [
    ("TPU v5 lite", (200.0, 819.0)), ("TPU v5e", (200.0, 819.0)),
    ("TPU v4", (300.0, 1228.0)), ("TPU v6 lite", (448.0, 1640.0))])
def test_tpu_peaks_table(kind, peaks):
    assert detect._tpu_peaks(kind) == peaks


def test_unknown_tpu_is_an_error_not_a_default():
    with pytest.raises(RuntimeError, match="unknown TPU device_kind"):
        detect._tpu_peaks("TPU v99")


def test_detect_does_not_turn_a_broken_jax_into_a_cpu(monkeypatch):
    def boom():
        raise RuntimeError("backend failed to initialize")
    monkeypatch.setattr(jax, "devices", boom)
    detect.detect.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="failed to initialize"):
            detect.detect()
    finally:
        detect.detect.cache_clear()


@pytest.mark.parametrize("asked,local,want", [
    (None, False, False),      # cvar unset: ICI kernels do not interpret
    (None, True, True),        # local kernels interpret off the TPU
    (False, True, False), (True, False, True)])
def test_one_interpret_rule_off_the_tpu(asked, local, want):
    got = _compat.resolve_interpret(asked, local=local)
    assert isinstance(got, pltpu.InterpretParams) if want else got is False


def test_interpret_params_pass_through():
    ip = _compat.interpret_params(detect_races=True)
    assert _compat.resolve_interpret(ip) is ip


@pytest.mark.parametrize("asked", [None, True, False,
                                   "params"])
def test_a_tpu_backend_never_interprets(monkeypatch, asked):
    monkeypatch.setattr(_compat, "on_tpu", lambda: True)
    monkeypatch.setenv("MV2T_ICI_INTERPRET", "1")
    if asked == "params":
        asked = _compat.interpret_params()
    assert _compat.resolve_interpret(asked) is False
    assert _compat.resolve_interpret(asked, local=True) is False


def test_platform_fallback_cannot_be_the_answer_on_a_tpu(monkeypatch):
    assert pallas_ici.planned_tier("allreduce", 1 << 20, "float32",
                                   "sum", interpret=False) == \
        ("xla", "platform")
    monkeypatch.setattr(pallas_ici, "on_tpu", lambda: True)
    _tier, reason = pallas_ici.planned_tier("allreduce", 1 << 20, "float32",
                                            "sum", interpret=False)
    # (a loaded CPU profile may still say xla *by size*; never by platform)
    assert reason != "platform"


@pytest.mark.parametrize("value,want", [
    ("cpu", True), ("cpu,tpu", True), ("tpu", False), ("", False),
    (None, False)])
def test_vpod_leaves_the_device_only_when_asked_for_the_cpu(monkeypatch,
                                                            value, want):
    if value is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", value)
    assert detect.env_asks_for_cpu() is want
