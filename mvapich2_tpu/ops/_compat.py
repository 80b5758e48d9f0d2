"""The one seam between ops/ and the installed jax (0.9.0), plus the
shared fallback accounting.

Everything here is written for the single jax this repo runs on: no
version probing, no alternate spellings. Three things live here:

``compiler_params`` / ``interpret_params`` / ``resolve_interpret`` — how
every kernel wrapper in ops/ builds its ``pallas_call`` arguments. The
interpret rule is ONE rule for the whole package (``resolve_interpret``):
a TPU backend never interprets, whatever was asked; off the TPU a kernel
interprets when the caller or the MV2T_ICI_INTERPRET cvar asks for it,
and local (no remote DMA) kernels interpret by default so the CPU suite
runs them. Interpreted kernels always get ``pltpu.InterpretParams`` (the
threaded TPU interpreter: remote DMAs, remote semaphore signals and the
barrier semaphore all work), so the credit handshake and entry barrier
the chip runs are the ones CPU tests run.

``serialize_executable`` / ``deserialize_executable`` — the daemon
exec-cache seam over ``jax.export``.

``note_fallback`` — the observability hook for the VMEM-cap / shape /
dtype rejections that used to be silent: every rejection bumps one of
the ``dev_coll_fallback_{size,dtype,shape,platform}`` pvars declared in
mpit.py. Kernel wrappers call it at trace time (once per compiled
shape); the per-call accounting for the MPI path lives in
coll/device.py.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from ..utils.mlog import get_logger

log = get_logger("pallas")


_BFLOAT16 = np.dtype(jax.numpy.bfloat16)


def dtype_kind(dtype) -> str:
    """numpy's kind letter for ``dtype`` as the device holds it: ``'f'``
    for bfloat16 too, which numpy knows only through ml_dtypes and
    reports as ``'V'``. The other ml_dtypes types keep their ``'V'``:
    no kernel or channel here has carried them. The one answer for the
    transport gate (coll/device._dtype_lowers) and the kernel tier gate
    (pallas_ici.planned_tier)."""
    dt = np.dtype(dtype)
    return "f" if dt == _BFLOAT16 else dt.kind


def compiler_params(**kw):
    """``pltpu.CompilerParams`` — one spelling, unknown keywords are a
    TypeError (a misspelt scheduling hint must not vanish silently)."""
    return pltpu.CompilerParams(**kw)


def kernel_name(kernel_fn) -> str:
    """``name=`` of a ``pallas_call``: ``mv2t`` + the kernel function's
    own name less its ``_kernel`` (``_hbm_all_reduce_kernel`` ->
    ``mv2t_hbm_all_reduce``). The compiler names the custom call after
    it, so a device trace's ``XLA Ops`` line carries the ``mv2t_`` token
    (chipbench's kernel_us reads it) and leads back to the source."""
    return "mv2t" + kernel_fn.__name__.removesuffix("_kernel")


def interpret_params(**kw):
    """The TPU interpreter config every interpreted kernel runs under."""
    return pltpu.InterpretParams(**kw)


def on_tpu() -> bool:
    """True when the process' default backend is a TPU. Initializes the
    backend (as any jit call would) and lets its failure propagate: a
    JAX that cannot start is an error, never 'not a TPU'."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret=None, *, local: bool = False):
    """The ``interpret=`` argument for a ``pallas_call`` — the single
    rule of ops/. ``interpret``: None (ask the MV2T_ICI_INTERPRET cvar;
    ``local`` kernels — no cross-device traffic — additionally default
    to the interpreter off the TPU), False, True, or a ready
    ``InterpretParams``. Returns False or an ``InterpretParams``.

    A TPU backend never interprets: the answer there is False whatever
    was asked, so no flag or leftover environment can put the
    interpreter between an MPI call and the chip."""
    if interpret is None:
        from ..utils.config import get_config
        interpret = bool(get_config()["ICI_INTERPRET"]) or \
            (local and not on_tpu())
    if not interpret:
        return False
    if on_tpu():
        log.warn("pallas interpret mode requested on a TPU backend; "
                 "ignored (kernels always compile on the chip)")
        return False
    if interpret is True:
        return interpret_params()
    return interpret


# -- device-executable export/import seam (the daemon exec cache) ------
# jax.export serializes a traced+lowered program (StableHLO + the
# already-compiled Mosaic payloads of any pallas custom calls) to
# portable bytes; deserializing skips jax tracing and lowering — the
# dominant cold-start cost of a device job's first collective. Both
# helpers return None when the program resists export (interpreter-mode
# kernels carry host callbacks), so callers no-op cleanly — the cache
# degrades to per-process builds, it never breaks the collective.

def exec_fingerprint() -> str:
    """The environment half of the executable-cache key: an artifact is
    only valid under the jax/backend/precision/tuning-profile that
    built it. Cheap string compare, never a version parse."""
    from ..utils.config import get_config
    prof = str(get_config().get("TUNING_PROFILE", "") or "")
    return (f"jax{jax.__version__}|{jax.default_backend()}"
            f"|x64:{int(bool(jax.config.jax_enable_x64))}|prof:{prof}")


def serialize_executable(fn, *args) -> Optional[bytes]:
    """Serialize ``fn`` (a jax.jit-wrapped callable) traced at the
    shapes/dtypes of ``args``. None = the program resists export — the caller skips caching."""
    from jax import export as jexp
    try:
        specs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
        return jexp.export(fn)(*specs).serialize()
    except Exception as e:   # noqa: BLE001 — caching is best-effort
        log.dbg(1, "executable export unavailable (%r)", e)
        return None


def deserialize_executable(blob: bytes):
    """Rehydrate a serialized executable as a jitted callable, or None
    when the blob does not load (the caller rebuilds from source)."""
    from jax import export as jexp
    try:
        return jax.jit(jexp.deserialize(blob).call)
    except Exception as e:   # noqa: BLE001
        log.dbg(1, "executable import failed (%r); rebuilding", e)
        return None


def note_fallback(coll: str, reason: str, nbytes: int,
                  dtype: Optional[object] = None) -> None:
    """Count one device-collective fallback to the XLA lowering.
    ``reason`` is one of size/dtype/shape/platform — the pvar family
    predeclared in mpit.py (fetch-side idiom)."""
    from .. import mpit
    mpit.pvar(f"dev_coll_fallback_{reason}").inc()
    log.dbg(1, "device collective %s fell back to XLA (%s, %d bytes, %s)",
            coll, reason, nbytes, dtype)
