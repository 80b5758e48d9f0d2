"""PMPI-style profiling interface.

Analog of the reference's weak-symbol profiling shim (every MPI_* has a
PMPI_* alias — e.g. `#pragma weak MPI_Allreduce = PMPI_Allreduce`,
src/mpi/coll/allreduce.c:75): a tool interposes on the MPI_* names and
calls through to PMPI_*. Python redesign: interceptors register around the
Comm/File/Win method tables; ``pmpi(obj, name)`` is the PMPI_* escape
hatch — the unwrapped implementation — so a tool never recurses into
itself.

Tools: ``install(interceptor)`` wraps the entry points; an interceptor is
``fn(name, call, args, kwargs) -> result`` where ``args[0]`` is the comm
the method was invoked on. Continue the chain (the next tool, ending at
the real implementation) with ``call(*args[1:], **kwargs)`` — ``call`` is
already bound to the comm. ``Profiler`` is a ready-made mpiP-style timing
tool.
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict
from types import MethodType
from typing import Callable, Dict, List, Tuple

from .core.comm import Comm

# the interposable surface: the MPI_* entry points tools care about
PROFILED_METHODS = [
    "send", "recv", "isend", "irecv", "ssend", "bsend", "sendrecv",
    "probe", "iprobe",
    "barrier", "bcast", "reduce", "allreduce", "allgather", "gather",
    "scatter", "alltoall", "reduce_scatter_block", "scan", "exscan",
    "ibarrier", "ibcast", "iallreduce", "iallgather", "ialltoall",
    "ireduce", "iscan", "iexscan", "igather", "iscatter",
    "igatherv", "iscatterv", "iallgatherv", "ialltoallv",
    "ireduce_scatter", "ireduce_scatter_block",
]

_lock = threading.Lock()
_interceptors: List[Callable] = []
# what a wrapped call walks, last installed first: ``_interceptors`` as
# a tuple, made anew under ``_lock`` by install and uninstall, so a call
# copies nothing
_chain: Tuple[Callable, ...] = ()
_originals: Dict[str, Callable] = {}     # the PMPI_* table
_installed = False


def pmpi(name: str) -> Callable:
    """The PMPI_* escape hatch: the unwrapped Comm method (unbound)."""
    return _originals.get(name) or getattr(Comm, name)


def _make_wrapper(name: str, real: Callable) -> Callable:
    def wrapper(self, *args, **kwargs):
        tools = _chain
        if len(tools) == 1:
            # one tool (the recorder's, as a rule): its ``call`` is the
            # implementation bound to the comm, nothing built per call
            return tools[0](name, MethodType(real, self), (self,) + args,
                            kwargs)
        if not tools:
            return real(self, *args, **kwargs)
        left = len(tools) - 1      # several: last installed is outermost

        def call(*a, **kw):
            nonlocal left
            if left:
                left -= 1
                return tools[left](name, call, (self,) + a, kw)
            return real(self, *a, **kw)

        return tools[left](name, call, (self,) + args, kwargs)

    wrapper.__name__ = name
    wrapper.__wrapped__ = real
    return wrapper


def install(interceptor: Callable) -> None:
    """Register a tool interceptor (outermost-first, like LD_PRELOAD
    layering of PMPI tools)."""
    global _installed, _chain
    with _lock:
        if not _installed:
            for name in PROFILED_METHODS:
                real = getattr(Comm, name, None)
                if real is None:
                    continue
                _originals[name] = real
                setattr(Comm, name, _make_wrapper(name, real))
            _installed = True
        _interceptors.append(interceptor)
        _chain = tuple(_interceptors)


def uninstall(interceptor: Callable = None) -> None:
    """Remove one interceptor (or all); restore the raw table when the
    last tool leaves."""
    global _installed, _chain
    with _lock:
        if interceptor is None:
            _interceptors.clear()
        elif interceptor in _interceptors:
            _interceptors.remove(interceptor)
        _chain = tuple(_interceptors)
        if not _interceptors and _installed:
            for name, real in _originals.items():
                setattr(Comm, name, real)
            _originals.clear()
            _installed = False


class Profiler:
    """mpiP-style aggregate profiler: per-function call counts, total
    time, and bytes (when inferable). Use as a context manager."""

    def __init__(self):
        self.calls: Dict[str, int] = defaultdict(int)
        self.seconds: Dict[str, float] = defaultdict(float)
        self._lock = threading.Lock()

    def _tool(self, name, call, args, kwargs):
        t0 = time.perf_counter()
        try:
            return call(*args[1:], **kwargs)
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self.calls[name] += 1
                self.seconds[name] += dt

    def __enter__(self):
        install(self._tool)
        return self

    def __exit__(self, *exc):
        uninstall(self._tool)
        return False

    def report(self) -> str:
        lines = ["# MPI function profile (mpiP-style)",
                 f"# {'function':<24} {'calls':>8} {'time(s)':>12}"]
        for name in sorted(self.calls, key=lambda n: -self.seconds[n]):
            lines.append(f"  {name:<24} {self.calls[name]:>8} "
                         f"{self.seconds[name]:>12.6f}")
        return "\n".join(lines)
