"""Two-point-slope device-op timing, shared by autotune and
benchmarks/acceptance.py.

A single timed call of a short device op measures mostly the constant
cost of the call — dispatch, and the host readback that ends it.
Instead: run the op K1 times and K2 times inside one jitted program
(forcing one scalar readback each), then
``t_op = (T(K2) - T(K1)) / (K2 - K1)`` — the constant overhead cancels.
Each T is min-of-iters (constant overhead + positive noise); the slope
is a median over ``nrep`` repeats.
"""

from __future__ import annotations

import functools
import time
from typing import Callable


def timed_min(fn_k: Callable, x, k: int, iters: int = 12,
              skip: int = 3) -> float:
    for _ in range(skip):
        float(fn_k(x, k))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        float(fn_k(x, k))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def slope(fn_k: Callable, x, k1: int = 4, k2: int = 16, iters: int = 12,
          skip: int = 3, nrep: int = 5) -> float:
    ss = []
    for _ in range(nrep):
        t1 = timed_min(fn_k, x, k1, iters, skip)
        t2 = timed_min(fn_k, x, k2, iters, skip)
        ss.append(max((t2 - t1) / (k2 - k1), 1e-9))
    ss.sort()
    return ss[len(ss) // 2]


def wrap_repeat(op: Callable, chains: bool) -> Callable:
    """``fn_k(x, k)``: K dependent executions of ``op`` in one jitted
    program with a scalar readback. ``chains=True`` feeds each output
    into the next call (op must be shape-preserving); ``chains=False``
    repeats the op on the same input and folds a scalar from each
    output into the result — the op must be marked effectful (e.g.
    pallas has_side_effects) or XLA CSE collapses the repeats."""
    import jax
    import jax.numpy as jnp

    if chains:
        @functools.partial(jax.jit, static_argnums=1)
        def fn_k(v, k):
            a = v
            for _ in range(k):
                a = op(a)
            return jnp.sum(a.reshape(-1)[:64])
    else:
        @functools.partial(jax.jit, static_argnums=1)
        def fn_k(v, k):
            acc = jnp.float32(0)
            for _ in range(k):
                acc = acc + op(v).reshape(-1)[0]
            return acc
    return fn_k
