"""Request objects (ch3u_request.c analog).

A Request is a completion promise tied to a rank's progress engine. Blocking
waits funnel into the engine's ``progress_wait`` (SURVEY §3.5) — the engine
polls its channels and sleeps on a condition variable that any completing
thread signals. Completion callbacks chain protocol state machines
(rendezvous CTS -> data -> FIN) and the nonblocking-collective scheduler.
"""

from __future__ import annotations

import threading
from typing import Callable, List, Optional

from .errors import MPIException, MPI_SUCCESS, MPI_ERR_REQUEST
from .status import Status

REQUEST_NULL = None


class Request:
    _ids = iter(range(1, 1 << 62))
    # a receive whose buffer was a jax.Array (a description: a device
    # array cannot be written into): the received device array, once
    # the request is complete; None before, for MPI_PROC_NULL and for
    # every other kind of request
    array = None

    def __init__(self, engine=None, kind: str = "generic"):
        self.engine = engine          # progress engine that completes me
        self.kind = kind
        self.status = Status()
        self.complete_flag = False
        self.error: Optional[MPIException] = None
        self.cancelled = False
        self._callbacks: List[Callable] = []
        self.persistent = False
        self._start_fn: Optional[Callable] = None  # for persistent requests
        self.req_id = next(Request._ids)

    # -- completion (called with engine lock held or from engine.complete) --
    def add_callback(self, cb: Callable) -> None:
        if self.complete_flag:
            cb(self)
        else:
            self._callbacks.append(cb)

    def _fire(self) -> None:
        self.complete_flag = True
        cbs, self._callbacks = self._callbacks, []
        for cb in cbs:
            cb(self)

    def complete(self, error: Optional[MPIException] = None) -> None:
        """Thread-safe completion via the owning engine."""
        if error is not None:
            self.error = error
            self.status.error = error.error_class
        if self.engine is not None:
            self.engine.complete_request(self)
        else:
            self._fire()

    # -- user-facing ------------------------------------------------------
    def test(self) -> bool:
        if not self.complete_flag and self.engine is not None:
            self.engine.progress_poke()
        return self.complete_flag

    def wait(self) -> Status:
        # already-complete fast path (every eager send): complete_flag
        # only ever transitions False->True, so an unlocked read that
        # sees True is safe and skips the progress mutex+poll
        if not self.complete_flag:
            if self.engine is not None:
                self.engine.progress_wait(lambda: self.complete_flag)
            else:
                raise MPIException(MPI_ERR_REQUEST,
                                   "wait on engine-less incomplete request")
        if self.error is not None:
            raise self.error
        return self.status

    def cancel(self) -> None:
        # Recv cancel = matching-queue removal; send cancel resolves
        # asynchronously through the protocol (see pt2pt/protocol.py).
        # _cancel_override marks requests (persistent sends) whose
        # local completion does not preclude cancelling.
        if self.complete_flag and not getattr(self, "_cancel_override",
                                              False):
            return
        canceller = getattr(self, "_cancel_fn", None)
        if canceller is not None and canceller():
            self.cancelled = True
            self.status.cancelled = True
            self.complete()

    def free(self) -> None:
        pass

    # -- persistent requests (MPI_Send_init / MPI_Start) ------------------
    def start(self) -> None:
        if not self.persistent or self._start_fn is None:
            raise MPIException(MPI_ERR_REQUEST, "not a persistent request")
        self.complete_flag = False
        self.status = Status()
        self._start_fn(self)

    def __repr__(self):
        return (f"Request({self.kind}, id={self.req_id}, "
                f"{'done' if self.complete_flag else 'pending'})")


class CompletedRequest(Request):
    """Immediately-complete request (e.g. self-send fast path, 0-byte ops)."""

    def __init__(self, status: Optional[Status] = None):
        super().__init__(None, "completed")
        if status is not None:
            self.status = status
        self.complete_flag = True


def waitall(requests: List[Optional[Request]]) -> List[Status]:
    stats = []
    for r in requests:
        stats.append(r.wait() if r is not None else Status())
    return stats


def waitany(requests: List[Optional[Request]]) -> int:
    """Returns index of a completed request; progresses until one completes."""
    live = [(i, r) for i, r in enumerate(requests) if r is not None]
    if not live:
        return -1
    engine = next((r.engine for _, r in live if r.engine is not None), None)

    def any_done():
        return any(r.complete_flag for _, r in live)

    if engine is not None:
        engine.progress_wait(any_done)
    for i, r in live:
        if r.complete_flag:
            if r.error is not None:
                raise r.error
            return i
    raise MPIException(MPI_ERR_REQUEST, "waitany: nothing completed")


def testall(requests: List[Optional[Request]]) -> bool:
    return all(r is None or r.test() for r in requests)


def testany(requests: List[Optional[Request]]):
    """(index, flag): first completed request's index, or (-1, False)."""
    for i, r in enumerate(requests):
        if r is not None and r.test():
            if r.error is not None:
                raise r.error
            return i, True
    return -1, False


def waitsome(requests: List[Optional[Request]]) -> List[int]:
    """Indices of all completed requests after at least one completes."""
    first = waitany(requests)
    if first < 0:
        return []
    out = []
    for i, r in enumerate(requests):
        if r is not None and r.complete_flag:
            if r.error is not None:
                raise r.error
            out.append(i)
    return out


def testsome(requests: List[Optional[Request]]) -> List[int]:
    out = []
    for i, r in enumerate(requests):
        if r is not None and r.test():
            if r.error is not None:
                raise r.error
            out.append(i)
    return out


class Grequest(Request):
    """Generalized request (MPI-3.1 §12.2, MPI_Grequest_start analog).

    The application completes it via ``complete()``; ``query_fn(status)``
    fills the status when the request is inspected at completion;
    ``free_fn``/``cancel_fn`` hook teardown and cancellation."""

    def __init__(self, engine, query_fn=None, free_fn=None,
                 cancel_fn=None):
        super().__init__(engine, "grequest")
        self._query_fn = query_fn
        self._free_fn = free_fn
        self._user_cancel_fn = cancel_fn
        if engine is not None:
            with engine.mutex:
                engine.track(self)

    def complete(self, error=None) -> None:  # MPI_Grequest_complete
        if self._query_fn is not None:
            self._query_fn(self.status)
        super().complete(error)

    def cancel(self) -> None:
        # MPI-3.1 §12.2: cancel_fn is invoked unconditionally, with
        # complete=true when the request has already completed (the
        # cancel then has no effect on the request's state)
        if self.complete_flag:
            if self._user_cancel_fn is not None:
                self._user_cancel_fn(True)
            return
        if self._user_cancel_fn is not None:
            self._user_cancel_fn(False)
        self.cancelled = True
        self.status.cancelled = True
        super().complete(None)

    def free(self) -> None:
        if self._free_fn is not None:
            self._free_fn()


def grequest_start(query_fn=None, free_fn=None, cancel_fn=None) -> Grequest:
    from ..runtime.universe import current_universe
    u = current_universe()
    return Grequest(u.engine if u is not None else None, query_fn,
                    free_fn, cancel_fn)
