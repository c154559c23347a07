"""Lockstep primitives for threads that share one device program.

* :class:`LockstepBatcher` — threads funnel requests into waves: each
  :meth:`~LockstepBatcher.submit` blocks until every active thread has
  submitted (or finished), and the last arrival flushes the whole wave
  through one call. The ``device`` executor batches its cells' solves with
  it, and ``serve.FleetLoop`` its fleets'.
* :class:`Baton` — threads take turns: one runs at a time, in a fixed
  order, so a wave's host work stays serial, with no contention for the
  interpreter lock.
"""
from __future__ import annotations

import threading
from typing import List, Optional


class LockstepBatcher:
    """Lockstep cross-thread request batcher.

    Every participating thread funnels each request here (a ``fused``
    solve, via :func:`repro.core.solvers.intercepted`); :meth:`submit`
    blocks until the whole wave's requests are flushed as one batch
    (``flush_fn``, list of requests in, list of results out, in order) and
    the caller's result is back.

    Liveness invariant: a flush fires exactly when every *active* thread
    is blocked in :meth:`submit` — the last arrival executes the flush.
    Every thread is registered (:meth:`register`) before any of them can
    submit, and a thread that will submit nothing more MUST
    :meth:`finish` (in a ``finally``), which both removes it from the
    barrier arithmetic and flushes any wave it was holding up. Threads
    make different numbers of requests: late waves simply batch across
    whichever threads are still running, down to single-request "batches"
    for the last one standing.

    A flush exception fans out to every waiting ``submit`` (re-raised in
    each thread); the batcher itself stays usable for the survivors.
    """

    def __init__(self, flush_fn):
        self._flush_fn = flush_fn
        self._cv = threading.Condition()
        self._active = 0
        self._pending: List[list] = []      # [request, result, exception]

    def register(self) -> None:
        with self._cv:
            self._active += 1

    def finish(self) -> None:
        with self._cv:
            self._active -= 1
            self._maybe_flush()

    def submit(self, request):
        item = [request, None, None]
        with self._cv:
            self._pending.append(item)
            self._maybe_flush()
            while item[1] is None and item[2] is None:
                self._cv.wait()
        if item[2] is not None:
            raise item[2]
        return item[1]

    def _maybe_flush(self) -> None:
        # Caller holds the lock. Every active thread pending -> flush now.
        # (The non-submitting threads are all inside submit(), waiting, so
        # holding the lock across the flush serializes nothing that could
        # otherwise run.)
        if not self._pending or len(self._pending) < self._active:
            return
        batch, self._pending = self._pending, []
        try:
            results = self._flush_fn([it[0] for it in batch])
            for it, res in zip(batch, results):
                it[1] = res
        except BaseException as e:          # noqa: BLE001 — fan out
            for it in batch:
                it[2] = e
        self._cv.notify_all()


class Baton:
    """Turns among ``n`` parties, 0 to ``n - 1``: party ``i`` runs only
    between :meth:`wait` returning and its next :meth:`pass_on` or
    :meth:`leave`. Party 0 holds the baton first; passing it hands it to
    the next party in cyclic order that has not left."""

    def __init__(self, n: int):
        self._cv = threading.Condition()
        self._left = [False] * n
        self._turn: Optional[int] = 0 if n else None

    def wait(self, i: int) -> None:
        """Block until party ``i`` holds the baton."""
        with self._cv:
            while self._turn != i:
                self._cv.wait()

    def pass_on(self, i: int) -> None:
        """Party ``i`` hands the baton on and stays in the turn."""
        with self._cv:
            self._advance(i)

    def leave(self, i: int) -> None:
        """Party ``i`` hands the baton on and takes no further turn."""
        with self._cv:
            self._left[i] = True
            self._advance(i)

    def _advance(self, i: int) -> None:
        n = len(self._left)
        self._turn = next((j % n for j in range(i + 1, i + n + 1)
                           if not self._left[j % n]), None)
        self._cv.notify_all()
