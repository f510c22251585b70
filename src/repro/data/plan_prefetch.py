"""Async host-side plan generation (DESIGN.md §1 step 4).

Epoch plans are tiny ``(seed, epoch)``-keyed index/weight arrays built
with numpy on the host (``data/pipeline.epoch_plan`` /
``subset_epoch_plan`` behind ``EpochEngine.full_plan`` /
``subset_plan``).  Building them synchronously between epoch dispatches
puts that (cheap but serial) host work — plus its ``device_put`` — on
the critical path.  ``PlanPrefetcher`` double-buffers upcoming plans on
a single worker thread so they build and transfer while the current
epoch chunk executes on device.

Determinism is free: plan builders are pure functions of
``(seed, epoch, selection)``, so a prefetched plan is bit-identical to
one built synchronously, and a resumed run — which starts with an empty
prefetch buffer — rebuilds exactly the plans the interrupted run would
have used (asserted by ``tests/test_sharded_engine.py``).

Keys are caller-chosen hashables (the training loop uses
``("full", epoch)`` / ``("subset", selection_round, epoch)``): a new
selection round changes the key, so a superseded plan can never be
served.  A key that will no longer be fetched still occupies a buffer
slot, so callers that re-key (the loop, after each selection round)
should call ``invalidate()`` to drop pending work — otherwise orphans
accumulate until the buffer is permanently full.

Failure semantics: a *transient* builder failure (flaky storage, an
injected chaos fault) is retried in place — ``retries`` attempts with
capped exponential backoff — on whichever thread runs the build, the
worker or the ``get()`` fallback, so both paths degrade identically
(DESIGN.md §10).  A builder that keeps failing must not strand the
consumer or leak the thread: ``get()`` re-raises the final exception at
the consumer (and frees the buffer slot, so the caller can retry
synchronously); an *orphaned* failed build is simply dropped by
``invalidate()``; ``close()`` — also run by ``__del__`` and the context
manager — cancels what hasn't started and joins the worker thread, and
is idempotent.
"""
from __future__ import annotations

import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Dict, Hashable

from repro import obs


class PlanPrefetcher:
    """Single-worker double buffer for plan construction.

    ``schedule(key, build)`` submits ``build`` (no-arg, returns the plan
    — typically already ``device_put``) to the worker thread; at most
    ``max_pending`` submissions are outstanding so a long horizon cannot
    pile up host memory.  ``get(key, build)`` returns the prefetched
    result when ``key`` was scheduled, else falls back to calling
    ``build`` synchronously — the two paths return identical values
    because builders are pure.  A prefetched build that *failed*
    re-raises its exception from ``get()``.
    """

    def __init__(self, max_pending: int = 2, retries: int = 2,
                 backoff_s: float = 0.05, max_backoff_s: float = 2.0):
        self.max_pending = int(max_pending)
        self.retries = int(retries)
        self.backoff_s = float(backoff_s)
        self.max_backoff_s = float(max_backoff_s)
        self._pending: Dict[Hashable, Future] = {}
        self._ex = ThreadPoolExecutor(max_workers=1,
                                      thread_name_prefix="plan-prefetch")
        self._closed = False
        #: observability: get() calls served from the buffer / built
        #: synchronously, and builds recovered by a retry (used by tests
        #: and the benchmark harness)
        self.hits = 0
        self.misses = 0
        self.retried = 0

    def _build_with_retries(self, build: Callable[[], object]):
        """Run ``build``, retrying transient failures ``retries`` times
        with capped exponential backoff before letting the exception
        propagate.  Builders are pure, so a retry returns exactly the
        plan a clean first attempt would have."""
        delay = self.backoff_s
        for attempt in range(self.retries + 1):
            try:
                return build()
            except Exception:
                if attempt == self.retries:
                    raise
                self.retried += 1
                time.sleep(delay)
                delay = min(delay * 2, self.max_backoff_s)

    def schedule(self, key: Hashable, build: Callable[[], object]) -> bool:
        """Queue ``build`` for ``key``.  Idempotent: an already-scheduled
        key reports True (so a caller topping up a look-ahead window can
        keep walking forward past keys it queued earlier); returns False
        only when closed or the buffer is full."""
        if key in self._pending:
            return True
        if self._closed or len(self._pending) >= self.max_pending:
            return False
        self._pending[key] = self._ex.submit(self._build_with_retries,
                                             build)
        return True

    def get(self, key: Hashable, build: Callable[[], object]):
        """The plan for ``key`` — from the buffer when prefetched, else
        built synchronously.  A builder exception raised on the worker
        thread propagates here, to the consumer that asked for the key
        (the slot is freed first, so retrying falls back to a
        synchronous ``build``).  The wait is the ``repro.prefetch.wait``
        span (``repro.obs``)."""
        fut = self._pending.pop(key, None)
        with obs.span("prefetch.wait", hit=fut is not None):
            if fut is None:
                self.misses += 1
                return self._build_with_retries(build)
            self.hits += 1
            return fut.result()    # re-raises the worker's exception

    def invalidate(self):
        """Drop every pending entry (cancelling what hasn't started):
        call when the keys change — e.g. a new selection round — so
        superseded plans don't pin buffer slots or device memory.  A
        dropped entry's result (or exception) is deliberately discarded."""
        for fut in self._pending.values():
            fut.cancel()
        self._pending.clear()

    def close(self):
        """Cancel anything not yet running, drain pending state and join
        the worker thread.  Idempotent; also invoked by ``__del__`` so a
        prefetcher dropped without an explicit ``close()`` (e.g. when
        the training loop dies mid-epoch) still releases its thread."""
        if self._closed:
            return
        self._closed = True
        self.invalidate()
        self._ex.shutdown(wait=True)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:       # interpreter teardown: best effort
            pass
