"""Lazy result handles for dispatched-but-unread device work.

The double-buffering primitive shared by the serving paths
(``Sentinel.entry_batch_nowait`` / ``ClusterEngine.request_tokens_nowait``):
the device step is dispatched (engine state already advanced in order) and
the device→host transfer started async; :meth:`PendingResult.result`
materializes. Holding a handle while dispatching the next batch overlaps the
readback — the dominant per-batch cost on a remote-attached device — with
the next batch's host prep.
"""

from __future__ import annotations


class _Cell:
    """Shared settle state for a :class:`PendingResult`.

    Split out of the handle so a GC finalizer can settle a leaked handle
    without resurrecting it: the finalizer closes over the cell, and a
    handle whose cell was already settled by the finalizer still returns
    the cached result from :meth:`settle`.
    """

    __slots__ = ("fn", "done", "res")

    def __init__(self, fn):
        self.fn = fn
        self.done = False
        self.res = None

    def settle(self):
        if not self.done:
            self.res = self.fn()
            self.done = True
            self.fn = None
        return self.res


class PendingResult:
    """Memoizing one-shot handle: ``result()`` runs the deferred
    materialization exactly once and returns the cached value after."""

    __slots__ = ("_cell", "__weakref__")

    def __init__(self, fn):
        self._cell = _Cell(fn)

    def result(self):
        return self._cell.settle()


def start_host_copy(arrays) -> None:
    """Kick off async device→host copies so a later ``np.asarray`` finds
    the data already (or nearly) resident instead of paying the full
    transfer at materialization time."""
    for a in arrays:
        a.copy_to_host_async()
