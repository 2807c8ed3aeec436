"""One socket write per connection per batch: the per-event-loop half.

An in-process `Connection` appends what it serialises to its own pending
list and registers here; the program's batch boundaries (the end of a read
chunk, of a settled batch's fan-out, of the ack drainer's resolved run)
flush it, and whatever no boundary covers (ticks, retries, replays, kicks,
forwards) goes out with the `call_soon` scheduled by the turn's first
append, as `WorkerFabric._flush` does for the pool's connections
(docs/protocol_plane.md "The in-process sink").
"""

from __future__ import annotations

import asyncio
import weakref


class LoopEgress:
    """The connections of one event loop with unwritten output."""

    __slots__ = ("_loop", "dirty", "_scheduled")

    def __init__(self, loop):
        # weak: the registry's value must not keep its key (the loop) alive
        self._loop = weakref.ref(loop)
        self.dirty: set = set()
        self._scheduled = False

    def mark(self, conn) -> None:
        self.dirty.add(conn)
        if not self._scheduled:
            self._scheduled = True
            self._loop().call_soon(self._end_of_turn)

    def _end_of_turn(self) -> None:
        self._scheduled = False
        self.flush()

    def flush(self) -> None:
        """Every dirty connection, one write each."""
        while self.dirty:
            dirty, self.dirty = self.dirty, set()
            for conn in dirty:
                conn.flush()


_by_loop: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def of(loop) -> LoopEgress:
    eg = _by_loop.get(loop)
    if eg is None:
        eg = _by_loop[loop] = LoopEgress(loop)
    return eg


def flush_dirty() -> None:
    """A batch boundary of the caller's loop: the end of a settled batch's
    fan-out. Every connection the batch touched is written once."""
    loop = asyncio._get_running_loop()
    if loop is not None:
        eg = _by_loop.get(loop)
        if eg is not None:
            eg.flush()
