"""The collector policy of the device-owner process.

A broker's heap is two populations. The table (subscriptions, sessions,
routes, retained messages: ~10 collector-tracked objects a subscription,
10^7 at a million) is loaded once and lives for days. The in-flight set
(messages, futures, packets, one ingest batch: ~10^5 objects) turns over
several times a second. CPython's defaults serve neither: a young pass
every 700 net allocations promotes every message that lives through one
round trip, and the promotions reach a quarter of the table's heap every
~20 s, which starts a full pass that visits all of it (3.7 s at 10^7) and
finds nothing, because the table is not garbage.

The policy has no setting. It acts on what the process can observe:

1. *Growth freezes.* When the housekeeping tick sees that items arrived
   since the last freeze, it collects the young generations (at most one
   tick's allocations) and `gc.freeze()`s: the long-lived heap leaves the
   generations, and no later pass visits it. A static table freezes once;
   during a load that is once a tick. Frozen objects still die by
   reference count.
2. *Thresholds fit the in-flight set* (`THRESHOLDS`), so a young pass is
   started by net growth, not by one burst, and with (1) every pass of any
   generation visits only what arrived since the last freeze.
3. *Releases thaw.* A cycle among frozen objects is never reclaimed, so
   the teardown paths break the cycles they know of
   (`Connection.run`, `ChannelManager`), and the tick counts the items
   released while frozen. Past a quarter of the items that were frozen
   (CPython's own rule, counted in what can be garbage instead of what was
   allocated) one thaw pass runs: unfreeze, full collection, freeze.

No timer, and the collector is never switched off: every trigger is a count.
"""

from __future__ import annotations

import gc

# Generation 0: above what the in-flight set swings by, so that a burst
# (48 publishers' pipelines of 100, one 2,300-row ingest batch and its
# deliveries: some 10^5 tracked objects) starts no pass by itself.
# Generations 1 and 2: a pass of generation 1 visits at most the survivors
# of 1 + 4 young passes, 0.5M objects (~0.2 s at the 0.3-0.4 us an object
# a pass costs); generation 2 holds what outlived those since the last
# freeze, and is looked at every fifth pass of generation 1.
THRESHOLDS = (100_000, 4, 4)

# A thaw pass visits the whole frozen heap, the interpreter's and the
# libraries' own ~0.5M objects at the least: under this many releases it
# costs more than they can hold.
THAW_MIN_RELEASED = 1_000

# the process has one collector: how many policies are installed, and what
# the first of them found (tier-1 runs several apps in one process)
_installed = 0
_found = (700, 10, 10)


class GcPolicy:
    """One per app; `install` at start, `tick` from the 1 Hz
    housekeeping, `restore` at stop."""

    def __init__(self, metrics) -> None:
        self.metrics = metrics
        self.installed = False
        self.frozen_objects = 0  # moved out of the generations by `tick`
        # as of the last freeze or thaw: the items alive (what the freeze
        # holds, and what a quarter is taken of) and those ever released
        self._live = 0
        self._released = 0
        self._released_at_thaw = 0

    def install(self) -> None:
        global _installed, _found
        if self.installed:
            return
        self.installed = True
        if _installed == 0:
            _found = gc.get_threshold()
            gc.set_threshold(*THRESHOLDS)
        _installed += 1

    def restore(self) -> None:
        """Leave the collector as `install` found it: the last policy of
        the process puts the thresholds back and unfreezes."""
        global _installed
        if not self.installed:
            return
        self.installed = False
        _installed -= 1
        if _installed == 0:
            gc.set_threshold(*_found)
            gc.unfreeze()
        self.frozen_objects = 0

    def tick(self, live: int, released: int) -> None:
        """`live`: the items the long-lived heap holds now (subscriptions,
        sessions, routes, retained messages); `released`: how many such
        items were ever released (monotonic)."""
        if not self.installed:
            return
        arrived = (live - self._live) + (released - self._released)
        thaw = released - self._released_at_thaw > max(
            self._live // 4, THAW_MIN_RELEASED
        )
        if thaw:
            gc.unfreeze()
            gc.collect()
            gc.freeze()
            self.frozen_objects = gc.get_freeze_count()
            self._released_at_thaw = released
            self.metrics.inc("owner.gc.thaws")
        elif arrived > 0:
            gc.collect(1)
            # what the freeze moves; `gc.get_freeze_count()` would walk the
            # whole frozen heap (0.25 s at 10^7 objects), this walks what
            # arrived since the last freeze
            self.frozen_objects += len(gc.get_objects())
            gc.freeze()
            self.metrics.inc("owner.gc.freezes")
        if thaw or arrived > 0:
            self._live, self._released = live, released
        self.metrics.gauge_set("owner.gc.frozen.objects", self.frozen_objects)
