"""Cluster membership: join/leave/heartbeat + nodedown notifications.

Reference analog: ekka — autocluster discovery, membership gossip, and
`ekka:monitor(membership)` subscriptions that the router helper uses to
purge a dead node's routes (emqx_router_helper.erl:96,135-148) and the
machine boot uses for autocluster (emqx_machine_boot.erl:46-51).

Failure detection here is heartbeat-deadline based (the BEAM uses
distribution-link breaks); the test nemesis advances a logical clock to
force timeouts deterministically, mirroring snabbkaffe-style scheduling
control rather than wall-clock sleeps.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional

from emqx_tpu.cluster.transport import LocalBus

MembershipCallback = Callable[[str, str], None]  # (event, node)

HEARTBEAT_INTERVAL = 1.0
# A node under load stops the world for seconds at a time (a full GC pass
# over a million subscriptions takes 3-4 s, PERF.md): no thread of it acks
# a heartbeat meanwhile. Declaring it down purges its routes from every
# replica, so the timeout has to outlast what a live node does. (The
# reference's detector, the distribution's net_ticktime, defaults to 60 s.)
FAILURE_TIMEOUT = 10.0


class Membership:
    """One node's view of the cluster, with pluggable clock for tests."""

    def __init__(
        self,
        node: str,
        bus: LocalBus,
        clock: Optional[Callable[[], float]] = None,
    ) -> None:
        self.node = node
        self._bus = bus
        self._clock = clock or time.monotonic
        self._lock = threading.Lock()
        self._last_seen: Dict[str, float] = {}  # guarded-by: _lock
        self._alive: Dict[str, bool] = {node: True}  # guarded-by: _lock
        self._callbacks: List[MembershipCallback] = []
        # when `expire` last ran, on the real clock: a gap says THIS
        # process stood still, and its peers' acks with it
        self._expired_at: Optional[float] = None  # guarded-by: _lock

    # -- ekka:monitor(membership) parity ----------------------------------
    def monitor(self, callback: MembershipCallback) -> None:
        self._callbacks.append(callback)

    def _emit(self, event: str, node: str) -> None:
        for cb in list(self._callbacks):
            cb(event, node)

    # -- cluster ops -------------------------------------------------------
    def join(self, seed: str) -> bool:
        """Join the cluster known to `seed` (ekka:join parity)."""
        try:
            peers = self._bus.send(
                self.node, seed, ("membership", "join", self.node)
            )
        except Exception:
            return False
        now = self._clock()
        with self._lock:
            for p in peers:
                if p != self.node and not self._alive.get(p):
                    self._alive[p] = True
                    self._last_seen[p] = now
        for p in peers:
            if p != self.node:
                self._emit("node_up", p)
        return True

    def handle(self, from_node: str, msg) -> object:
        kind = msg[1]
        now = self._clock()
        if kind == "join":
            joiner = msg[2]
            newly = False
            with self._lock:
                if not self._alive.get(joiner):
                    self._alive[joiner] = True
                    newly = True
                self._last_seen[joiner] = now
                view = [n for n, up in self._alive.items() if up]
            if newly:
                self._emit("node_up", joiner)
                # gossip the join to the rest of the cluster
                for p in view:
                    if p not in (self.node, joiner):
                        self._bus.cast(
                            self.node, p, ("membership", "join", joiner)
                        )
            return view
        if kind in ("heartbeat", "heartbeat_ack"):
            with self._lock:
                came_back = not self._alive.get(from_node)
                self._alive[from_node] = True
                self._last_seen[from_node] = now
            if came_back:
                self._emit("node_up", from_node)
            if kind == "heartbeat":
                # receipt-confirmed liveness: the sender learns we are
                # alive from this ack ARRIVING, never from its own send
                # buffer accepting bytes (see heartbeat() below)
                self._bus.cast(
                    self.node, from_node, ("membership", "heartbeat_ack")
                )
            return True
        if kind == "leave":
            with self._lock:
                was_up = self._alive.pop(from_node, False)
                self._last_seen.pop(from_node, None)
            if was_up:
                self._emit("node_down", from_node)
            return True
        return None

    def leave(self) -> None:
        """Graceful leave: notify peers (ekka:leave parity)."""
        for p in self.peers():
            self._bus.cast(self.node, p, ("membership", "leave"))

    def heartbeat(self) -> None:
        """Send one heartbeat round + expire dead peers. Called on a timer.

        `_last_seen` refreshes ONLY when the peer's ack (or any inbound
        membership traffic) arrives — never on the outbound cast
        "succeeding". Over TCP a `sendall` to a freshly-killed peer
        happily buffers in the kernel (the RST comes later), so
        send-side success is evidence about OUR socket, not the peer;
        trusting it kept kill -9'd nodes alive past FAILURE_TIMEOUT
        whenever the connection reader hadn't yet noticed the close
        (the cluster-proc flake this line exists to pin)."""
        for p in self.peers():
            self._bus.cast(self.node, p, ("membership", "heartbeat"))
        self.expire()

    def expire(self) -> None:
        now = self._clock()
        real = time.monotonic()
        downs = []
        with self._lock:
            stood = 0.0
            if self._expired_at is not None and self._clock is time.monotonic:
                # (a test's logical clock stands outside real time.) Called
                # every HEARTBEAT_INTERVAL on a live app: what is over that
                # was this process stopped (a GC pass, a starved thread),
                # when no ack could be read: no evidence against a peer
                stood = max(
                    0.0, real - self._expired_at - 2 * HEARTBEAT_INTERVAL
                )
            self._expired_at = real
            if stood:
                for p in self._last_seen:
                    self._last_seen[p] += stood
            for p, seen in list(self._last_seen.items()):
                if self._alive.get(p) and now - seen > FAILURE_TIMEOUT:
                    self._alive[p] = False
                    downs.append(p)
        for p in downs:
            self._emit("node_down", p)

    # -- views -------------------------------------------------------------
    def peers(self) -> List[str]:
        with self._lock:
            return sorted(
                n for n, up in self._alive.items() if up and n != self.node
            )

    def running_nodes(self) -> List[str]:
        with self._lock:
            return sorted(n for n, up in self._alive.items() if up)

    def is_alive(self, node: str) -> bool:
        with self._lock:
            return bool(self._alive.get(node))
