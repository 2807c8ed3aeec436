"""Cluster message transport with keyed ordered channels.

Reference analog: gen_rpc's multi-channel TCP — the data plane picks a
stable channel per topic so per-topic message order is preserved across
nodes while unrelated topics flow in parallel (emqx_rpc.erl:66-80,
`emqx_broker.erl:278-293` forwards keyed by topic).

`LocalBus` is the in-process implementation used by the multi-node test
harness (the analog of the reference's slave-node CT setup,
emqx_router_helper_SUITE.erl:61) and by single-host multi-worker runs.
A TCP implementation can drop in behind the same interface; the RPC and
replication layers only see `send(to_node, channel_key, payload)`.

Delivery model: per (src, dst, channel) FIFO. A partitioned/stopped node
raises NodeUnreachable on send, mirroring gen_rpc's {badtcp,...} errors.
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Dict, Optional, Tuple

Handler = Callable[[str, object], Optional[object]]  # (from_node, payload)


class NodeUnreachable(Exception):
    pass


class LocalBus:
    """In-process cluster fabric: registry of node inboxes + partitions."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._handlers: Dict[str, Handler] = {}
        # simulated partitions: set of (a, b) unordered pairs that cannot talk
        self._cut: set[Tuple[str, str]] = set()

    def attach(self, node: str, handler: Handler) -> None:
        with self._lock:
            self._handlers[node] = handler

    def detach(self, node: str) -> None:
        with self._lock:
            self._handlers.pop(node, None)

    def nodes(self) -> list[str]:
        with self._lock:
            return sorted(self._handlers)

    # -- fault injection (test nemesis; reference: docker node kill in FVT) --
    def partition(self, a: str, b: str) -> None:
        with self._lock:
            self._cut.add((min(a, b), max(a, b)))

    def heal(self, a: str, b: str) -> None:
        with self._lock:
            self._cut.discard((min(a, b), max(a, b)))

    def reachable(self, a: str, b: str) -> bool:
        with self._lock:
            return (
                b in self._handlers and (min(a, b), max(a, b)) not in self._cut
            )

    # -- send paths --------------------------------------------------------
    def send(
        self, src: str, dst: str, payload: object, channel_key="",
        patient=None,
    ) -> object:
        """Synchronous request/response (gen_rpc call). Returns handler
        result. `channel_key` / `patient` are the TCP bus's (which socket;
        how long to wait for a live peer): in process the call is direct."""
        with self._lock:
            handler = self._handlers.get(dst)
            cut = (min(src, dst), max(src, dst)) in self._cut
        if handler is None or cut:
            raise NodeUnreachable(f"{src} -> {dst}")
        return handler(src, payload)

    def cast(self, src: str, dst: str, payload: object) -> bool:
        """Fire-and-forget (gen_rpc cast): delivery not guaranteed on cut."""
        try:
            self.send(src, dst, payload)
            return True
        except NodeUnreachable:
            return False


class ChannelPool:
    """Stable key→channel mapping preserving per-key FIFO order.

    gen_rpc parity: the reference hashes the topic to pick one of N TCP
    channels so one topic's forwards never reorder (emqx_rpc.erl:66-80).
    In-process the bus is already synchronous, so this just records the
    channel choice for observability and future TCP transport use.
    """

    def __init__(self, n_channels: int = 8) -> None:
        self.n_channels = n_channels
        self._sent: Dict[int, int] = {}

    def pick(self, key: str) -> int:
        ch = hash(key) % self.n_channels
        self._sent[ch] = self._sent.get(ch, 0) + 1
        return ch

    def stats(self) -> Dict[int, int]:
        return dict(self._sent)


class AsyncSender:
    """Background thread draining an ordered queue per destination node.

    Implements the async forward mode ([rpc, mode] = async,
    emqx_broker.erl:283-288): callers enqueue and return immediately;
    per-destination order is preserved by a single drain thread.
    """

    def __init__(self, bus: LocalBus, src: str) -> None:
        self._bus = bus
        self._src = src
        self._queues: Dict[str, queue.Queue] = {}
        self._threads: Dict[str, threading.Thread] = {}
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self.dropped = 0

    def enqueue(self, dst: str, payload: object) -> None:
        with self._lock:
            q = self._queues.get(dst)
            if q is None:
                q = self._queues[dst] = queue.Queue()
                t = threading.Thread(
                    target=self._drain, args=(dst, q), daemon=True
                )
                self._threads[dst] = t
                t.start()
        q.put(payload)

    def _drain(self, dst: str, q: "queue.Queue") -> None:
        while not self._stop.is_set():
            try:
                payload = q.get(timeout=0.1)
            except queue.Empty:
                continue
            if not self._bus.cast(self._src, dst, payload):
                self.dropped += 1
            q.task_done()

    def flush(self, timeout: float = 5.0) -> None:
        with self._lock:
            qs = list(self._queues.values())
        for q in qs:
            q.join()

    def stop(self) -> None:
        self._stop.set()
