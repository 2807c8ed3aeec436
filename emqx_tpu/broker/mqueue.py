"""Bounded priority message queue (reference: apps/emqx/src/emqx_mqueue.erl).

Per-topic priorities, bounded length, drop policy; $SYS-topic messages can
be dropped preferentially like the reference's `store_qos0`/priorities
behavior. QoS0 messages may bypass the queue entirely when the inflight
window has room (handled by the session)."""

from __future__ import annotations

from collections import deque
from itertools import repeat, starmap
from typing import Dict, List, Optional

from emqx_tpu.broker.message import Message


class MQueue:
    def __init__(
        self,
        max_len: int = 1000,
        priorities: Optional[Dict[str, int]] = None,
        default_priority: int = 0,
        store_qos0: bool = True,
    ):
        self.max_len = max_len
        self.priorities = priorities or {}
        self.default_priority = default_priority
        self.store_qos0 = store_qos0
        # priority -> deque; drained highest priority first
        self._qs: Dict[int, deque] = {}
        self._len = 0
        self.dropped = 0

    def __len__(self) -> int:
        return self._len

    def _prio(self, msg: Message) -> int:
        return self.priorities.get(msg.topic, self.default_priority)

    def in_(self, msg: Message) -> Optional[Message]:
        """Enqueue; returns a dropped message if the queue was full."""
        if msg.qos == 0 and not self.store_qos0:
            self.dropped += 1
            return msg
        # slab-escape site: banked messages outlive their fabric frame —
        # materialize before queueing (no-op for ordinary messages)
        msg.own_buffers()
        p = self._prio(msg)
        q = self._qs.setdefault(p, deque())
        dropped = None
        if self.max_len and self._len >= self.max_len:
            # drop-oldest within the lowest priority band
            lowest = min(self._qs, key=lambda k: (k, ))
            lq = self._qs[lowest]
            if lq:
                dropped = lq.popleft()
                self._len -= 1
                self.dropped += 1
        q.append(msg)
        self._len += 1
        return dropped

    def out(self) -> Optional[Message]:
        got = self.take(1)
        return got[0] if got else None

    def take(self, n: int) -> List[Message]:
        """Dequeue up to `n` messages: the highest priority band first,
        FIFO inside a band."""
        out: List[Message] = []
        if n <= 0 or self._len == 0:
            return out
        qs = self._qs
        for p in sorted(qs, reverse=True) if len(qs) > 1 else tuple(qs):
            q = qs[p]
            if len(q) <= n - len(out):
                out.extend(q)
                q.clear()
            else:  # n - len(out) calls of popleft, made by the C loop
                out.extend(starmap(q.popleft, repeat((), n - len(out))))
            if len(out) == n:
                break
        self._len -= len(out)
        return out

    def peek_all(self):
        for p in sorted(self._qs, reverse=True):
            yield from self._qs[p]
