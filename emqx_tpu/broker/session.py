"""Per-client session state (reference: apps/emqx/src/emqx_session.erl).

Holds subscriptions, the inflight window, the bounded mqueue, QoS2
awaiting_rel set, and the packet-id counter. Survives connection churn:
on takeover the whole object moves to the new channel
(emqx_session:takeover/resume/replay, emqx_session.erl:85-90).

Pure state machine — no I/O. A run of deliveries (`deliver_run`; `deliver`
is the run of one) takes the window's room once and says what to send; a
run of acks (`ack_run`) clears the window and refills it from the queue
once, whatever its length.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

from emqx_tpu.broker.inflight import Inflight
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.mqueue import MQueue
from emqx_tpu.mqtt import packet as pkt


@dataclass
class SessionConfig:
    max_inflight: int = 32
    max_mqueue: int = 1000
    retry_interval: float = 30.0
    await_rel_timeout: float = 300.0
    max_awaiting_rel: int = 100
    # default persistence for v3.1.1 clean_session=0 clients (the reference
    # defaults to 2h); v5 clients override via Session-Expiry-Interval, and
    # clean-start v4 sessions are forced to 0 by the channel manager
    expiry_interval: float = 7200.0
    # device-resident session store (broker/session_store.py): inflight
    # windows + QoS state land on segment tables, ack clears fuse into
    # serving launches, retry scans become device sweeps. Off = the
    # host-dict path alone (also the degrade-ladder fallback when on)
    device_store: bool = False
    # initial (slot, packet-id) row capacity; grows by doubling
    store_capacity: int = 4096
    # compact width of the device retry/expiry sweep (pow2-rounded);
    # uncapped counts tell the store when a flood needs a second sweep
    store_sweep_slots: int = 1024
    # how often housekeeping arms a sweep / runs the host fallback scan
    store_sweep_interval: float = 5.0


class Session:
    def __init__(
        self,
        client_id: str,
        config: SessionConfig = SessionConfig(),
        store=None,
    ):
        """`store`: an optional `broker.session_store.SessionStore` —
        when given, inflight/awaiting-rel state writes through to the
        device-resident session table (the dict view stays authoritative
        for this live session; the table carries the aggregate state for
        fused ack clears, device sweeps, and mass resume)."""
        import dataclasses

        self.client_id = client_id
        self.config = dataclasses.replace(config)  # per-session copy
        self.created_at = time.time()
        self.subscriptions: Dict[str, pkt.SubOpts] = {}
        self.store = store
        if store is not None:
            self.store_slot = store.attach(client_id)
            self.inflight = store.make_inflight(
                self.store_slot, config.max_inflight
            )
        else:
            self.store_slot = None
            self.inflight = Inflight(config.max_inflight)
        self.mqueue = MQueue(config.max_mqueue)
        # called with each message the full queue drops in `deliver` (the
        # owning channel counts it: session.mqueue.dropped)
        self.on_dropped: Optional[Callable[[Message], None]] = None
        self.awaiting_rel: Dict[int, float] = {}  # incoming QoS2 packet ids
        self._next_pid = 1

    # -- packet ids -------------------------------------------------------
    def alloc_packet_id(self) -> int:
        return self.alloc_packet_ids(1)[0]

    def alloc_packet_ids(self, n: int) -> List[int]:
        """`n` packet ids for `n` inserts to come: the counter walks on
        (65535 wraps to 1) past every id the window holds."""
        contains = self.inflight.contains
        pid = self._next_pid
        out: List[int] = []
        while len(out) < n:
            if not contains(pid):
                out.append(pid)
            pid = pid % 65535 + 1
        self._next_pid = pid
        return out

    # -- outgoing (broker -> client) --------------------------------------
    def deliver(
        self, msg: Message, opts: Optional[pkt.SubOpts] = None
    ) -> List[pkt.Publish]:
        """Accept one routed message; return PUBLISH packets ready to send."""
        return [
            self._publish_packet(self._adjust(m, qos, retain), qos, pid)
            for _, m, qos, retain, pid in self.deliver_run(((msg, opts),))
        ]

    def deliver_run(
        self, items
    ) -> List[Tuple[int, Message, int, bool, Optional[int]]]:
        """Accept a run of routed messages, `(message, subscription
        options)` pairs in delivery order (a settled batch's for this
        connection, or one) -> what leaves now, `(index in the run,
        message, qos, retain, packet id)` in the run's order.

        One pass. The effective QoS is the lower of the message's and the
        subscription's; a forwarded message carries retain=0 unless the
        subscription set retain-as-published, a retained-store replay
        keeps retain=1 (MQTT spec). A QoS0 item stays in place in the
        order (packet id None). The QoS1/2 items take the window's room
        once: `alloc_packet_ids`, one clock reading and the inserts for
        those that fit, the queue for every later one (what it drops goes
        to `on_dropped`, in the queue's order). No ack is handled inside a run, so a window that
        fills stays full: this is what `deliver` called once per item
        leaves behind, packet ids included."""
        free = self.inflight.room(len(items))
        sends: List = []
        fits: List = []  # (place in `sends`, the message as the window holds it)
        drops: List[Message] = []
        for i, (msg, opts) in enumerate(items):
            if opts:
                qos = min(msg.qos, opts.qos)
                retain = (
                    msg.retain
                    if opts.retain_as_published
                    else bool(msg.headers.get("retained"))
                )
            else:
                qos, retain = msg.qos, bool(msg.headers.get("retained"))
            if qos == 0:
                sends.append((i, msg, 0, retain, None))
                continue
            held = (
                msg
                if msg.qos == qos and msg.retain == retain
                else self._adjust(msg, qos, retain)
            )
            if len(fits) < free:
                fits.append((len(sends), held))
                sends.append((i, msg, qos, retain))
                continue
            dropped = self.mqueue.in_(held)
            if dropped is not None:
                drops.append(dropped)
        if fits:
            insert = self.inflight.insert
            now = time.monotonic()
            for pid, (at, held) in zip(self.alloc_packet_ids(len(fits)), fits):
                insert(pid, held, "publish", now)
                sends[at] += (pid,)
        if drops and self.on_dropped is not None:
            # after the inserts: a callback that raises loses no message
            for dropped in drops:
                self.on_dropped(dropped)
        return sends

    def _adjust(self, msg: Message, qos: int, retain: bool) -> Message:
        if msg.qos == qos and msg.retain == retain:
            return msg
        import copy

        m = copy.copy(msg)
        m.qos = qos
        m.retain = retain
        return m

    def _publish_packet(
        self, msg: Message, qos: int, pid: Optional[int], dup: bool = False
    ) -> pkt.Publish:
        return pkt.Publish(
            topic=msg.topic,
            payload=msg.payload,
            qos=qos,
            retain=msg.retain,
            dup=dup,
            packet_id=pid,
            properties=dict(msg.properties),
        )

    def ack_run(
        self, acks
    ) -> Tuple[List[Message], List[Tuple[int, bool]], List[Tuple[int, Message]]]:
        """A run of the subscriber's acks, `(packet type, packet id)` pairs
        in arrival order (a read chunk's, or one) -> (the acknowledged
        messages, `(packet id, known)` of each PUBREC, the refills).

        One pass: a PUBACK / PUBCOMP pops its id from the window (an
        unknown id yields nothing; a PUBCOMP counts only in the rel
        phase), a PUBREC moves its entry to the rel phase, and the window
        is then refilled once from the queue (`refill`). A message leaves
        the window only on its own ack."""
        delete = self.inflight.delete
        puback, pubrec = pkt.PUBACK, pkt.PUBREC
        done: List[Message] = []
        recs: List[Tuple[int, bool]] = []
        for t, pid in acks:
            if t == pubrec:
                recs.append((pid, self.pubrec(pid)))
                continue
            e = delete(pid)
            if (
                e is not None
                and e.msg is not None
                and (t == puback or e.phase == "pubrel")
            ):
                done.append(e.msg)
        return done, recs, (self.refill() if len(recs) < len(acks) else [])

    def refill(self) -> List[Tuple[int, Message]]:
        """Move queued messages into the room the window has, in the
        queue's order -> `(packet id, message)` of each, in the window
        (one clock reading for all) before the caller sends it."""
        room = self.inflight.room(len(self.mqueue))
        if not room:
            return []
        insert = self.inflight.insert
        now = time.monotonic()
        out = list(zip(self.alloc_packet_ids(room), self.mqueue.take(room)))
        for pid, msg in out:
            insert(pid, msg, "publish", now)
        return out

    def _publish_packets(self, refills) -> List[pkt.Publish]:
        return [
            self._publish_packet(msg, msg.qos, pid) for pid, msg in refills
        ]

    def _drain(self) -> List[pkt.Publish]:
        return self._publish_packets(self.refill())

    def _ack_one(
        self, type_: int, packet_id: int
    ) -> Tuple[Optional[Message], List[pkt.Publish]]:
        done, _, more = self.ack_run(((type_, packet_id),))
        return (done[0] if done else None), self._publish_packets(more)

    def puback(
        self, packet_id: int
    ) -> Tuple[Optional[Message], List[pkt.Publish]]:
        """QoS1 ack; returns (acked msg | None, replacement publishes)."""
        return self._ack_one(pkt.PUBACK, packet_id)

    def pubrec(self, packet_id: int) -> bool:
        """QoS2 phase 1 ack'd by receiver -> move to rel phase."""
        e = self.inflight.get(packet_id)
        if e is None or e.phase != "publish":
            return False
        self.inflight.update(packet_id, "pubrel")
        return True

    def pubcomp(
        self, packet_id: int
    ) -> Tuple[Optional[Message], List[pkt.Publish]]:
        return self._ack_one(pkt.PUBCOMP, packet_id)

    # -- incoming QoS2 (client -> broker) ---------------------------------
    def await_rel(self, packet_id: int) -> bool:
        """Track an incoming QoS2 publish until PUBREL; False if duplicate.
        Stamps are monotonic (expiry is an elapsed-time question)."""
        if packet_id in self.awaiting_rel:
            return False
        if len(self.awaiting_rel) >= self.config.max_awaiting_rel:
            raise OverflowError("max_awaiting_rel")
        self.awaiting_rel[packet_id] = time.monotonic()
        if self.store is not None:
            self.store.await_rel(self.store_slot, packet_id)
        return True

    def release_rel(self, packet_id: int) -> bool:
        ok = self.awaiting_rel.pop(packet_id, None) is not None
        if ok and self.store is not None:
            self.store.release_rel(self.store_slot, packet_id)
        return ok

    # -- retry ------------------------------------------------------------
    def retry(self) -> List[pkt.Packet]:
        """Retransmit inflight entries older than retry_interval."""
        out: List[pkt.Packet] = []
        for pid, e in self.inflight.retry_due(self.config.retry_interval):
            if e.phase == "publish" and e.msg is not None:
                out.append(self._publish_packet(e.msg, e.msg.qos, pid, dup=True))
            else:
                rel = pkt.PubAck(packet_id=pid)
                rel.type = pkt.PUBREL
                out.append(rel)
            e.ts = time.monotonic()
            if self.store is not None:
                self.store.touch_inflight(self.store_slot, pid)
        return out

    # -- takeover ---------------------------------------------------------
    def replay(self) -> List[pkt.Packet]:
        """All inflight packets re-sent after takeover/resume (dup=True)."""
        out: List[pkt.Packet] = []
        for pid, e in self.inflight.items():
            if e.phase == "publish" and e.msg is not None:
                out.append(self._publish_packet(e.msg, e.msg.qos, pid, dup=True))
            else:
                rel = pkt.PubAck(packet_id=pid)
                rel.type = pkt.PUBREL
                out.append(rel)
        return out + self._drain()
