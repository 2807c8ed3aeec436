"""An ack run is one pass over the session window (broker/session.py
`Session.ack_run`, broker/channel.py `Channel._in_acks`;
docs/protocol_plane.md "The ack run").

A read chunk's PUBACK / PUBREC / PUBCOMP clear and refill the window once
and the refills leave through the split frame their message's first sends
share. Pinned here by state and bytes (never a time): a run equals the
same acks handled one by one, and both equal what `frame.serialize` of
each refill gives, which is what the per-ack path wrote."""

import asyncio

import numpy as np
import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.channel import Channel, ChannelConfig
from emqx_tpu.broker.cm import ChannelManager
from emqx_tpu.broker.hooks import STOP, Hooks
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.mqueue import MQueue
from emqx_tpu.broker.router import Router
from emqx_tpu.broker.session import Session, SessionConfig
from emqx_tpu.broker.session_store import SessionStore
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.mqtt.frame import Parser, serialize
from emqx_tpu.transport.connection import Connection

PROPS = {"Content-Type": "text/plain", "User-Property": [("k", "v")]}


class Writer:
    """Records the socket's bytes."""

    def __init__(self):
        self.data = b""
        self.transport = self

    def get_extra_info(self, key):
        return ("127.0.0.1", 1)

    def write(self, data):
        self.data += bytes(data)

    def writelines(self, segs):
        self.data += b"".join(bytes(s) for s in segs)

    def close(self):
        pass


class PacketSink:
    """A sink without `send_segments`: every send is a `send_packet`."""

    def __init__(self, version):
        self.version = version
        self.data = b""

    def send_packet(self, p):
        self.data += serialize(p, self.version)

    def close(self, reason):
        pass


def _ack(pid, type_=pkt.PUBACK):
    p = pkt.PubAck(packet_id=pid)
    p.type = type_
    return p


def _broker():
    return Broker(router=Router(min_tpu_batch=10 ** 6), hooks=Hooks())


def _connected(b, version=pkt.MQTT_V4, sink=None, store=None, **session):
    """A connected channel over a recording socket (or over `sink`);
    driven without a loop, so the sink writes through."""
    cm = ChannelManager(b)
    if sink is None:
        ch = Connection(b, cm, None, Writer(), ChannelConfig()).channel
    else:
        ch = Channel(b, cm, sink)
    ch.state, ch.client_id, ch.version = "connected", "c1", version
    ch.session = Session("c1", SessionConfig(**session), store=store)
    return ch


def _out(ch):
    sink = ch.sink
    return sink.data if isinstance(sink, PacketSink) else sink.writer.data


def _msg(i, props=None, qos=1, topic=None):
    return Message(topic=topic or f"t/{i % 4}", payload=b"p%d" % i, qos=qos,
                   properties=dict(props or {}))


def _fill(ch, msgs, qos=1):
    for m in msgs:
        ch.handle_deliver(m, pkt.SubOpts(qos=qos))


def _window(session):
    return [(pid, e.msg.mid, e.phase) for pid, e in session.inflight.items()]


def _queue(session):
    return [m.mid for m in session.mqueue.peek_all()]


# -- a run equals its acks one by one ----------------------------------------------


@pytest.mark.parametrize("props", [None, PROPS], ids=["plain", "props"])
@pytest.mark.parametrize("version", [pkt.MQTT_V4, pkt.MQTT_V5])
@pytest.mark.parametrize("n", [1, 3, 32])
def test_a_run_of_n_pubacks_equals_n_single_acks(n, version, props):
    """Same window, same queue, same bytes; and the bytes are each refill
    serialised alone by `frame.serialize`, as the per-ack path wrote."""
    msgs = [_msg(i, props) for i in range(3 * n)]  # n in flight, 2n queued
    b1, b2 = _broker(), _broker()
    run = _connected(b1, version, max_inflight=n, max_mqueue=4 * n)
    one = _connected(b2, version, max_inflight=n, max_mqueue=4 * n)
    for ch in (run, one):
        _fill(ch, msgs)
    before = len(_out(run))
    assert _out(one) == _out(run) and len(run.session.mqueue) == 2 * n

    run.handle_acks([_ack(pid) for pid in range(1, n + 1)])
    for pid in range(1, n + 1):
        asyncio.run(one.handle_in(_ack(pid)))

    assert _window(run.session) == _window(one.session)
    assert [pid for pid, _, _ in _window(run.session)] == list(
        range(n + 1, 2 * n + 1))
    assert _queue(run.session) == _queue(one.session) == [
        m.mid for m in msgs[2 * n:]]
    assert _out(run) == _out(one)
    assert _out(run)[before:] == b"".join(
        serialize(pkt.Publish(topic=m.topic, payload=m.payload, qos=1,
                              packet_id=n + 1 + i,
                              properties=dict(m.properties)), version)
        for i, m in enumerate(msgs[n:2 * n]))
    # every refill left through the shared split frame, one run or n
    for b, runs in ((b1, 1), (b2, n)):
        assert b.metrics.get("channel.ack.runs") == runs
        assert b.metrics.get("packets.sent") == 2 * n
        assert b.metrics.get("dispatch.serialize.frames") == 2 * n
        assert b.metrics.get("packets.received") == n


def test_the_refills_of_all_subscribers_share_one_serialisation(monkeypatch):
    """A message queued in many sessions is split once: the refills find
    the frame its first sends (or an earlier refill) left on it."""
    from emqx_tpu.mqtt import slab_serializer as SS

    calls = []
    real = SS.split_publish
    monkeypatch.setattr(
        SS, "split_publish",
        lambda *a, **kw: (calls.append(a[0]), real(*a, **kw))[1])
    b = _broker()
    chans = [_connected(b, max_inflight=2, max_mqueue=8) for _ in range(5)]
    msgs = [_msg(i) for i in range(4)]  # two sent first, two queued
    for ch in chans:
        _fill(ch, msgs)
    assert len(calls) == 2  # the first sends' frames; the queued: none yet
    for ch in chans:
        ch.handle_acks([_ack(1), _ack(2)])
    assert len(calls) == 4
    assert len({_out(ch) for ch in chans}) == 1


# -- what a run may hold ---------------------------------------------------------------


def test_unknown_and_duplicate_ids_yield_nothing():
    b = _broker()
    acked = []
    b.hooks.add("message.acked", lambda ci, m: acked.append(m.mid))
    ch = _connected(b, max_inflight=3, max_mqueue=8)
    msgs = [_msg(i) for i in range(8)]
    _fill(ch, msgs)
    ch.handle_acks([_ack(1), _ack(1), _ack(99), _ack(2)])
    assert acked == [msgs[0].mid, msgs[1].mid]
    # two ids freed: two refills, in the queue's order
    assert _window(ch.session) == [
        (3, msgs[2].mid, "publish"), (4, msgs[3].mid, "publish"),
        (5, msgs[4].mid, "publish")]
    assert _queue(ch.session) == [m.mid for m in msgs[5:]]
    # a run of nothing known frees nothing and sends nothing
    sent = b.metrics.get("packets.sent")
    ch.handle_acks([_ack(1), _ack(77)])
    assert b.metrics.get("packets.sent") == sent
    assert len(ch.session.inflight) == 3


@pytest.mark.parametrize("version", [pkt.MQTT_V4, pkt.MQTT_V5])
def test_a_mixed_run_keeps_pubrel_order_and_publish_order(version):
    b = _broker()
    done = []
    b.hooks.add("delivery.completed", lambda ci, m, lat: done.append(m.mid))
    ch = _connected(b, version, max_inflight=4, max_mqueue=8)
    msgs = [_msg(0, qos=2), _msg(1, qos=1), _msg(2, qos=2), _msg(3, qos=2),
            _msg(4, topic="t/x"), _msg(5, topic="t/y"), _msg(6, topic="t/x")]
    _fill(ch, msgs, qos=2)
    asyncio.run(ch.handle_in(_ack(4, pkt.PUBREC)))  # pid 4 is in its rel phase
    before = len(_out(ch))
    ch.handle_acks([
        _ack(1, pkt.PUBREC), _ack(2, pkt.PUBACK), _ack(3, pkt.PUBREC),
        _ack(4, pkt.PUBCOMP), _ack(77, pkt.PUBREC), _ack(3, pkt.PUBCOMP)])
    out = Parser(version=version).feed(_out(ch)[before:])
    rels = [(p.packet_id, p.reason_code) for p in out if p.type == pkt.PUBREL]
    unknown = (pkt.RC_PACKET_IDENTIFIER_NOT_FOUND
               if version == pkt.MQTT_V5 else pkt.RC_SUCCESS)  # v4: no code
    assert rels == [(1, pkt.RC_SUCCESS), (3, pkt.RC_SUCCESS), (77, unknown)]
    pubs = [(p.topic, p.payload, p.packet_id) for p in out
            if p.type == pkt.PUBLISH]
    # pids 2, 4 and 3 left the window: three refills in the queue's order
    assert pubs == [("t/x", b"p4", 5), ("t/y", b"p5", 6), ("t/x", b"p6", 7)]
    assert len(out) == 6
    assert done == [msgs[1].mid, msgs[3].mid, msgs[2].mid]
    assert [(pid, ph) for pid, _, ph in _window(ch.session)] == [
        (1, "pubrel"), (5, "publish"), (6, "publish"), (7, "publish")]


def test_a_run_of_pubrecs_alone_refills_nothing():
    b = _broker()
    ch = _connected(b, max_inflight=2, max_mqueue=8)
    _fill(ch, [_msg(i, qos=2) for i in range(2)], qos=2)
    ch.session.mqueue.in_(_msg(9))  # as a takeover's parking leaves it
    ch.session.inflight.max_size = 4
    ch.handle_acks([_ack(1, pkt.PUBREC), _ack(2, pkt.PUBREC)])
    assert len(ch.session.mqueue) == 1 and len(ch.session.inflight) == 2


def test_a_pubcomp_before_its_pubrec_completes_nothing():
    s = Session("c", SessionConfig(max_inflight=2))
    s.deliver(_msg(0, qos=2))
    done, recs, refills = s.ack_run([(pkt.PUBCOMP, 1), (pkt.PUBREC, 1)])
    assert (done, recs, refills) == ([], [(1, False)], [])
    assert len(s.inflight) == 0


# -- the queue's order --------------------------------------------------------------------


def test_priorities_are_honoured_across_one_refill():
    b = _broker()
    ch = _connected(b, max_inflight=3, max_mqueue=16)
    ch.session.mqueue = MQueue(16, priorities={"hi": 5, "mid": 2})
    first = [_msg(i) for i in range(3)]
    queued = [_msg(10, topic="lo"), _msg(11, topic="hi"),
              _msg(12, topic="mid"), _msg(13, topic="lo"),
              _msg(14, topic="hi")]
    _fill(ch, first + queued)
    before = len(_out(ch))
    ch.handle_acks([_ack(1), _ack(2), _ack(3)])
    out = Parser().feed(_out(ch)[before:])
    assert [(p.topic, p.payload) for p in out] == [
        ("hi", b"p11"), ("hi", b"p14"), ("mid", b"p12")]
    assert [m.payload for m in ch.session.mqueue.peek_all()] == [
        b"p10", b"p13"]


@pytest.mark.parametrize("n", [0, 1, 2, 3, 5, 9])
def test_take_n_is_n_times_out(n):
    def mk():
        q = MQueue(16, priorities={"hi": 5, "mid": 2})
        for i, t in enumerate(["lo", "hi", "mid", "lo", "hi", "lo"]):
            q.in_(Message(topic=t, payload=b"%d" % i, qos=1))
        return q

    a, c = mk(), mk()
    one_by_one = [m for m in (c.out() for _ in range(n)) if m is not None]
    assert [m.payload for m in a.take(n)] == [m.payload for m in one_by_one]
    assert len(a) == len(c) == max(0, 6 - n)
    assert [m.payload for m in a.peek_all()] == [
        m.payload for m in c.peek_all()]


def test_an_unbounded_window_takes_the_whole_queue():
    s = Session("c", SessionConfig(max_inflight=0, max_mqueue=8))
    for i in range(5):
        s.mqueue.in_(_msg(i))
    assert [pid for pid, _ in s.refill()] == [1, 2, 3, 4, 5]
    assert len(s.mqueue) == 0 and len(s.inflight) == 5


def test_packet_ids_wrap_and_walk_past_the_window():
    s = Session("c", SessionConfig(max_inflight=8, max_mqueue=8))
    s._next_pid = 65534
    s.inflight.insert(65535, _msg(0))
    s.inflight.insert(2, _msg(1))
    assert s.alloc_packet_ids(3) == [65534, 1, 3]
    assert s.alloc_packet_id() == 4 and s._next_pid == 5
    # a refill over the wrap: ids in the queue's order, none twice
    s._next_pid = 65535
    for i in range(3):
        s.mqueue.in_(_msg(10 + i))
    assert [(pid, m.payload) for pid, m in s.refill()] == [
        (1, b"p10"), (3, b"p11"), (4, b"p12")]


def test_a_refill_stamps_the_run_with_one_clock_reading(monkeypatch):
    import time

    reads = []
    real = time.monotonic
    monkeypatch.setattr(
        time, "monotonic", lambda: (reads.append(1), real())[1])
    s = Session("c", SessionConfig(max_inflight=4, max_mqueue=16))
    for i in range(12):
        s.deliver(_msg(i))
    reads.clear()
    _, _, refills = s.ack_run([(pkt.PUBACK, pid) for pid in (1, 2, 3, 4)])
    assert len(refills) == 4 and len(reads) == 1
    assert len({e.ts for _, e in s.inflight.items()}) == 1


def test_puback_and_pubcomp_are_the_run_of_one():
    s = Session("c", SessionConfig(max_inflight=2, max_mqueue=8))
    msgs = [_msg(0), _msg(1, qos=2), _msg(2), _msg(3)]
    for m in msgs:
        s.deliver(m)
    done, more = s.puback(1)
    assert done is msgs[0]
    assert [(p.packet_id, p.payload, p.qos, p.dup) for p in more] == [
        (3, b"p2", 1, False)]
    done, more = s.pubcomp(2)  # no PUBREC yet: gone, and not complete
    assert done is None and [p.packet_id for p in more] == [4]
    assert len(s.inflight) == 2 and len(s.mqueue) == 0
    assert s.puback(99)[0] is None


# -- where the split frame does not apply --------------------------------------------


@pytest.mark.parametrize("version", [pkt.MQTT_V4, pkt.MQTT_V5])
def test_a_sink_without_segments_takes_send_packet(version):
    b = _broker()
    ch = _connected(b, version, sink=PacketSink(version), max_inflight=3,
                    max_mqueue=8)
    msgs = [_msg(i, PROPS) for i in range(6)]
    _fill(ch, msgs)
    before = len(_out(ch))
    ch.handle_acks([_ack(1), _ack(2), _ack(3)])
    assert _out(ch)[before:] == b"".join(
        serialize(pkt.Publish(topic=m.topic, payload=m.payload, qos=1,
                              packet_id=4 + i, properties=dict(PROPS)),
                  version)
        for i, m in enumerate(msgs[3:]))
    assert b.metrics.get("dispatch.serialize.frames") == 0
    assert b.metrics.get("packets.sent") == 6


def test_a_retained_replay_takes_send_packet_in_the_queue_s_order():
    b = _broker()
    ch = _connected(b, max_inflight=3, max_mqueue=8)
    msgs = [_msg(i) for i in range(6)]
    msgs[4].headers["retained"] = True  # a retained-store replay, queued
    msgs[4].retain = True
    _fill(ch, msgs)
    before = len(_out(ch))
    frames = b.metrics.get("dispatch.serialize.frames")
    ch.handle_acks([_ack(1), _ack(2), _ack(3)])
    assert _out(ch)[before:] == b"".join(
        serialize(pkt.Publish(topic=m.topic, payload=m.payload, qos=1,
                              retain=m.retain, packet_id=4 + i),
                  pkt.MQTT_V4)
        for i, m in enumerate(msgs[3:]))
    assert b.metrics.get("dispatch.serialize.frames") == frames + 2
    assert not hasattr(msgs[4], "_fbq")  # the store's message caches nothing


# -- hooks ---------------------------------------------------------------------------------


def test_acked_then_completed_fire_once_per_message_in_order():
    b = _broker()
    seen = []
    b.hooks.add("message.acked",
                lambda ci, m: seen.append(("acked", ci["client_id"], m.mid)))
    b.hooks.add(
        "delivery.completed",
        lambda ci, m, lat: seen.append(("done", m.mid, lat >= 0)))
    ch = _connected(b, max_inflight=3, max_mqueue=8)
    msgs = [_msg(i) for i in range(5)]
    _fill(ch, msgs)
    ch.handle_acks([_ack(2), _ack(1), _ack(3)])
    assert seen == [
        x for m in (msgs[1], msgs[0], msgs[2])
        for x in (("acked", "c1", m.mid), ("done", m.mid, True))]


def test_a_stop_ends_one_message_s_chain_and_async_callbacks_wait():
    b = _broker()
    seen = []

    async def later(ci, m):
        seen.append("async")

    b.hooks.add("message.acked", lambda ci, m: seen.append("a") or STOP,
                priority=5)
    b.hooks.add("message.acked", lambda ci, m: seen.append("never"))
    b.hooks.add("message.acked", later, priority=9)
    ch = _connected(b, max_inflight=2, max_mqueue=8)
    _fill(ch, [_msg(i) for i in range(2)])
    ch.handle_acks([_ack(1), _ack(2)])
    assert seen == ["a", "a"]
    assert len(b.hooks.sync_callbacks("message.acked")) == 2


def test_a_default_app_has_no_message_acked_callback(tmp_path):
    from emqx_tpu.app import BrokerApp
    from emqx_tpu.config.schema import load_config

    app = BrokerApp(load_config({
        "listeners": [{"port": 0, "bind": "127.0.0.1"}],
        "router": {"enable_tpu": False},
        "observe": {"trace_dir": str(tmp_path / "trace")},
    }))
    assert app.hooks.callbacks("message.acked") == []
    tags = {e[1] for e in app.hooks.callbacks("delivery.completed")}
    assert tags == {"slow_subs"}
    # the QoS0 raw lane's condition reads what it read: TopicMetrics is there
    assert "topic_metrics" in {
        e[1] for e in app.hooks.callbacks("message.delivered")}
    assert "event_message" not in {
        e[1] for name in ("message.delivered", "message.dropped")
        for e in app.hooks.callbacks(name)}


@pytest.mark.parametrize("event", ["message_delivered", "message_acked",
                                   "message_dropped"])
def test_an_enabled_event_attaches_and_a_changed_setting_reattaches(event):
    from emqx_tpu.observe.event_message import EventMessage

    hooks = Hooks()
    em = EventMessage(None, enabled={event})
    em.attach(hooks)
    name = event.replace("_", ".")
    per_message = ("message.delivered", "message.acked", "message.dropped")
    assert [n for n in per_message if hooks.callbacks(n)] == [name]
    assert len(hooks.callbacks("client.connected")) == 1
    em.enabled = set()
    em.attach(hooks)
    assert not any(hooks.callbacks(n) for n in per_message)
    assert len(hooks.callbacks("client.connected")) == 1


@pytest.mark.parametrize("event", ["message_delivered", "message_acked",
                                   "message_dropped"])
def test_a_rule_s_from_attaches_its_event_and_its_deletion_detaches(event):
    from emqx_tpu.rules.engine import Console, RuleEngine

    b = _broker()
    eng = RuleEngine(b)
    eng.attach(b.hooks)
    name = event.replace("_", ".")
    per_message = ("message.delivered", "message.acked", "message.dropped")
    assert not any(b.hooks.callbacks(n) for n in per_message)
    eng.create_rule("r1", f'SELECT * FROM "$events/{event}"', [Console()])
    eng.create_rule("r2", 'SELECT * FROM "t/#"', [Console()])
    assert [n for n in per_message if b.hooks.callbacks(n)] == [name]
    args = {"message.dropped": (_msg(0), "queue_full")}.get(
        name, ({"client_id": "c"}, _msg(0)))
    b.hooks.run(name, *args)
    assert eng.get_rule("r1").metrics.matched == 1
    eng.get_rule("r1").enabled = False  # a live check, as before
    b.hooks.run(name, *args)
    assert eng.get_rule("r1").metrics.matched == 1
    assert eng.delete_rule("r1")
    assert not any(b.hooks.callbacks(n) for n in per_message)


# -- the device store's view ---------------------------------------------------------


def _table(store, slot):
    t = store.table
    rows = np.asarray(t.rows_of_slot(slot), dtype=np.int64)
    return sorted(zip(t.sess_pid[rows].tolist(), t.sess_state[rows].tolist()))


@pytest.mark.parametrize("n", [1, 3, 32])
def test_a_store_session_sees_the_same_table_after_a_run(n):
    b1, b2 = _broker(), _broker()
    s1, s2 = SessionStore(capacity=256), SessionStore(capacity=256)
    run = _connected(b1, store=s1, max_inflight=n, max_mqueue=4 * n)
    one = _connected(b2, store=s2, max_inflight=n, max_mqueue=4 * n)
    msgs = [_msg(i, qos=1 + i % 2) for i in range(3 * n)]
    for ch in (run, one):
        _fill(ch, msgs, qos=2)
    acks = [_ack(pid, pkt.PUBACK if pid % 2 else pkt.PUBREC)
            for pid in range(1, n + 1)]
    run.handle_acks(acks)
    for p in acks:
        asyncio.run(one.handle_in(p))
    assert _table(s1, run.session.store_slot) == _table(
        s2, one.session.store_slot)
    assert len(_table(s1, run.session.store_slot)) == n
    assert s1.table.live == s2.table.live == len(run.session.inflight)
    assert _window(run.session) == _window(one.session)
    # the run's PUBRELs leave before its refills, each kind in its order
    a, c = Parser().feed(_out(run)), Parser().feed(_out(one))
    for kind in (pkt.PUBREL, pkt.PUBLISH):
        assert [p for p in a if p.type == kind] == [
            p for p in c if p.type == kind]
    assert len(a) == len(c)


# -- the counter -------------------------------------------------------------------------


def test_ack_runs_counts_runs_not_acks():
    b = _broker()
    ch = _connected(b, max_inflight=4, max_mqueue=8)
    _fill(ch, [_msg(i) for i in range(6)])
    ch.handle_acks([_ack(1), _ack(2), _ack(3)])
    asyncio.run(ch.handle_in(_ack(4)))
    assert b.metrics.get("channel.ack.runs") == 2
    assert b.metrics.get("packets.received") == 4
