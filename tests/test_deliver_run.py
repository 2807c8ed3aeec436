"""A delivery run is one pass over the session window and one send
(broker/broker.py `DeliveryRuns`, broker/channel.py
`Channel.handle_deliver_run`, broker/session.py `Session.deliver_run`;
docs/protocol_plane.md "The delivery run").

A settled batch's deliveries to one in-process connection are collected in
message order and handed over in one call. Pinned here by counts and
bytes (never a time): a run equals the same deliveries made one by one,
both equal what `frame.serialize` gives for each packet a plain reference
expects, and what a run gives back goes the per-message path with the
counts the per-message path alone would have left."""

import json
import types

import numpy as np
import pytest

from emqx_tpu.broker.broker import Broker, DeliveryRuns, run_target
from emqx_tpu.broker.channel import Channel, ChannelConfig
from emqx_tpu.broker.cm import ChannelManager
from emqx_tpu.broker.hooks import STOP, Hooks
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.router import Router
from emqx_tpu.broker.session import Session, SessionConfig
from emqx_tpu.models.router_model import RouteResult
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.mqtt.frame import serialize
from emqx_tpu.ops import topics as T
from emqx_tpu.transport.connection import Connection
from emqx_tpu.transport.workers import WorkerFabric

PROPS = {"Content-Type": "text/plain", "User-Property": [("k", "v")]}
V4, V5 = pkt.MQTT_V4, pkt.MQTT_V5
VERSIONS = pytest.mark.parametrize("version", [V4, V5], ids=["v4", "v5"])


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
    """A sink without `send_segments` / `send_bytes`: every send is a
    `send_packet`."""

    def __init__(self, version):
        self.version = version
        self.data = b""

    def send_packet(self, p):
        self.data += serialize(p, self.version)

    def close(self, reason):
        pass


def _broker():
    return Broker(router=Router(min_tpu_batch=10 ** 6), hooks=Hooks())


def _connected(b, version=V4, sink=None, cid="c1", **session):
    """A connected channel over a recording socket (or over `sink`);
    driven without a loop, so the sink writes through."""
    cm = ChannelManager(b)
    if sink is None:
        ch = Connection(b, cm, None, Writer(), ChannelConfig()).channel
    else:
        ch = Channel(b, cm, sink)
    ch.state, ch.client_id, ch.version = "connected", cid, version
    ch.session = Session(cid, SessionConfig(**session))
    ch.session.on_dropped = ch._queue_dropped
    return ch


def _out(ch):
    sink = ch.sink
    return sink.data if isinstance(sink, PacketSink) else sink.writer.data


def _msg(i, qos=1, props=None, topic=None, retain=False, **kw):
    return Message(topic=topic or f"t/{i % 4}", payload=b"p%d" % i, qos=qos,
                   retain=retain, properties=dict(props or {}), **kw)


def _window(session):
    return [(pid, e.msg.mid, e.msg.qos, e.msg.retain, e.phase)
            for pid, e in session.inflight.items()]


def _queue(session):
    return [(m.mid, m.qos, m.retain) for m in session.mqueue.peek_all()]


def _state(ch):
    s = ch.session
    return _window(s), _queue(s), s._next_pid, _out(ch)


def _mixed(n, props=None):
    """`n` deliveries mixing QoS 0/1/2, retain-as-published and a
    subscription QoS under the message's."""
    items = []
    for i in range(n):
        m = _msg(i, qos=i % 3, props=props, retain=i % 5 == 0)
        o = pkt.SubOpts(qos=(i // 3) % 3, retain_as_published=i % 2 == 0)
        items.append((m, o))
    return items


def _reference(items, version, next_pid=1, room=10 ** 9, held=()):
    """What the wire carries for `items` delivered in order, from the
    rules alone: the lower QoS, retain only as published (or a replay), a
    packet id per QoS1/2 message while the window has room."""
    out = b""
    held = set(held)
    for msg, opts in items:
        qos = min(msg.qos, opts.qos)
        retain = (
            msg.retain if opts.retain_as_published
            else bool(msg.headers.get("retained"))
        )
        pid = None
        if qos:
            if room <= 0:
                continue
            room -= 1
            while next_pid in held:
                next_pid = next_pid % 65535 + 1
            pid, next_pid = next_pid, next_pid % 65535 + 1
            held.add(pid)
        out += serialize(
            pkt.Publish(topic=msg.topic, payload=msg.payload, qos=qos,
                        retain=retain, packet_id=pid,
                        properties=dict(msg.properties)),
            version,
        )
    return out


def _both(version, items, sink=None, **session):
    """`items` as one run and one by one, on two equal channels."""
    run = _connected(_broker(), version,
                     sink and sink(version), **session)
    one = _connected(_broker(), version,
                     sink and sink(version), **session)
    assert not run.handle_deliver_run(items)
    for m, o in items:
        one.handle_deliver(m, o)
    return run, one


# -- a run equals its deliveries one by one -------------------------------


@pytest.mark.parametrize("props", [None, PROPS], ids=["plain", "props"])
@VERSIONS
@pytest.mark.parametrize("n", [1, 7, 60])
def test_a_run_equals_its_deliveries_one_by_one(n, version, props):
    """Same bytes, window, queue and next packet id; and the bytes are
    each packet serialised alone by `frame.serialize`."""
    items = _mixed(n, props)
    run, one = _both(version, items, max_inflight=1024)
    assert _state(run) == _state(one)
    assert _out(run) == _reference(items, version)
    assert len(run.session.mqueue) == 0
    assert len(run.session.inflight) == sum(
        1 for m, o in items if min(m.qos, o.qos))


@VERSIONS
def test_a_run_reports_nothing_failed_and_returns_no_packets(version):
    ch = _connected(_broker(), version)
    assert not ch.handle_deliver_run(_mixed(9))
    assert not ch.handle_deliver_run([])
    assert ch.handle_deliver(_msg(1), pkt.SubOpts(qos=1)) is None


@VERSIONS
def test_session_deliver_is_the_run_of_one(version):
    """`Session.deliver` keeps its packets: the run's sends, as packets."""
    s = Session("c", SessionConfig(max_inflight=2))
    m = _msg(0, qos=2, retain=True, props=PROPS)
    [p] = s.deliver(m, pkt.SubOpts(qos=1, retain_as_published=True))
    assert (p.qos, p.retain, p.packet_id, p.topic, p.payload) == (
        1, True, 1, m.topic, m.payload)
    assert p.properties == PROPS and p.properties is not m.properties
    [q] = s.deliver(_msg(1, qos=0), pkt.SubOpts(qos=2))
    assert (q.qos, q.packet_id, q.retain) == (0, None, False)
    sends = s.deliver_run([(_msg(2), pkt.SubOpts(qos=1)),
                           (_msg(3), pkt.SubOpts(qos=1))])
    assert [(i, qos, pid) for i, _, qos, _, pid in sends] == [(0, 1, 2)]
    assert len(s.mqueue) == 1 and s.deliver(_msg(4), None) == []


# -- the window overflows into the queue, the queue into drops ----------


@VERSIONS
@pytest.mark.parametrize("window,queue", [(4, 5), (1, 1), (3, 1000)])
def test_a_run_overflows_window_then_queue_as_one_by_one(
        version, window, queue):
    items = [(_msg(i, qos=1 + i % 2), pkt.SubOpts(qos=2)) for i in range(14)]
    items.insert(6, (_msg(99, qos=0), pkt.SubOpts(qos=1)))  # QoS0 in place
    dropped = ([], [])
    chans = []
    for k in range(2):
        b = _broker()
        b.hooks.add("message.dropped",
                    lambda m, why, _k=k: dropped[_k].append((m.mid, why)))
        chans.append(_connected(b, version, max_inflight=window,
                                max_mqueue=queue))
    run, one = chans
    assert not run.handle_deliver_run(items)
    for m, o in items:
        one.handle_deliver(m, o)
    assert _state(run) == _state(one)
    assert _out(run) == _reference(items, version, room=window)
    n_drop = max(0, 14 - window - queue)
    assert dropped[0] == dropped[1] and len(dropped[0]) == n_drop
    assert all(why == "queue_full" for _, why in dropped[0])
    for ch in chans:
        assert ch.broker.metrics.get("session.mqueue.dropped") == n_drop
        assert ch.session.mqueue.dropped == n_drop
    # the oldest queued are the ones dropped: the window's overflow first
    assert [mid for mid, _ in dropped[0]] == [
        m.mid for m, _ in [it for it in items if it[0].qos][window:][:n_drop]]


@VERSIONS
def test_a_full_window_queues_the_whole_run_and_sends_only_qos0(version):
    run = _connected(_broker(), version, max_inflight=2)
    first = [(_msg(i), pkt.SubOpts(qos=1)) for i in range(2)]
    run.handle_deliver_run(first)
    before = len(_out(run))
    items = [(_msg(10 + i, qos=i % 2), pkt.SubOpts(qos=1)) for i in range(8)]
    run.handle_deliver_run(items)
    assert _out(run)[before:] == _reference(
        [it for it in items if it[0].qos == 0], version)
    assert len(run.session.mqueue) == 4 and run.session._next_pid == 3


# -- packet ids ----------------------------------------------------------


@VERSIONS
@pytest.mark.parametrize("start", [65533, 65535])
def test_packet_ids_across_the_wrap(version, start):
    """65535 wraps to 1, past every id the window still holds."""
    items = [(_msg(i), pkt.SubOpts(qos=1)) for i in range(6)]
    chans = []
    for _ in range(2):
        ch = _connected(_broker(), version, max_inflight=64)
        for pid in (1, 2, 4):  # still unacknowledged from long ago
            ch.session.inflight.insert(pid, _msg(1000 + pid))
        ch.session._next_pid = start
        chans.append(ch)
    run, one = chans
    run.handle_deliver_run(items)
    for m, o in items:
        one.handle_deliver(m, o)
    pids = [pid for pid, *_ in _window(run.session)][3:]
    assert pids == [p for p in (65533, 65534, 65535, 3, 5, 6, 7, 8)
                    if p >= start or p < 9][:6]
    assert pids == [pid for pid, *_ in _window(one.session)][3:]
    assert run.session._next_pid == one.session._next_pid
    assert _out(run) == _out(one) == _reference(
        items, version, next_pid=start, held=(1, 2, 4))


# -- what has no split frame falls back in its place ---------------------


@VERSIONS
def test_a_sink_without_send_segments_gets_packets_in_order(version):
    items = _mixed(12, PROPS)
    run, one = _both(version, items, sink=PacketSink, max_inflight=64)
    assert _state(run) == _state(one)
    assert _out(run) == _reference(items, version)
    assert run.broker.metrics.get("dispatch.serialize.frames") == 0
    assert run.broker.metrics.get("packets.sent") == 12


@VERSIONS
def test_a_retained_replay_and_an_oversize_topic_fall_back_in_order(version):
    """Neither rides a cached frame: a replay's Message lives as long as
    the store, an oversize topic has no split frame (the codec's error
    closes the connection, as the per-message path's `send_packet` did)."""
    replay = _msg(1, retain=True, headers={"retained": True})
    replay0 = _msg(2, qos=0, retain=True, headers={"retained": True})
    items = [
        (_msg(0), pkt.SubOpts(qos=1)),
        (replay, pkt.SubOpts(qos=1)),
        (_msg(3, qos=2), pkt.SubOpts(qos=2)),
        (replay0, pkt.SubOpts(qos=1)),
        (_msg(4), pkt.SubOpts(qos=1)),
    ]
    run, one = _both(version, items, max_inflight=64)
    assert _state(run) == _state(one)
    assert _out(run) == _reference(items, version)
    assert not hasattr(replay, "_fbq") and not hasattr(replay0, "_fb")
    assert run.broker.metrics.get("dispatch.serialize.frames") == 3
    assert run.broker.metrics.get("packets.sent") == 5

    big = [(_msg(5), pkt.SubOpts(qos=1)),
           (_msg(6, topic="x" * 70000), pkt.SubOpts(qos=1)),
           (_msg(7), pkt.SubOpts(qos=1))]
    run, one = _both(version, big, max_inflight=64)
    assert _state(run) == _state(one)
    assert _out(run) == _reference(big[:1], version)  # then it closed
    assert run.sink._closing and one.sink._closing


# -- hooks ---------------------------------------------------------------


@VERSIONS
def test_hooks_run_once_per_sent_message_and_none_for_a_queued(version):
    """`message.delivered` per sent message, `delivery.completed` per QoS0
    one; STOP ends that message's chain, not the run."""
    seen = {"first": [], "second": [], "done": [], "done2": []}
    chans = []
    for k in range(2):
        b = _broker()

        def first(ci, m, _k=k):
            if _k == 0:
                seen["first"].append(m.mid)
            return STOP if m.payload == b"p3" else None

        def second(ci, m, _k=k):
            if _k == 0:
                seen["second"].append(m.mid)

        def done(ci, m, latency, _k=k):
            if _k == 0:
                seen["done"].append(m.mid)
            assert ci["client_id"] == "c1" and latency >= 0
            return STOP

        b.hooks.add("message.delivered", first, priority=2)
        b.hooks.add("message.delivered", second, priority=1)
        b.hooks.add("delivery.completed", done, priority=2)
        b.hooks.add("delivery.completed",
                    lambda ci, m, lat: seen["done2"].append(m.mid), priority=1)
        chans.append(_connected(b, version, max_inflight=3))
    run, one = chans
    items = [(_msg(i, qos=0 if i in (1, 6) else 1), pkt.SubOpts(qos=1))
             for i in range(8)]
    assert not run.handle_deliver_run(items)
    for m, o in items:
        one.handle_deliver(m, o)
    assert _state(run) == _state(one)
    mids = [m.mid for m, _ in items]
    sent = [mids[i] for i in (0, 1, 2, 3, 6)]  # 4, 5, 7 are queued
    assert seen["first"] == sent
    assert seen["second"] == [m for m in sent if m != mids[3]]
    assert seen["done"] == [mids[1], mids[6]] and seen["done2"] == []


@VERSIONS
def test_a_hook_that_raises_fails_its_delivery_alone(version):
    """As when `handle_deliver` raised for that message: it stays in the
    window unsent, the others leave, and the one-message form raises."""
    b = _broker()

    def bad(ci, m):
        if m.payload == b"p2":
            raise RuntimeError("hook")

    b.hooks.add("message.delivered", bad)
    ch = _connected(b, version, max_inflight=8)
    items = [(_msg(i), pkt.SubOpts(qos=1)) for i in range(4)]
    failed = ch.handle_deliver_run(items)
    assert [i for i, _ in failed] == [2]
    assert isinstance(failed[0][1], RuntimeError)
    assert len(ch.session.inflight) == 4
    assert _out(ch) == b"".join(
        serialize(pkt.Publish(topic=m.topic, payload=m.payload, qos=1,
                              packet_id=i + 1), version)
        for i, (m, _) in enumerate(items) if i != 2)
    with pytest.raises(RuntimeError):
        ch.handle_deliver(_msg(2), pkt.SubOpts(qos=1))


# -- the connection-less window and the mountpoint -------------------------


@VERSIONS
def test_a_run_to_a_disconnected_channel_parks_in_the_queue(version):
    chans = [_connected(_broker(), version, max_mqueue=3) for _ in range(2)]
    items = [(_msg(i, qos=i % 2), pkt.SubOpts(qos=1)) for i in range(10)]
    for ch in chans:
        ch.state = "disconnected"
    run, one = chans
    assert not run.handle_deliver_run(items)
    for m, o in items:
        one.handle_deliver(m, o)
    assert _state(run) == _state(one) and _out(run) == b""
    assert len(run.session.mqueue) == 3
    assert run.broker.metrics.get("session.mqueue.dropped") == 2
    gone = _connected(_broker(), version)
    gone.session = None
    assert not gone.handle_deliver_run(items)


@VERSIONS
def test_a_run_unmounts_on_the_way_out(version):
    items = [(_msg(i, topic=t), pkt.SubOpts(qos=1))
             for i, t in enumerate(["mp/a", "other/b", "mp/c"])]
    run, one = (_connected(_broker(), version) for _ in range(2))
    run.mountpoint = one.mountpoint = "mp/"
    run.handle_deliver_run(items)
    for m, o in items:
        one.handle_deliver(m, o)
    assert _out(run) == _out(one) == _reference(
        [(_msg(i, topic=t), pkt.SubOpts(qos=1))
         for i, t in enumerate(["a", "other/b", "c"])], version)
    assert items[0][0].topic == "mp/a"  # the batch's message is not touched


# -- the broker: collect per connection, hand over after the rows --------


def _results(b, rows, picks=None):
    """A `RouteResult` as the device returns it: `rows[i]` the
    `Subscriber`s whose slots message i matched, `picks[i]` its
    `(real filter, group, member index)` picks."""
    k = max([len(r) for r in rows] + [1])
    slots = np.full((len(rows), k), -1, np.int32)
    for i, r in enumerate(rows):
        slots[i, :len(r)] = [s.slot for s in r]
    pk = None
    if picks is not None:
        p = max([len(r) for r in picks] + [1])
        gid = np.full((len(rows), p), -1, np.int32)
        idx = np.zeros((len(rows), p), np.int32)
        for i, r in enumerate(picks):
            for j, (real, group, member) in enumerate(r):
                gid[i, j] = b.grouptab.gid_of(real, group)
                idx[i, j] = member
        pk = (gid, idx)
    z = np.zeros(len(rows), np.int32)
    return RouteResult(
        matched=np.full((len(rows), 1), -1, np.int32), mcount=z,
        flags=z.copy(), bitmaps=None, picks=pk, slots=slots,
        slot_count=z.copy(), overflow=np.zeros(len(rows), bool),
    )


def _sub(b, sid, filter_):
    group, real = T.parse_share(filter_)
    if group is not None:
        return b.shared.group(real, group).members[sid]
    return b._subs[real][sid]


def _subscribe(b, ch, filter_, **opts):
    o = pkt.SubOpts(**opts)
    b.subscribe(ch.client_id, ch.client_id, filter_, o, ch._make_deliverer(o))
    return _sub(b, ch.client_id, filter_)


def _per_message(b, msgs, results):
    """The per-message path for the same batch: every row dispatched
    without the batch's runs, every delivery a `handle_deliver`."""
    slots = results.slots.tolist()
    out = []
    for i, m in enumerate(msgs):
        pk = None
        if results.picks is not None:
            pk = (results.picks[0][i], results.picks[1][i])
        n = b._dispatch_row(m, None, (), pk, set(), slots=slots[i])
        if n == 0:
            b.hooks.run("message.dropped", m, "no_subscribers")
        out.append(n)
    return out


def _pair(version=V5, **session):
    """Two equal brokers, each with two connected channels."""
    out = []
    for _ in range(2):
        b = _broker()
        b.dropped = []
        b.hooks.add("message.dropped",
                    lambda m, why, _b=b: _b.dropped.append((m.payload, why)))
        a = _connected(b, version, cid="a", max_inflight=256, **session)
        c = _connected(b, version, cid="c", max_inflight=256, **session)
        out.append((b, a, c))
    return out


@VERSIONS
def test_the_deliverer_of_a_channel_offers_a_run_and_a_stub_none(version):
    ch = _connected(_broker(), version)
    assert run_target(ch._make_deliverer(pkt.SubOpts())) is ch
    got = []
    assert run_target(lambda m, o: got.append(m)) is None
    assert run_target(got.append) is None and run_target(print) is None

    def other(msg, _opts):  # closed over the channel, not its deliverer
        ch.sink.send_bytes(b"")

    assert run_target(other) is None


@VERSIONS
def test_two_subscriptions_of_one_connection_keep_message_order(version):
    """The run's key is the connection: its deliveries through different
    subscriptions (each under its own options) stay in message order."""
    (b1, a1, c1), (b2, a2, c2) = _pair(version)
    msgs = [_msg(i, qos=2, topic=("x/1", "x/2", "y")[i % 3], retain=i == 4)
            for i in range(12)]
    outs = []
    for b, a, c in ((b1, a1, c1), (b2, a2, c2)):
        s1 = _subscribe(b, a, "x/+", qos=1)
        s2 = _subscribe(b, a, "x/1", qos=2, retain_as_published=True)
        s3 = _subscribe(b, a, "y", qos=0)
        s4 = _subscribe(b, c, "y", qos=1)
        rows = [{"x/1": [s1, s2], "x/2": [s1], "y": [s3, s4]}[m.topic]
                for m in msgs]
        outs.append((b, _results(b, rows)))
    n1 = b1._dispatch_device_results(msgs, outs[0][1])
    n2 = _per_message(b2, msgs, outs[1][1])
    assert n1 == n2 == [2, 1, 2] * 4
    assert _state(a1) == _state(a2) and _state(c1) == _state(c2)
    want = []
    for m in msgs:
        if m.topic == "x/1":
            want += [(m, pkt.SubOpts(qos=1)),
                     (m, pkt.SubOpts(qos=2, retain_as_published=True))]
        elif m.topic == "x/2":
            want.append((m, pkt.SubOpts(qos=1)))
        else:
            want.append((m, pkt.SubOpts(qos=0)))
    assert _out(a1) == _reference(want, version)
    assert b1.metrics.get("dispatch.runs") == 2
    assert b1.metrics.get("dispatch.run.deliveries") == 20
    assert b1.metrics.get("messages.delivered") == 20
    assert b2.metrics.get("dispatch.runs") == 0


@VERSIONS
def test_a_slot_and_a_share_pick_of_one_message_keep_todays_order(version):
    """A subscriber that holds a slot and is a group member gets both
    deliveries of a message, slot first, message after message."""
    (b1, a1, c1), (b2, a2, c2) = _pair(version)
    msgs = [_msg(i, topic="s/t") for i in range(6)]
    outs = []
    for b, a, c in ((b1, a1, c1), (b2, a2, c2)):
        plain = _subscribe(b, a, "s/t", qos=1)
        _subscribe(b, a, "$share/g/s/t", qos=1)
        _subscribe(b, c, "$share/g/s/t", qos=1)
        g = b.shared.group("s/t", "g")
        g.rr_index = 0
        rows = [[plain]] * 6
        picks = [[("s/t", "g", i % 2)] for i in range(6)]
        outs.append(_results(b, rows, picks))
    n1 = b1._dispatch_device_results(msgs, outs[0])
    n2 = _per_message(b2, msgs, outs[1])
    assert n1 == n2 == [2] * 6
    assert _state(a1) == _state(a2) and _state(c1) == _state(c2)
    o = pkt.SubOpts(qos=1)
    assert _out(a1) == _reference(
        [(m, o) for i, m in enumerate(msgs) for _ in range(2 - i % 2)],
        version)
    assert _out(c1) == _reference([(m, o) for m in msgs[1::2]], version)
    g1, g2 = (b.shared.group("s/t", "g") for b in (b1, b2))
    assert g1.rr_index == g2.rr_index == 6
    assert b1.metrics.get("shared.picks") == 6
    assert b1.metrics.get("shared.picks.stale") == 0
    assert b1.metrics.get("dispatch.run.deliveries") == 12


@VERSIONS
def test_no_local_holds_in_a_run(version):
    (b1, a1, _), (b2, a2, _) = _pair(version)
    msgs = [_msg(i, topic="n/t", from_client="a" if i % 2 else "z")
            for i in range(6)]
    outs = []
    for b, a in ((b1, a1), (b2, a2)):
        s = _subscribe(b, a, "n/t", qos=1, no_local=True)
        outs.append(_results(b, [[s]] * 6))
    n1 = b1._dispatch_device_results(msgs, outs[0])
    assert n1 == _per_message(b2, msgs, outs[1]) == [1, 0] * 3
    assert _state(a1) == _state(a2)
    assert b1.dropped == b2.dropped == [
        (m.payload, "no_subscribers") for m in msgs[1::2]]
    assert b1.metrics.get("messages.dropped.no_subscribers") == 3


@VERSIONS
def test_a_deliverer_without_a_run_is_called_per_message_on_the_spot(version):
    """In row order, before the runs are handed over; in neither counter."""
    b = _broker()
    a = _connected(b, version, cid="a", max_inflight=64)
    s = _subscribe(b, a, "m/t", qos=1)
    calls = []

    def stub(msg, opts):
        calls.append((msg.payload, len(_out(a))))

    b.subscribe("stub", "stub", "m/t", pkt.SubOpts(qos=1), stub)
    msgs = [_msg(i, topic="m/t") for i in range(5)]
    n = b._dispatch_device_results(
        msgs, _results(b, [[s, _sub(b, "stub", "m/t")]] * 5))
    assert n == [2] * 5
    assert calls == [(m.payload, 0) for m in msgs]  # nothing written yet
    assert _out(a) == _reference([(m, pkt.SubOpts(qos=1)) for m in msgs],
                                 version)
    assert b.metrics.get("dispatch.runs") == 1
    assert b.metrics.get("dispatch.run.deliveries") == 5
    assert b.metrics.get("messages.delivered") == 10


@VERSIONS
@pytest.mark.parametrize("how", ["run_raises", "every_delivery_raises"])
def test_a_run_that_raises_gives_its_messages_back(version, how):
    """To the per-message path, before the counts are final: a plain
    subscription's delivery counts as the per-message path leaves it, a
    pick fails over to the next member, and `n`, the PUBACK's reason code
    and `message.dropped` come out as without runs."""
    (b1, a1, c1), (b2, a2, c2) = _pair(version)
    msgs = [_msg(i, topic=("p/t", "q/t")[i % 2]) for i in range(6)]
    outs = []
    for b, a, c in ((b1, a1, c1), (b2, a2, c2)):
        plain = _subscribe(b, a, "p/t", qos=1)
        _subscribe(b, a, "$share/g/q/t", qos=1)
        _subscribe(b, c, "$share/g/q/t", qos=1)
        b.shared.group("q/t", "g").rr_index = 0
        rows = [[plain] if m.topic == "p/t" else [] for m in msgs]
        picks = [[("q/t", "g", 0)] if m.topic == "q/t" else [] for m in msgs]
        outs.append(_results(b, rows, picks))

        def boom(items, _real=a.handle_deliver_run):
            if len(items) > 1 or how != "run_raises":
                raise RuntimeError("gone")
            return _real(items)

        if how == "run_raises":
            if b is b1:
                a.handle_deliver_run = boom  # a batch's run, not one message
        else:
            a.session.deliver_run = boom  # the connection, run or not
    n1 = b1._dispatch_device_results(msgs, outs[0])
    n2 = _per_message(b2, msgs, outs[1])
    if how == "run_raises":
        # one by one the connection takes them: nothing is lost
        assert n1 == n2 == [1] * 6
        assert _out(a1) == _out(a2) != b"" and _out(c1) == b""
        assert b1.metrics.get("delivery.errors") == 0
    else:
        # a's plain deliveries fail, its picks go to the next member
        assert n1 == n2 == [0, 1] * 3
        assert _out(a1) == b"" and _out(c1) == _out(c2) == _reference(
            [(m, pkt.SubOpts(qos=1)) for m in msgs[1::2]], version)
        assert b1.metrics.get("delivery.errors") == 3
        assert b1.dropped == b2.dropped and len(b1.dropped) == 3
    assert _window(c1.session) == _window(c2.session)
    assert b1.metrics.get("dispatch.runs") == 0
    assert b1.metrics.get("dispatch.run.deliveries") == 0
    assert b1.metrics.get("shared.picks.stale") == 0
    g1, g2 = (b.shared.group("q/t", "g") for b in (b1, b2))
    assert g1.rr_index == g2.rr_index == 3
    # the publisher's PUBACK says what `n` says
    for n in (n1[0], n1[1]):
        pub = _connected(_broker(), version, cid="pub")
        pub._send_pub_ack(7, n, pkt.PUBACK)
        rc = pkt.RC_NO_MATCHING_SUBSCRIBERS if (
            n == 0 and version == V5) else pkt.RC_SUCCESS
        assert _out(pub) == serialize(
            pkt.PubAck(packet_id=7, reason_code=rc), version)


@VERSIONS
def test_an_item_that_fails_inside_a_run_is_not_offered_again(version):
    """The run contains it: the others are delivered once, the failed
    pick goes on to the group's next member, the failed plain delivery
    counts as when its deliverer raised."""
    b = _broker()
    a = _connected(b, version, cid="a", max_inflight=64)
    c = _connected(b, version, cid="c", max_inflight=64)

    def bad(ci, m):
        if ci["client_id"] == "a" and m.payload in (b"p2", b"p3"):
            raise RuntimeError("hook")

    b.hooks.add("message.delivered", bad)
    plain = _subscribe(b, a, "p/t", qos=1)
    _subscribe(b, a, "$share/g/q/t", qos=1)
    _subscribe(b, c, "$share/g/q/t", qos=1)
    b.shared.group("q/t", "g").rr_index = 0
    msgs = [_msg(i, topic=("p/t", "q/t")[i % 2]) for i in range(6)]
    rows = [[plain] if m.topic == "p/t" else [] for m in msgs]
    picks = [[("q/t", "g", 0)] if m.topic == "q/t" else [] for m in msgs]
    n = b._dispatch_device_results(msgs, _results(b, rows, picks))
    assert n == [1, 1, 0, 1, 1, 1]
    o = pkt.SubOpts(qos=1)
    assert len(a.session.inflight) == 6  # each taken once, two unsent
    assert _out(c) == _reference([(msgs[3], o)], version)
    assert b.metrics.get("delivery.errors") == 1
    assert b.metrics.get("dispatch.runs") == 1
    assert b.metrics.get("dispatch.run.deliveries") == 4
    assert b.metrics.get("messages.delivered") == 5
    assert b.shared.group("q/t", "g").rr_index == 3


@VERSIONS
def test_a_flagged_row_does_not_overtake_a_pending_run(version):
    """A row the device flags goes the CPU dispatch, on the spot: the
    runs collected so far are handed over first."""
    (b1, a1, _), (b2, a2, _) = _pair(version)
    msgs = [_msg(i, topic="f/t") for i in range(5)]
    outs = []
    for b, a in ((b1, a1), (b2, a2)):
        s = _subscribe(b, a, "f/t", qos=1)
        res = _results(b, [[s]] * 5)
        res.flags[2] = 1
        outs.append(res)
    n1 = b1._dispatch_device_results(msgs, outs[0])
    assert n1 == [1] * 5
    for m in msgs:
        a2.handle_deliver(m, pkt.SubOpts(qos=1))
    assert _state(a1) == _state(a2)
    assert b1.metrics.get("dispatch.runs") == 2
    assert b1.metrics.get("dispatch.run.deliveries") == 4
    assert b1.metrics.get("messages.routed.device_fallback") == 1
    assert b1.metrics.get("messages.delivered") == 5


def test_the_pools_deliverers_get_the_parents_sequence_call_for_call():
    """The pool's deliverers offer no run: `enqueue` sees a message's
    handles adjacent, in row order, and folds them into one outbox record
    per message (not one per delivery)."""
    b = _broker()
    fab = WorkerFabric.__new__(WorkerFabric)
    fab.broker, fab.app = b, types.SimpleNamespace(retainer=None)
    fab._fabric_subs, fab._outbox, fab._outbox_last = {}, {}, {}
    fab._writers, fab._flush_scheduled = {1: object()}, True
    calls = []
    enqueue = fab.enqueue
    fab.enqueue = lambda wid, h, m: (calls.append((h, m.payload)),
                                     enqueue(wid, h, m))[1]
    a = _connected(b, V4, cid="a", max_inflight=256)
    s = _subscribe(b, a, "w/t", qos=1)
    for h in range(1, 9):
        fab._on_sub(1, json.dumps(
            {"h": h, "sid": f"s{h}", "f": "w/t", "qos": 1}).encode())
    pool = [_sub(b, f"w1|s{h}", "w/t") for h in range(1, 9)]
    msgs = [_msg(i, topic="w/t") for i in range(4)]
    # the in-process connection's slot sits among the pool's
    n = b._dispatch_device_results(
        msgs, _results(b, [pool[:4] + [s] + pool[4:]] * 4))
    assert n == [9] * 4
    assert calls == [(h, m.payload) for m in msgs for h in range(1, 9)]
    assert [(m.payload, hs) for m, hs in fab._outbox[1]] == [
        (m.payload, list(range(1, 9))) for m in msgs]
    assert _out(a) == _reference([(m, pkt.SubOpts(qos=1)) for m in msgs], V4)
    assert b.metrics.get("dispatch.run.deliveries") == 4


def test_delivery_runs_settle_counts_only_after_the_hand_over():
    """`DeliveryRuns` alone: collected deliveries count at once, a run
    that gives one back takes it off again."""
    b = _broker()
    a = _connected(b, V4, cid="a")
    s = _subscribe(b, a, "d/t", qos=1)
    runs = DeliveryRuns(b, 3)
    for row in range(3):
        runs.row = row
        assert b._deliver_one(s, _msg(row, topic="d/t"), runs.hand) == 1
        runs.counts[row] += 1
    assert _out(a) == b"" and runs.counts == [1, 1, 1]
    a.session.deliver_run = None  # not callable: the run raises
    runs.deliver()
    assert runs.counts == [0, 0, 0] and runs.pick_stats == [0, 0]
    assert b.metrics.get("delivery.errors") == 3
    runs.deliver()  # nothing pending: nothing happens
    assert b.metrics.get("delivery.errors") == 3
