"""One socket write per connection per batch (transport/connection.py,
transport/egress.py; docs/protocol_plane.md "The in-process sink").

The sink of an in-process `Connection` appends what it serialises and a
batch boundary writes it once. Pinned here, over a recording writer and
over real loopback sockets, by counts and bytes (never a time):

- a chunk of N acks -> one write with the N replacement frames, byte for
  byte what N single writes carried, in order;
- a settled batch touching K connections -> K writes (CPU and device
  path); a publisher's PUBACKs of one batch -> one write;
- a refusing CONNACK, a kick's and a takeover's DISCONNECT reach the peer
  before the FIN;
- a send no boundary covers (a timer's retry) leaves with the turn;
- large segments go out by `writelines`, uncopied, under the iovec limit;
- `_drain` and the congestion alarm see what was pending;
- two packets share one WebSocket frame and an independent client
  parses both.
"""

import asyncio
import functools

import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.channel import ChannelConfig
from emqx_tpu.broker.cm import ChannelManager
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.ingest import BatchIngest
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.router import Router
from emqx_tpu.broker.session import Session, SessionConfig
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.mqtt.frame import Parser, serialize
from emqx_tpu.transport import egress
from emqx_tpu.transport.connection import Connection
from emqx_tpu.transport.listener import ListenerConfig, Listeners
from emqx_tpu.transport.ws import HAVE_WEBSOCKETS

from minimqtt import MiniClient


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **kw):
        asyncio.run(asyncio.wait_for(fn(*a, **kw), timeout=60))

    return wrapper


class Reader:
    """Chunks fed by the test; an empty chunk is the peer's EOF."""

    def __init__(self):
        self.q = asyncio.Queue()

    def feed(self, *packets, version=pkt.MQTT_V4):
        self.q.put_nowait(b"".join(serialize(p, version) for p in packets))

    def eof(self):
        self.q.put_nowait(b"")

    async def read(self, n):
        return await self.q.get()


class Writer:
    """Records each socket write: ("write", bytes) / ("writelines", segs)."""

    def __init__(self):
        self.calls = []
        self.closed_after = None  # number of writes seen when close() came
        self.transport = self

    def get_extra_info(self, key):
        return ("127.0.0.1", 1)

    def write(self, data):
        self.calls.append(("write", data))

    def writelines(self, segs):
        self.calls.append(("writelines", list(segs)))

    def get_write_buffer_size(self):  # the transport's side, for Congestion
        return sum(len(self.data(i)) for i in range(len(self.calls)))

    def data(self, i):
        kind, d = self.calls[i]
        return bytes(d) if kind == "write" else b"".join(bytes(s) for s in d)

    async def drain(self):
        pass

    def close(self):
        if self.closed_after is None:
            self.closed_after = len(self.calls)

    async def wait_closed(self):
        pass


def _puback(pid, type_=pkt.PUBACK):
    p = pkt.PubAck(packet_id=pid)
    p.type = type_
    return p


def _broker(min_batch=10 ** 6):
    return Broker(router=Router(min_tpu_batch=min_batch), hooks=Hooks())


def _connected(broker, cm, version=pkt.MQTT_V4, client_id="c1", **session):
    """A Connection whose channel is past CONNECT, over a fed reader and a
    recording writer."""
    reader, writer = Reader(), Writer()
    conn = Connection(broker, cm, reader, writer, ChannelConfig())
    ch = conn.channel
    ch.state, ch.client_id, ch.version = "connected", client_id, version
    ch.session = Session(client_id, SessionConfig(**session))
    return conn, reader, writer


async def _turns(n=3):
    for _ in range(n):
        await asyncio.sleep(0)


# -- a chunk's run of acks --------------------------------------------------------


@pytest.mark.parametrize("version", [pkt.MQTT_V4, pkt.MQTT_V5])
@pytest.mark.parametrize("n", [1, 3, 32])
def test_a_chunk_of_n_acks_is_one_write_of_the_n_replacements(n, version):
    b = _broker()
    conn, reader, writer = _connected(
        b, ChannelManager(b), version, max_inflight=n, max_mqueue=64)
    ch = conn.channel
    for i in range(2 * n):  # n in flight, n queued behind them
        ch.handle_deliver(
            Message(topic=f"t/{i}", payload=b"p%d" % i, qos=1),
            pkt.SubOpts(qos=1))
    # no loop yet: written through, one write per packet
    assert len(writer.calls) == n and len(ch.session.mqueue) == n
    # what n single writes carry: each replacement serialised alone
    single = [
        serialize(pkt.Publish(topic=f"t/{i}", payload=b"p%d" % i, qos=1,
                              packet_id=i + 1), version)
        for i in range(n, 2 * n)
    ]

    async def main():
        reader.feed(*[_puback(pid) for pid in range(1, n + 1)],
                    version=version)
        reader.eof()
        await conn.run()

    asyncio.run(main())
    assert len(writer.calls) == n + 1
    assert writer.data(n) == b"".join(single)
    assert len(ch.session.mqueue) == 0
    assert b.metrics.get("egress.writes") == n + 1
    assert b.metrics.get("packets.sent") == 2 * n


def _recording_transport(monkeypatch, port_of):
    """Record the selector transport's write()/writelines() calls made on
    the server's side of a loopback connection."""
    from asyncio import selector_events as se

    calls = []
    cls = se._SelectorSocketTransport
    real_write, real_writelines = cls.write, cls.writelines

    def write(self, data):
        if self.get_extra_info("sockname")[1] == port_of():
            calls.append(bytes(data))
        return real_write(self, data)

    def writelines(self, segs):
        segs = list(segs)
        if self.get_extra_info("sockname")[1] == port_of():
            calls.append(b"".join(bytes(s) for s in segs))
        return real_writelines(self, segs)

    monkeypatch.setattr(cls, "write", write)
    monkeypatch.setattr(cls, "writelines", writelines)
    return calls


class Bed:
    """One broker + TCP listener on an ephemeral port."""

    def __init__(self, **session):
        self.broker = Broker(hooks=Hooks())
        self.cm = ChannelManager(self.broker)
        self.listeners = Listeners(self.broker, self.cm)
        self.cfg = ChannelConfig(session=SessionConfig(**session))
        self.port = None

    async def __aenter__(self):
        lsn = await self.listeners.start_listener(
            ListenerConfig(port=0), self.cfg)
        self.port = lsn.port
        return self

    async def __aexit__(self, *exc):
        await self.listeners.stop_all()


async def _raw(port, *packets, version=pkt.MQTT_V4):
    r, w = await asyncio.open_connection("127.0.0.1", port)
    w.write(b"".join(serialize(p, version) for p in packets))
    return r, w


async def _read_packets(r, parser, n, timeout=10):
    out = []
    while len(out) < n:
        data = await asyncio.wait_for(r.read(65536), timeout)
        assert data, f"EOF after {len(out)} of {n} packets"
        out.extend(parser.feed(data))
    return out


def test_over_a_loopback_socket_a_chunk_of_acks_is_one_transport_write(
        monkeypatch):
    n = 8
    bed = Bed(max_inflight=n, max_mqueue=64)
    calls = _recording_transport(monkeypatch, lambda: bed.port)

    @async_test
    async def main():
        async with bed:
            r, w = await _raw(
                bed.port, pkt.Connect(client_id="sub"),
                pkt.Subscribe(packet_id=1,
                              filters=[("t/#", pkt.SubOpts(qos=1))]))
            parser = Parser()
            await _read_packets(r, parser, 2)  # CONNACK, SUBACK
            for i in range(2 * n):
                bed.broker.publish(
                    Message(topic=f"t/{i}", payload=b"p%d" % i, qos=1))
            first = await _read_packets(r, parser, n)
            assert [p.packet_id for p in first] == list(range(1, n + 1))
            before = len(calls)
            # the n PUBACKs in one segment: one read chunk at the broker
            w.write(b"".join(
                serialize(_puback(p.packet_id), pkt.MQTT_V4) for p in first))
            rest = await _read_packets(r, parser, n)
            assert [p.payload for p in rest] == [
                b"p%d" % i for i in range(n, 2 * n)]
            assert calls[before:] == [b"".join(
                serialize(pkt.Publish(topic=f"t/{i}", payload=b"p%d" % i,
                                      qos=1, packet_id=i + 1), pkt.MQTT_V4)
                for i in range(n, 2 * n))]
            w.close()

    main()


# -- a settled batch --------------------------------------------------------------


@pytest.mark.parametrize("path", ["cpu", "device"])
def test_a_settled_batch_touching_k_connections_makes_k_writes(path):
    """K subscribers x M messages through the ingest: K socket writes of M
    frames each, and the publisher's M PUBACKs one more."""
    k, m = 5, 16

    @async_test
    async def main():
        b = _broker(min_batch=8 if path == "device" else 10 ** 6)
        cm = ChannelManager(b)
        ing = BatchIngest(b, max_batch=64, window_us=500)
        b.ingest = ing
        ing.start()
        subs = []
        for i in range(k):
            conn, reader, writer = _connected(
                b, cm, client_id=f"s{i}", max_inflight=64)
            b.subscribe(f"s{i}", f"s{i}", "t/#", pkt.SubOpts(qos=1),
                        conn.channel.handle_deliver)
            subs.append((conn, writer))
        pub, reader, pw = _connected(b, cm, client_id="pub")
        task = asyncio.ensure_future(pub.run())
        if path == "device":  # the compile lands before the counted batch
            await b.apublish(Message(topic="warm/x", payload=b"w"))
            rs = [await b.apublish_enqueue(
                Message(topic=f"warm/{i}", payload=b"w")) for i in range(8)]
            await asyncio.gather(*rs)
        routed = b.metrics.get("messages.routed.device")
        writes0 = b.metrics.get("egress.writes")
        reader.feed(*[
            pkt.Publish(topic=f"t/{i}", payload=b"p%d" % i, qos=1,
                        packet_id=i + 1) for i in range(m)])
        for _ in range(500):
            if len(pw.calls) and all(len(w.calls) for _, w in subs):
                break
            await asyncio.sleep(0.01)
        assert (b.metrics.get("messages.routed.device") - routed
                == (m if path == "device" else 0))
        for _, w in subs:
            assert len(w.calls) == 1
            got = list(Parser().feed(w.data(0)))
            assert [p.payload for p in got] == [b"p%d" % i for i in range(m)]
        # the publisher's PUBACKs of the one batch: one write, in order
        assert len(pw.calls) == 1
        assert pw.data(0) == b"".join(
            serialize(_puback(i + 1), pkt.MQTT_V4) for i in range(m))
        assert b.metrics.get("egress.writes") - writes0 == k + 1
        reader.eof()
        await task
        await ing.stop()

    main()


def test_the_ack_drainer_flushes_before_it_waits_on_an_unsettled_publish():
    """Two batches' worth of pipelined publishes: the first batch's
    PUBACKs leave when the drainer meets the second's unresolved future,
    not when the queue is empty."""

    @async_test
    async def main():
        b = _broker()
        cm = ChannelManager(b)
        conn, reader, writer = _connected(b, cm, client_id="pub")
        loop = asyncio.get_running_loop()
        futs = [loop.create_future() for _ in range(6)]
        it = iter(futs)

        async def enqueue(msg):
            return next(it)

        b.apublish_enqueue = enqueue
        task = asyncio.ensure_future(conn.run())
        reader.feed(*[
            pkt.Publish(topic="t/x", payload=b"p", qos=1, packet_id=i + 1)
            for i in range(6)])
        await _turns(5)
        assert writer.calls == []
        for f in futs[:4]:  # the first batch settles
            f.set_result(1)
        await _turns(5)
        assert len(writer.calls) == 1
        assert writer.data(0) == b"".join(
            serialize(_puback(i + 1), pkt.MQTT_V4) for i in range(4))
        for f in futs[4:]:
            f.set_result(1)
        await _turns(5)
        assert len(writer.calls) == 2
        assert writer.data(1) == b"".join(
            serialize(_puback(i + 1), pkt.MQTT_V4) for i in (4, 5))
        reader.eof()
        await task

    main()


# -- the last packet before the FIN -----------------------------------------------


@pytest.mark.parametrize("case", ["refusing_connack", "kick", "takeover"])
def test_the_last_packet_reaches_the_peer_before_the_fin(case):
    @async_test
    async def main():
        async with Bed() as bed:
            parser = Parser(version=pkt.MQTT_V5)
            if case == "refusing_connack":
                # v3.1.1, no client id, clean_start false: identifier rejected
                r, w = await _raw(bed.port, pkt.Connect(
                    client_id="", clean_start=False,
                    proto_ver=pkt.MQTT_V4))
                parser = Parser()
                want_type, want_rc = pkt.CONNACK, 2
            else:
                r, w = await _raw(bed.port, pkt.Connect(
                    client_id="victim", proto_ver=pkt.MQTT_V5),
                    version=pkt.MQTT_V5)
                (ack,) = await _read_packets(r, parser, 1)
                assert ack.type == pkt.CONNACK and ack.reason_code == 0
                if case == "kick":
                    assert bed.cm.kick_client("victim")
                    want_rc = pkt.RC_ADMINISTRATIVE_ACTION
                else:
                    c2 = MiniClient("victim", version=5, clean=False)
                    await c2.connect("127.0.0.1", bed.port)
                    want_rc = pkt.RC_SESSION_TAKEN_OVER
                want_type = pkt.DISCONNECT
            data = await asyncio.wait_for(r.read(-1), 10)  # up to the FIN
            (last,) = list(parser.feed(data))
            assert last.type == want_type and last.reason_code == want_rc
            w.close()
            if case == "takeover":
                await c2.close()

    main()


def test_close_flushes_then_closes_and_drops_later_sends():
    @async_test
    async def main():
        b = _broker()
        conn, reader, writer = _connected(
            b, ChannelManager(b), version=pkt.MQTT_V5)
        conn.channel._close("boom", pkt.RC_UNSPECIFIED_ERROR)
        # the DISCONNECT was pending when close() came: written first
        assert writer.closed_after == 1
        (d,) = list(Parser(version=pkt.MQTT_V5).feed(writer.data(0)))
        assert d.type == pkt.DISCONNECT
        conn.send_packet(_puback(1))
        conn.flush()
        await _turns()
        assert len(writer.calls) == 1

    main()


def test_a_failing_write_closes_the_connection_once():
    @async_test
    async def main():
        b = _broker()
        conn, reader, writer = _connected(b, ChannelManager(b))

        def boom(data):
            raise OSError("broken pipe")

        writer.write = boom
        conn.send_packet(_puback(1))
        conn.flush()
        assert conn._closing and writer.closed_after == 0
        assert b.metrics.get("egress.writes") == 0

    main()


# -- the safety net ----------------------------------------------------------------


def test_a_timer_s_retry_leaves_with_the_turn_that_made_it():
    """`Channel.tick` from a timer callback: no boundary covers it, the
    loop's end-of-turn call does — the next iteration, no later."""

    @async_test
    async def main():
        b = _broker()
        conn, reader, writer = _connected(
            b, ChannelManager(b), max_inflight=8, retry_interval=0.0)
        ch = conn.channel
        loop = asyncio.get_running_loop()
        for i in range(3):
            ch.handle_deliver(
                Message(topic="t/1", payload=b"%d" % i, qos=1),
                pkt.SubOpts(qos=1))
        await _turns(2)
        assert len(writer.calls) == 1  # the three deliveries: one write
        seen = []
        loop.call_soon(lambda: (ch.tick(), seen.append(len(writer.calls))))
        await asyncio.sleep(0)  # the timer's turn
        assert seen == [1]  # appended, not written inside the callback
        await asyncio.sleep(0)  # the turn after: the end-of-turn flush ran
        assert len(writer.calls) == 2
        dups = list(Parser().feed(writer.data(1)))
        assert [(p.packet_id, p.dup) for p in dups] == [
            (1, True), (2, True), (3, True)]
        assert egress.of(loop).dirty == set()

    main()


def test_without_a_running_loop_the_sink_writes_through():
    b = _broker()
    conn, reader, writer = _connected(b, ChannelManager(b))
    conn.send_packet(_puback(1))
    conn.send_bytes(b"\xd0\x00")
    conn.send_segments([b"\x40\x02", b"\x00\x02"])
    assert [writer.data(i) for i in range(3)] == [
        serialize(_puback(1), pkt.MQTT_V4), b"\xd0\x00", b"\x40\x02\x00\x02"]
    assert conn._pending == []
    assert b.metrics.get("egress.writes") == 3


# -- join or writelines -----------------------------------------------------------


@pytest.mark.parametrize("shape", ["small", "large", "over_iov_max",
                                   "many_small_over_join_max"])
def test_the_flush_joins_small_frames_and_hands_large_segments_uncopied(
        shape, monkeypatch):
    monkeypatch.setattr(Connection, "IOV_MAX", 16)
    big = Connection.JOIN_MAX // 2 + 1
    segs = {
        # 3 split PUBLISHes: head, packet id, tail
        "small": [b"\x32\x10", b"\x00\x01", b"x" * 14] * 3,
        # two frames with big tails: past JOIN_MAX, large on average
        "large": [b"\x32\xff\xff\x03", b"\x00\x01", bytes(big)] * 2,
        # 14 such frames: 42 segments, three slices under the limit
        "over_iov_max": [b"\x32\xff\xff\x03", b"\x00\x01", bytes(big)] * 14,
        # past JOIN_MAX in bytes but tiny on average: one join all the same
        "many_small_over_join_max": [b"y" * 100] * 700,
    }[shape]

    @async_test
    async def main():
        b = _broker()
        conn, reader, writer = _connected(b, ChannelManager(b))
        for i in range(0, len(segs), 3):
            conn.send_segments(segs[i:i + 3])
        assert writer.calls == []
        conn.flush()
        kinds = [k for k, _ in writer.calls]
        if shape in ("small", "many_small_over_join_max"):
            assert kinds == ["write"]
        elif shape == "large":
            assert kinds == ["writelines"]
        else:
            assert kinds == ["writelines"] * 3
        for kind, d in writer.calls:
            if kind == "writelines":
                assert len(d) <= Connection.IOV_MAX
                # uncopied: the very objects the channel handed over
                assert all(any(s is t for t in segs) for s in d)
        assert b"".join(writer.data(i) for i in range(len(kinds))) \
            == b"".join(segs)
        assert b.metrics.get("egress.writes") == len(kinds)

    main()


def test_the_iovec_limit_is_the_transport_s():
    from asyncio import selector_events as se

    assert Connection.IOV_MAX == se.SC_IOV_MAX


# -- backpressure and the congestion alarm ----------------------------------------


def test_drain_and_the_congestion_alarm_see_what_was_pending(monkeypatch):
    from emqx_tpu.transport.congestion import Congestion

    class Alarms:
        def __init__(self):
            self.active = {}

        def activate(self, name, details, message):
            self.active[name] = details

        def deactivate(self, name):
            self.active.pop(name, None)

    @async_test
    async def main():
        b = _broker()
        conn, reader, writer = _connected(b, ChannelManager(b))
        seen_at_drain = []
        real_drain = writer.drain

        async def drain():
            seen_at_drain.append(len(writer.calls))
            await real_drain()

        writer.drain = drain
        conn.send_bytes(b"x" * 4096)
        await conn._drain()
        assert seen_at_drain == [1]  # flushed before the transport drains
        # the tick's congestion check reads the transport's buffer, so
        # what is pending has to be in it. Not connected: the tick itself
        # neither sends nor drains
        conn.channel.state = "disconnected"
        alarms = Alarms()
        conn.congestion = Congestion(
            alarms=alarms, high_watermark=8192, low_watermark=1024)
        conn.send_bytes(b"y" * 8192)
        real_sleep = asyncio.sleep
        naps = []

        async def nap(seconds):  # the tick loop's one pass, without the wait
            naps.append(seconds)
            if len(naps) > 1:
                conn._closing = True

        monkeypatch.setattr(asyncio, "sleep", nap)
        await conn._tick_loop()
        monkeypatch.setattr(asyncio, "sleep", real_sleep)
        assert alarms.active["conn_congestion/c1"]["buffer_bytes"] == 12288

    main()


# -- WebSocket ---------------------------------------------------------------------


@pytest.mark.skipif(not HAVE_WEBSOCKETS, reason="websockets not installed")
def test_two_packets_share_one_websocket_frame_and_minimqtt_parses_them():
    from websockets.asyncio.client import connect as ws_connect

    import minimqtt

    @async_test
    async def main():
        b = Broker(hooks=Hooks())
        listeners = Listeners(b, ChannelManager(b))
        lsn = await listeners.start_listener(
            ListenerConfig(name="w", type="ws", bind="127.0.0.1", port=0),
            ChannelConfig())
        try:
            ws = await ws_connect(
                f"ws://127.0.0.1:{lsn.port}/mqtt", subprotocols=["mqtt"])
            mc = MiniClient("wsc")  # its encoder and decoder, our socket
            body = (minimqtt.utf8("MQTT") + bytes([4, 2]) + b"\x00\x3c"
                    + minimqtt.utf8("wsc"))
            await ws.send(mc._frame(1, 0, body))
            assert (await ws.recv())[0] >> 4 == 2  # CONNACK
            sub = b"\x00\x01" + minimqtt.utf8("t/#") + b"\x01"
            await ws.send(mc._frame(8, 2, sub))
            assert (await ws.recv())[0] >> 4 == 9  # SUBACK
            # one batch, two deliveries to the one WS connection
            b.dispatch_batch_folded([
                Message(topic="t/a", payload=b"one", qos=1),
                Message(topic="t/b", payload=b"two", qos=1)])
            frame = await asyncio.wait_for(ws.recv(), 10)

            # minimqtt's own decoder over the one frame's bytes
            mc.reader = asyncio.StreamReader()
            mc.reader.feed_data(frame)
            mc.reader.feed_eof()
            p1 = await mc._read_packet()
            p2 = await mc._read_packet()
            assert mc.reader.at_eof()
            assert (p1.type, p2.type) == (3, 3)
            assert p1.body.endswith(b"one") and p2.body.endswith(b"two")
            await ws.close()
        finally:
            await listeners.stop_all()

    main()
