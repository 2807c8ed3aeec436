"""Cluster over REAL TCP sockets: in-process pairs and true OS processes.

The cluster once passed tests only on an in-process LocalBus. These tests run the same membership / route
replication / forward / nodedown-GC machinery over `TcpBus` — framed
sockets between two event spaces, including a genuine second OS process
(the reference's docker-compose 2-node FVT analog,
.github/workflows/run_fvt_tests.yaml:47-113).
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time

import pytest

from emqx_tpu.broker.message import Message
from emqx_tpu.cluster.membership import FAILURE_TIMEOUT
from emqx_tpu.cluster.node import ClusterNode
from emqx_tpu.cluster.tcp_transport import RemoteCallError, TcpBus
from emqx_tpu.cluster.transport import NodeUnreachable
from emqx_tpu.mqtt.packet import SubOpts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def collector():
    got = []

    def deliver(msg, opts):
        got.append(msg)

    return got, deliver


def poll(cond, timeout=10.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


# -- raw bus ---------------------------------------------------------------


def test_tcp_bus_call_cast_and_errors():
    a = TcpBus("a@t")
    b = TcpBus("b@t")
    try:
        seen = []

        def handler(frm, payload):
            seen.append((frm, payload))
            if payload == "boom":
                raise ValueError("kaput")
            return ("echo", payload)

        b.attach("b@t", handler)
        a.add_peer("b@t", "127.0.0.1", b.port)

        assert a.send("a@t", "b@t", {"x": 1}) == ("echo", {"x": 1})
        assert a.cast("a@t", "b@t", "fire")
        assert poll(lambda: ("a@t", "fire") in seen)
        with pytest.raises(RemoteCallError, match="kaput"):
            a.send("a@t", "b@t", "boom")
        with pytest.raises(NodeUnreachable):
            a.send("a@t", "nobody@t", 1)
        # per-key channel selection spreads across sockets but stays ordered
        for i in range(20):
            a.send("a@t", "b@t", ("seq", i), channel_key=f"k{i % 4}")
    finally:
        a.stop()
        b.stop()


def test_tcp_bus_reconnects_after_peer_restart():
    a = TcpBus("a@t")
    b = TcpBus("b@t")
    b.attach("b@t", lambda frm, p: p)
    a.add_peer("b@t", "127.0.0.1", b.port)
    assert a.send("a@t", "b@t", 1) == 1
    port = b.port
    b.stop()
    with pytest.raises(NodeUnreachable):
        a.send("a@t", "b@t", 2)
    # peer comes back on the same port
    b2 = TcpBus("b@t", port=port)
    b2.attach("b@t", lambda frm, p: ("again", p))
    try:
        assert poll(
            lambda: _try_send(a, "b@t", 3) == ("again", 3), timeout=5
        )
    finally:
        a.stop()
        b2.stop()


def _try_send(bus, dst, payload):
    try:
        return bus.send(bus.node, dst, payload)
    except NodeUnreachable:
        return None


# -- two ClusterNodes over TCP in one process ------------------------------


@pytest.fixture
def tcp_pair():
    clock = FakeClock()
    bus_a = TcpBus("a@tcp")
    bus_b = TcpBus("b@tcp")
    a = ClusterNode("a@tcp", bus_a, clock=clock, forward_mode="sync")
    b = ClusterNode("b@tcp", bus_b, clock=clock, forward_mode="sync")
    bus_a.add_peer("b@tcp", "127.0.0.1", bus_b.port)
    bus_b.add_peer("a@tcp", "127.0.0.1", bus_a.port)
    assert b.join("a@tcp")
    yield a, b, clock
    for n in (a, b):
        n.rpc.stop()
    bus_a.stop()
    bus_b.stop()


def test_route_replication_and_forward_over_tcp(tcp_pair):
    a, b, _ = tcp_pair
    got, deliver = collector()
    b.subscribe("s1", "c1", "dev/+/temp/#", SubOpts(qos=1), deliver)
    assert poll(lambda: a.routes.has_route("dev/+/temp/#"))
    n = a.publish(Message(topic="dev/3/temp/x", qos=1, payload=b"v"))
    assert n == 1
    assert poll(lambda: len(got) == 1)
    assert got[0].payload == b"v"


def test_unsubscribe_unreplicates_over_tcp(tcp_pair):
    a, b, _ = tcp_pair
    got, deliver = collector()
    b.subscribe("s1", "c1", "u/+", SubOpts(), deliver)
    assert poll(lambda: a.routes.has_route("u/+"))
    assert b.unsubscribe("s1", "u/+")
    assert poll(lambda: not a.routes.has_route("u/+"))
    assert a.publish(Message(topic="u/1")) == 0


def test_nodedown_gc_over_tcp(tcp_pair):
    a, b, clock = tcp_pair
    got, deliver = collector()
    b.subscribe("s1", "c1", "gone/#", SubOpts(), deliver)
    assert poll(lambda: a.routes.has_route("gone/#"))
    # b dies without a goodbye: heartbeats fail, expiry GCs its routes
    b.bus.stop()
    clock.advance(FAILURE_TIMEOUT + 1)
    a.membership.heartbeat()
    assert poll(lambda: not a.routes.has_route("gone/#"), timeout=5)
    assert a.publish(Message(topic="gone/x")) == 0


def test_heartbeat_liveness_is_receipt_confirmed_not_send_confirmed():
    """Root-cause regression for the two-OS-process flake: a cast to a
    freshly-killed TCP peer can 'succeed' (sendall buffers in the
    kernel; the RST arrives after the reader thread notices, which under
    full-suite load can be arbitrarily late). Send-side success must
    therefore NEVER refresh `_last_seen` — only the peer's ack arriving
    may. A bus that accepts every cast but delivers nothing (the
    kernel-buffer race, made deterministic) must still expire the peer."""
    from emqx_tpu.cluster.membership import Membership

    clock = FakeClock()

    class BlackHoleBus:
        """Every send/cast 'succeeds'; nothing is ever delivered."""

        def send(self, src, dst, payload):
            return ["m@bh", "dead@bh"]  # join view

        def cast(self, src, dst, payload):
            return True  # bytes buffered != peer alive

    m = Membership("m@bh", BlackHoleBus(), clock=clock)
    downs = []
    m.monitor(lambda ev, n: downs.append((ev, n)) if ev == "node_down" else None)
    assert m.join("dead@bh")
    assert m.is_alive("dead@bh")
    clock.advance(FAILURE_TIMEOUT + 1)
    m.heartbeat()  # casts "succeed" but no ack ever arrives
    assert not m.is_alive("dead@bh")
    assert ("node_down", "dead@bh") in downs


def test_heartbeat_ack_keeps_live_tcp_peer_alive():
    """The other half of the contract: over a real TcpBus, a live peer's
    ack refreshes `_last_seen`, so advancing the clock past the failure
    timeout does NOT expire a peer that is still answering."""
    clock = FakeClock()
    bus_a = TcpBus("a@hb")
    bus_b = TcpBus("b@hb")
    a = ClusterNode("a@hb", bus_a, clock=clock)
    b = ClusterNode("b@hb", bus_b, clock=clock)
    bus_a.add_peer("b@hb", "127.0.0.1", bus_b.port)
    bus_b.add_peer("a@hb", "127.0.0.1", bus_a.port)
    try:
        assert b.join("a@hb")
        clock.advance(FAILURE_TIMEOUT + 1)
        a.membership.heartbeat()  # ack is async over TCP
        assert poll(lambda: a.membership.is_alive("b@hb"), timeout=5)
        # the refreshed last_seen survives the next expiry sweep
        a.membership.expire()
        assert a.membership.is_alive("b@hb")
    finally:
        for n in (a, b):
            n.rpc.stop()
        bus_a.stop()
        bus_b.stop()


# -- a genuine second OS process -------------------------------------------

CHILD_SCRIPT = r"""
import sys, time
sys.path.insert(0, sys.argv[3])
from emqx_tpu.broker.message import Message
from emqx_tpu.cluster.node import ClusterNode
from emqx_tpu.cluster.tcp_transport import TcpBus
from emqx_tpu.mqtt.packet import SubOpts

parent_port = int(sys.argv[1])
bus = TcpBus("child@proc")
node = ClusterNode("child@proc", bus, forward_mode="sync")
bus.add_peer("parent@proc", "127.0.0.1", parent_port)
print(f"PORT {bus.port}", flush=True)

def deliver(msg, opts):
    node.publish(Message(topic="ack/child", payload=msg.payload))

node.subscribe("s1", "cc", "t/#", SubOpts(), deliver)
assert node.join("parent@proc")
print("READY", flush=True)
while True:
    time.sleep(0.2)
"""


def test_two_os_processes_cluster(tmp_path):
    """Publish on the parent -> forwarded over real TCP to a child process
    -> child publishes an ack back; then kill -9 the child and verify
    heartbeat expiry GCs its routes (emqx_router_helper nodedown parity)."""
    clock = FakeClock()
    bus = TcpBus("parent@proc")
    parent = ClusterNode("parent@proc", bus, clock=clock, forward_mode="sync")
    script = tmp_path / "child.py"
    script.write_text(CHILD_SCRIPT)
    proc = subprocess.Popen(
        [sys.executable, str(script), str(bus.port), "x", REPO],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("PORT "), (line, proc.stderr.read())
        bus.add_peer("child@proc", "127.0.0.1", int(line.split()[1]))
        assert proc.stdout.readline().strip() == "READY"

        got, deliver = collector()
        parent.subscribe("s1", "cp", "ack/child", SubOpts(), deliver)
        assert poll(lambda: parent.routes.has_route("t/#"), timeout=30)

        # exact routes replicate async (dirty-write parity): the child must
        # have ack/child before its ack publish can route back
        def child_has_ack_route():
            try:
                dump = parent.rpc.call("child@proc", "route", "dump")
            except Exception:
                return False
            return any(f == "ack/child" for f, _nodes in dump)

        assert poll(child_has_ack_route, timeout=30)
        parent.publish(Message(topic="t/hello", payload=b"ping"))
        assert poll(lambda: len(got) >= 1, timeout=30)
        assert got[0].payload == b"ping"

        # hard-kill the child: no goodbye, routes must be GC'd on expiry
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        clock.advance(FAILURE_TIMEOUT + 1)
        parent.membership.heartbeat()
        assert poll(lambda: not parent.routes.has_route("t/#"), timeout=15)
        assert parent.publish(Message(topic="t/hello")) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
        parent.rpc.stop()
        bus.stop()


# -- wire-framing round-trip fuzz (PR 19 wire-contract auditor) -----------
# The bus framing (>I length prefix + pickle, cluster.bus.len_prefix in
# emqx_tpu/proto/registry.py) is exercised differentially against a LIVE
# socketpair: whatever `_send_frame` emits — randomized op tags, oversized
# traceparent-carrying Messages, frames delivered in torn 1..7-byte
# slivers — `_recv_frame` must return semantically identical objects, and
# truncation/oversize must fail loudly rather than desync the stream.


def _bus_corpus(rng):
    """Randomized but schema-shaped bus frames: every registered kind
    plus hostile sizes/strings."""
    from emqx_tpu.proto.registry import CLUSTER_BUS_KINDS, MEMBERSHIP_TAGS

    frames = []
    for i in range(40):
        kind = rng.choice(sorted(CLUSTER_BUS_KINDS) + ["hello"])
        rid = rng.randrange(0, 1 << 31)
        if kind == "hello":
            payload = (f"node-{i}", "10.0.0.%d" % rng.randrange(256),
                       rng.randrange(1024, 65536))
        elif rng.random() < 0.5:
            tag = rng.choice(sorted(MEMBERSHIP_TAGS))
            payload = ("membership", tag, {"node": f"n{i}", "epoch": i})
        else:
            # an rpc call shipping an oversized pickled Message with a
            # traceparent header (the cluster-handoff hot case)
            m = Message(
                topic="fuzz/" + "x" * rng.randrange(1, 200),
                payload=rng.randbytes(rng.randrange(1, 1 << 16)),
                qos=rng.randrange(3),
                headers={"traceparent": "00-" + "%032x" % rng.getrandbits(128)
                         + "-" + "%016x" % rng.getrandbits(64) + "-01"},
                mid=i,
                timestamp=1754000000.0 + i,
            )
            payload = ("rpc", "call", "broker", 1, "route_publish", (m,))
        frames.append((kind, rid, payload))
    return frames


def test_bus_framing_roundtrip_fuzz_torn_reads():
    import pickle
    import random
    import socket
    import threading

    from emqx_tpu.cluster.tcp_transport import _recv_frame

    rng = random.Random(0xC0FFEE)
    frames = _bus_corpus(rng)

    a, b = socket.socketpair()
    try:
        # reference bytes: what _send_frame would put on the wire
        wire = bytearray()
        for f in frames:
            blob = pickle.dumps(f, protocol=pickle.HIGHEST_PROTOCOL)
            wire += len(blob).to_bytes(4, "big") + blob

        def drip():
            # torn writes: 1..7-byte slivers so every _recv_exact loop
            # iteration sees a short read at least once
            off = 0
            while off < len(wire):
                n = rng.randrange(1, 8)
                a.sendall(wire[off : off + n])
                off += n

        t = threading.Thread(target=drip, daemon=True)
        t.start()
        for sent in frames:
            got = _recv_frame(b)
            assert got[0] == sent[0] and got[1] == sent[1]
            if got[0] not in ("hello",) and got[2][0] == "rpc":
                gm, sm = got[2][5][0], sent[2][5][0]
                assert gm.topic == sm.topic
                assert gm.payload == sm.payload
                assert gm.headers["traceparent"] == sm.headers["traceparent"]
            else:
                assert got[2] == sent[2]
        t.join(timeout=10)
    finally:
        a.close()
        b.close()


def test_bus_framing_roundtrip_via_send_frame():
    """The actual sender (not a byte-level reimplementation) against the
    actual receiver over a live socketpair."""
    import random
    import socket

    from emqx_tpu.cluster.tcp_transport import _recv_frame, _send_frame

    rng = random.Random(7)
    frames = _bus_corpus(rng)
    a, b = socket.socketpair()
    try:
        a.settimeout(10)
        b.settimeout(10)
        for sent in frames:
            _send_frame(a, sent)
            got = _recv_frame(b)
            assert got[0] == sent[0] and got[1] == sent[1]
    finally:
        a.close()
        b.close()


def test_bus_framing_truncation_and_oversize_fail_loudly():
    import socket
    import struct as _s

    from emqx_tpu.cluster.tcp_transport import MAX_FRAME, _recv_frame

    # truncated body: the prefix promises more than arrives before close
    a, b = socket.socketpair()
    a.sendall(_s.pack(">I", 1000) + b"short")
    a.close()
    try:
        with pytest.raises(ConnectionError):
            _recv_frame(b)
    finally:
        b.close()

    # oversize prefix: refused before any allocation-scale read
    a, b = socket.socketpair()
    a.sendall(_s.pack(">I", MAX_FRAME + 1))
    try:
        with pytest.raises(ConnectionError):
            _recv_frame(b)
    finally:
        a.close()
        b.close()
