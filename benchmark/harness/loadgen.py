"""The load generator's processes: jax-free, program-free, one epoll loop
each. Subscriber processes hold a share of the subscriber connections, ack
every QoS1 delivery and record it; publisher processes hold a share of the
publisher connections and run the cell's loop (closed: a fixed number in
flight; open: a schedule of due times, sent whether or not earlier ones were
acknowledged). All clocks are CLOCK_MONOTONIC, comparable across processes
on one host. The driver talks to each over a multiprocessing pipe."""

import array
import os
import select
import time
import zlib

import numpy as np

from harness import mqtt
from harness.traffic import Stream, Table

SETUP_TIMEOUT_S = 900.0


def _cpu_s():
    t = os.times()
    return t.user + t.system


class _Marks:
    """CPU seconds of this process at the window's two edges."""

    def __init__(self):
        self.edges, self.cpu = None, []

    def poll(self, now):
        if self.edges is not None and len(self.cpu) < 2 \
                and now >= self.edges[len(self.cpu)]:
            self.cpu.append(_cpu_s())

    def share(self):
        if self.edges is None or len(self.cpu) < 2:
            return None
        return (self.cpu[1] - self.cpu[0]) / (self.edges[1] - self.edges[0])


def _expect(pipe, what):
    msg = pipe.recv()
    if msg[0] != what:
        raise RuntimeError(f"generator expected {what!r}, got {msg[0]!r}")
    return msg


def subscriber_main(pipe, spec):
    """spec: subs [(index, port)], table spec, seed, qos, client prefix."""
    try:
        _subscriber(pipe, spec)
    except BaseException as e:  # the driver must hear of it, then re-raise
        pipe.send(("error", repr(e)))
        raise


def _subscriber(pipe, spec):
    table = Table(spec["table"], spec["seed"])
    socks = {}
    for s, port in spec["subs"]:
        socks[s] = mqtt.open_connection(port, f"{spec['prefix']}-sub-{s}")
    pipe.send(("connected", len(socks)))
    _expect(pipe, "subscribe")
    t0, n = time.monotonic(), 0
    for s, sock in socks.items():
        fs = table.filters_of(s)
        mqtt.subscribe(sock, fs, spec["qos"], SETUP_TIMEOUT_S)
        n += len(fs)
    pipe.send(("subscribed", n, time.monotonic() - t0))

    ep = select.epoll()
    by_fd, pending = {}, {}
    for s, sock in socks.items():
        sock.settimeout(None)
        by_fd[sock.fileno()] = (s, sock)
        ep.register(sock.fileno(), select.EPOLLIN)
    ep.register(pipe.fileno(), select.EPOLLIN)
    read_t, read_sub, read_n = array.array("d"), array.array("i"), array.array("i")
    seqs, crcs = array.array("q"), array.array("I")
    dup_at = array.array("q")  # indices into seqs of DUP-flagged deliveries
    marks = _Marks()
    crc32 = zlib.crc32
    stop = False
    while not stop:
        events = ep.poll(0.05)
        now = time.monotonic()
        marks.poll(now)
        for fd, _ in events:
            if fd == pipe.fileno():
                msg = pipe.recv()
                if msg[0] == "window":
                    marks.edges = msg[1:3]
                elif msg[0] == "count":
                    pipe.send(("count", len(seqs)))
                elif msg[0] == "stop":
                    stop = True
                continue
            s, sock = by_fd[fd]
            chunk = sock.recv(1 << 18)
            if not chunk:
                raise ConnectionError(f"subscriber {s}: broker closed")
            data = pending.pop(fd, b"") + chunk
            n, i, got = len(data), 0, 0
            acks = bytearray()
            while n - i >= 2:
                b0, rl, j = data[i], data[i + 1], i + 2
                if rl & 0x80:
                    rl &= 0x7F
                    shift = 7
                    while j < n:
                        b = data[j]
                        j += 1
                        rl |= (b & 0x7F) << shift
                        shift += 7
                        if not b & 0x80:
                            break
                    else:
                        break
                end = j + rl
                if end > n:
                    break
                if b0 & 0xF0 == 0x30:
                    t1 = j + 2 + ((data[j] << 8) | data[j + 1])
                    p0 = t1
                    if b0 & 0x06:
                        acks += b"\x40\x02" + data[t1:t1 + 2]
                        p0 += 2
                    if b0 & 0x08:
                        dup_at.append(len(seqs))
                    seqs.append(int.from_bytes(data[p0:p0 + 8], "big"))
                    crcs.append(crc32(data[p0:end], crc32(data[j + 2:t1])))
                    got += 1
                i = end
            if i < n:
                pending[fd] = data[i:]
            if acks:
                sock.sendall(acks)
            if got:
                read_t.append(now)
                read_sub.append(s)
                read_n.append(got)
    pipe.send(("result", {
        "read_t": np.frombuffer(read_t, np.float64),
        "read_sub": np.frombuffer(read_sub, np.int32),
        "read_n": np.frombuffer(read_n, np.int32),
        "seq": np.frombuffer(seqs, np.int64),
        "crc": np.frombuffer(crcs, np.uint32),
        "dup_at": np.frombuffer(dup_at, np.int64),
        "cpu_share": marks.share(),
    }))
    for sock in socks.values():
        sock.close()


def publisher_main(pipe, spec):
    """spec: pubs [(conn, port)], traffic, table spec, seed, client prefix."""
    try:
        _publisher(pipe, spec)
    except BaseException as e:
        pipe.send(("error", repr(e)))
        raise


class _Conn:
    def __init__(self, conn, sock, stream):
        self.conn, self.sock, self.stream = conn, sock, stream
        self.k = 0  # next message
        self.k0 = None  # first message of the open loop
        self.out = {}  # packet id -> k, unacknowledged
        self.send_t, self.ack_t = array.array("d"), {}
        self.buf = b""

    def send(self, count, now):
        packets = []
        for k in range(self.k, self.k + count):
            pid = k % 65535 + 1
            self.out[pid] = k
            packets.append(mqtt.publish_packet(
                self.stream.topic(k), self.stream.payload(k), pid))
            self.send_t.append(now)
        self.k += count
        self.sock.sendall(b"".join(packets))

    def on_readable(self, now):
        chunk = self.sock.recv(1 << 16)
        if not chunk:
            raise ConnectionError(f"publisher {self.conn}: broker closed")
        data = self.buf + chunk
        n = len(data) - len(data) % 4
        for i in range(0, n, 4):
            if data[i] != 0x40 or data[i + 1] != 2:
                raise ConnectionError(
                    f"publisher {self.conn}: not a PUBACK: {data[i:i + 4]!r}")
            self.ack_t[self.out.pop((data[i + 2] << 8) | data[i + 3])] = now
        self.buf = data[n:]


def _publisher(pipe, spec):
    traffic = spec["traffic"]
    table = Table(spec["table"], spec["seed"])
    conns = [
        _Conn(c, mqtt.open_connection(port, f"{spec['prefix']}-pub-{c}"),
              Stream(traffic, table, spec["seed"], c))
        for c, port in spec["pubs"]
    ]
    pipe.send(("connected", len(conns)))
    open_loop = traffic["loop"] == "open"
    steady = 0 if open_loop else traffic["in_flight"]

    ep = select.epoll()
    by_fd = {}
    for c in conns:
        c.sock.settimeout(None)
        by_fd[c.sock.fileno()] = c
        ep.register(c.sock.fileno(), select.EPOLLIN)
    ep.register(pipe.fileno(), select.EPOLLIN)
    marks = _Marks()
    late = array.array("d")  # open loop: actual send minus due, seconds
    # Warm-up: a closed loop at the in-flight count the driver sets (`cap`),
    # stage by stage. Then the plan: t_loop (the cell's own loop starts: the
    # closed loop at the traffic's in_flight, or the open loop's schedule),
    # t_open, t_mark, t_close (nothing is sent from t_close on).
    warm_cap, plan, t_loop = 0, None, None
    stop, drained_sent = False, False
    while not stop:
        now = time.monotonic()
        marks.poll(now)
        wait = 0.05
        if plan is None or now < t_loop:
            cap = warm_cap
        elif now < plan["t_close"]:
            cap = steady
        else:
            cap = None
            if not drained_sent and not any(c.out for c in conns):
                pipe.send(("drained", {c.conn: c.k for c in conns}))
                drained_sent = True
        if cap is not None:
            for c in conns:
                if cap:  # closed loop (the warm-up, and a closed cell's window)
                    room = cap - len(c.out)
                    if room > 0:
                        c.send(room, now)
                elif plan is not None and len(c.out) < 60000:  # 16-bit packet ids
                    if c.k0 is None:
                        c.k0 = c.k
                    due = c.stream.due  # IndexError past the pool: enlarge it
                    k1 = c.k
                    while t_loop + due[k1 - c.k0] <= now:
                        k1 += 1
                    if k1 > c.k:
                        for k in range(c.k, k1):
                            late.append(now - t_loop - due[k - c.k0])
                        c.send(k1 - c.k, now)
                    wait = min(wait, t_loop + due[k1 - c.k0] - now)
        for fd, _ in ep.poll(max(wait, 0.0)):
            if fd == pipe.fileno():
                msg = pipe.recv()
                if msg[0] == "cap":
                    warm_cap = msg[1]
                elif msg[0] == "run":
                    plan = msg[1]
                    t_loop = plan["t_loop"]
                    marks.edges = (plan["t_open"], plan["t_mark"])
                elif msg[0] == "close":  # a traced run ends when its capture does
                    plan["t_close"] = msg[1]
                elif msg[0] == "stop":
                    stop = True
            else:
                by_fd[fd].on_readable(time.monotonic())
    out = {}
    for c in conns:
        ack = np.full(c.k, np.nan)
        for k, t_ack in c.ack_t.items():
            ack[k] = t_ack
        due = None
        if open_loop:  # warm-up messages have no due time
            k0 = c.k if c.k0 is None else c.k0
            due = np.full(c.k, np.nan)
            due[k0:] = t_loop + c.stream.due[:c.k - k0]
        out[c.conn] = {"send_t": np.frombuffer(c.send_t, np.float64),
                       "ack_t": ack, "due_t": due}
        c.sock.close()
    pipe.send(("result", {
        "conns": out, "cpu_share": marks.share(),
        "late_s": np.frombuffer(late, np.float64), "t_loop": t_loop,
    }))
