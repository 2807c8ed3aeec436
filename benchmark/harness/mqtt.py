"""The benchmark's own minimal MQTT 3.1.1 client codec: CONNECT, SUBSCRIBE,
PUBLISH QoS1, PUBACK. Blocking helpers for set-up; the generators parse the
steady stream themselves (loadgen.py). Imports nothing of the program."""

import socket
import struct
import time


def varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _str(s):
    b = s.encode()
    return struct.pack(">H", len(b)) + b


def connect_packet(client_id):
    body = _str("MQTT") + bytes([4, 0x02]) + struct.pack(">H", 0) + _str(client_id)
    return b"\x10" + varint(len(body)) + body


def subscribe_packet(packet_id, filters, qos):
    body = struct.pack(">H", packet_id) + b"".join(
        _str(f) + bytes([qos]) for f in filters)
    return b"\x82" + varint(len(body)) + body


def publish_packet(topic, payload, packet_id, qos=1):
    t = topic.encode()
    body = struct.pack(">H", len(t)) + t
    if qos:
        body += struct.pack(">H", packet_id)
    body += payload
    return bytes([0x30 | (qos << 1)]) + varint(len(body)) + body


def puback_packet(packet_id):
    return b"\x40\x02" + struct.pack(">H", packet_id)


def read_packet(sock, buf):
    """Blocking: -> (first byte, body) of the next whole packet; `buf` (a
    bytearray) keeps what was read beyond it."""
    while True:
        if len(buf) >= 2:
            n, shift, i = 0, 0, 1
            while i < len(buf):
                n |= (buf[i] & 0x7F) << shift
                shift += 7
                i += 1
                if not buf[i - 1] & 0x80:
                    if len(buf) >= i + n:
                        head, body = buf[0], bytes(buf[i:i + n])
                        del buf[:i + n]
                        return head, body
                    break
        chunk = sock.recv(1 << 16)
        if not chunk:
            raise ConnectionError("peer closed during set-up")
        buf += chunk


def open_connection(port, client_id, timeout=120.0):
    """TCP + CONNECT/CONNACK. A `workers` listener binds its SO_REUSEPORT
    sockets after the server prints its row: refused connects are retried."""
    deadline = time.monotonic() + 30.0
    while True:
        try:
            sock = socket.create_connection(("127.0.0.1", port), timeout=timeout)
            break
        except ConnectionRefusedError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.2)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    sock.sendall(connect_packet(client_id))
    buf = bytearray()
    head, body = read_packet(sock, buf)
    if head != 0x20 or body[1] != 0:
        raise ConnectionError(f"{client_id}: CONNACK {head:#x} {body!r}")
    if buf:
        raise ConnectionError(f"{client_id}: bytes after CONNACK")
    return sock


def subscribe(sock, filters, qos, timeout):
    """One multi-filter SUBSCRIBE; every return code must grant `qos`."""
    sock.settimeout(timeout)
    sock.sendall(subscribe_packet(1, filters, qos))
    head, body = read_packet(sock, bytearray())
    codes = body[2:]
    if head != 0x90 or len(codes) != len(filters) or any(c != qos for c in codes):
        raise ConnectionError(f"SUBACK {head:#x} codes {bytes(codes[:8])!r}")
