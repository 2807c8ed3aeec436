"""Worker-fabric wire protocol: connection workers <-> router process.

The reference scales its connection layer with one BEAM process per
connection inside a single node (emqx_connection.erl:173-176 — the
scheduler spreads them over cores). A Python host gets the same effect
with OS processes: N connection WORKERS own the client sockets (accepting
on a shared SO_REUSEPORT port, one asyncio loop + full Channel/Session
stack each), while the ROUTER process owns the single DeviceRouter and
the subscription tables. This module is the seam between them: a
length-prefixed binary protocol over a unix-domain socket, batched in
both directions so the device batch window keeps its shape.

Frames (all little-endian, u32 length prefix EXCLUDES the 5-byte header):

  [u32 len][u8 type][body]

  HELLO (w->r): u16 worker_id
  SUB   (w->r): json {h, sid, cid, f, qos, nl, rap, rh}
  UNSUB (w->r): json {sid, f}
  PUBB  (w->r): u32 seq, u32 n, n * pub_record
  DLV   (r->w): u32 n, n * dlv_record
  PUBB_ACK (r->w): u32 seq, u32 n, n * i32 delivery_count

A PUBB is acked AFTER the router dispatched (or banked) every message
in it, with per-message delivery counts — the worker-side channel
holds each QoS1/2 client ack on that confirmation, so the at-least-once
boundary sits at the router, not at the worker's socket buffer.

  pub_record: u16 tlen, topic, u32 plen, payload,
              u8 flags (qos | retain<<2 | dup<<3 | has_props<<4),
              u16 clen, from_client,
              [u32 pblen, props_block]           (iff has_props)
  dlv_record: u16 tlen, topic, u32 plen, payload,
              u8 flags (pub qos | retain<<2 | retained<<3 |
                        has_props<<4),
              u16 clen, from_client,
              [u32 pblen, props_block],          (iff has_props)
              u16 ntargets, ntargets * u32 handle

props_block is the MQTT5 encoded property block (frame.encode_properties
output) — v5 publish properties survive the worker fabric end to end.

A delivery record carries the message ONCE per worker; per-subscription
QoS downgrade happens worker-side in the Session (same code path as the
in-process broker), so the router serializes each matched message once
per worker, not once per subscriber.
"""

from __future__ import annotations

import json
import os
import struct
from typing import Iterable, List, Tuple

import numpy as np

T_HELLO = 0
T_SUB = 1
T_UNSUB = 2
T_PUBB = 3
T_DLV = 4
T_PUBB_ACK = 5
# SUB confirm (router -> worker, body = json {h}): sent after the
# router registered the subscription + enqueued retained replay. The
# worker holds the client's SUBACK on it, so SUBACK keeps the
# reference's meaning — the subscription is ROUTABLE, broker-wide
# (emqx_broker.erl:127-160 is synchronous for the same reason).
T_SUB_ACK = 6
# RAW delivery (r->w): pre-serialized MQTT PUBLISH frames for the QoS0
# fast lane — the router serializes once per (message, version, retain)
# and the worker writes the bytes straight to subscriber sockets,
# bypassing the per-delivery Channel/Session work (eligibility is
# negotiated per subscription via the SUB json's "fl" field: qos 0, no
# mountpoint, empty delivered/completed hook chains worker-side).
#   body: u32 n, n * (u32 blen, frame_bytes, u16 nh, nh * u32 handle)
T_RAW = 8
# Slab twins of PUBB/DLV (see "slab codec" below): same record fields,
# but all fixed headers land in ONE contiguous table followed by the
# variable regions (topics, payloads, clients, props[, handles]) each
# concatenated — so the receiver recovers every record offset/length
# with a handful of vectorized numpy passes and hands out memoryviews
# into the ONE read buffer instead of materializing per-record tuples.
T_PUBB_S = 9
T_DLV_S = 10
# Session ops (json, both directions): the router brokers emqx_cm
# semantics ACROSS workers — open (w->r: resolve takeover/resume at
# CONNECT), take/discard (r->w: hand over / kill a live channel),
# state (w->r: serialized session after take), open_ack (r->w),
# park (w->r: disconnect with expiry>0 -> router-side detached store,
# WAL-backed when persistence is on), resume_done (w->r: new channel
# installed; router flushes handoff-banked messages), closed (w->r).
T_SESS = 7

_HDR = struct.Struct("<IB")
_U16 = struct.Struct("<H")
_U32 = struct.Struct("<I")

MAX_FRAME = 64 * 1024 * 1024
# soft per-frame body cap for senders: batches above this split into
# multiple frames so a large tick (pipelined max-size publishes, a huge
# fan-out delivery flush) can never hit the receiver's MAX_FRAME reject,
# which would tear down the whole fabric link
MAX_BODY = 8 * 1024 * 1024


def pack_frame(ftype: int, body: bytes) -> bytes:
    return _HDR.pack(len(body), ftype) + body


def pub_record_size(m) -> int:
    """Serialized size of one pub_record (sender-side chunking). Props
    count too: a batch of props-carrying max-size publishes sized only
    by topic+payload could exceed the receiver's MAX_FRAME and tear the
    fabric link."""
    props = getattr(m, "properties", None)
    return (
        9
        + len(m.topic.encode())
        + len(m.payload or b"")
        + len((m.from_client or "").encode())
        + ((4 + len(_encode_props(props))) if props else 0)
    )


def pack_json(ftype: int, obj) -> bytes:
    return pack_frame(ftype, json.dumps(obj).encode())


def _encode_props(props) -> bytes:
    from emqx_tpu.mqtt.frame import encode_properties

    return encode_properties(props)


def _decode_props(blob: bytes):
    from emqx_tpu.mqtt.frame import decode_properties

    props, _off = decode_properties(blob, 0)
    return props


def pack_pub_batch(msgs, seq: int = 0) -> bytes:
    """msgs: iterable of Message."""
    parts = [b""]
    n = 0
    for m in msgs:
        t = m.topic.encode()
        p = m.payload or b""
        c = (m.from_client or "").encode()
        props = getattr(m, "properties", None)
        flags = (m.qos & 3) | (4 if m.retain else 0) | (
            8 if getattr(m, "dup", False) else 0
        ) | (0x10 if props else 0)
        rec = (
            _U16.pack(len(t)) + t + _U32.pack(len(p)) + p
            + bytes([flags]) + _U16.pack(len(c)) + c
        )
        if props:
            pb = _encode_props(props)
            rec += _U32.pack(len(pb)) + pb
        parts.append(rec)
        n += 1
    parts[0] = _U32.pack(seq) + _U32.pack(n)
    return pack_frame(T_PUBB, b"".join(parts))


def unpack_pub_batch(body: bytes):
    """-> (seq, [(topic, payload, qos, retain, dup, from_client,
    props | None)])"""
    (seq,) = _U32.unpack_from(body, 0)
    (n,) = _U32.unpack_from(body, 4)
    off = 8
    out = []
    for _ in range(n):
        (tl,) = _U16.unpack_from(body, off)
        off += 2
        topic = body[off : off + tl].decode()
        off += tl
        (pl,) = _U32.unpack_from(body, off)
        off += 4
        payload = body[off : off + pl]
        off += pl
        flags = body[off]
        off += 1
        (cl,) = _U16.unpack_from(body, off)
        off += 2
        client = body[off : off + cl].decode()
        off += cl
        props = None
        if flags & 0x10:
            (pbl,) = _U32.unpack_from(body, off)
            off += 4
            props = _decode_props(body[off : off + pbl])
            off += pbl
        out.append(
            (topic, payload, flags & 3, bool(flags & 4), bool(flags & 8),
             client, props)
        )
    return seq, out


def pack_pub_ack(seq: int, counts) -> bytes:
    return pack_frame(
        T_PUBB_ACK,
        _U32.pack(seq) + _U32.pack(len(counts))
        + struct.pack(f"<{len(counts)}i", *counts),
    )


def unpack_pub_ack(body: bytes):
    (seq,) = _U32.unpack_from(body, 0)
    (n,) = _U32.unpack_from(body, 4)
    return seq, list(struct.unpack_from(f"<{n}i", body, 8))


def pack_dlv_batches(records, max_body: float = MAX_BODY):
    """records: [(msg, [handle, ...])] -> yields one or more DLV frames,
    each body bounded by ~max_body (always at least one record per
    frame), so a huge delivery tick can't exceed the receiver's
    MAX_FRAME and tear the fabric link."""
    out = bytearray(9)  # frame header (5) + count (4), patched below
    n = 0
    for m, handles in records:
        t = m.topic.encode()
        p = m.payload or b""
        c = (m.from_client or "").encode()
        props = getattr(m, "properties", None)
        flags = (m.qos & 3) | (4 if m.retain else 0) | (
            8 if m.headers.get("retained") else 0
        ) | (0x10 if props else 0)
        head = (
            _U16.pack(len(t)) + t + _U32.pack(len(p)) + p
            + bytes([flags]) + _U16.pack(len(c)) + c
        )
        if props:
            pb = _encode_props(props)
            head += _U32.pack(len(pb)) + pb
        # ntargets is u16: split monster fan-outs across records rather
        # than raise mid-flush (a 10M-sub broker CAN put >65535 matching
        # subscriptions on one worker)
        for lo in range(0, len(handles), 0xFFFF):
            chunk = handles[lo : lo + 0xFFFF]
            rec_len = len(head) + 2 + 4 * len(chunk)
            if n and len(out) + rec_len > max_body:
                out[0:5] = _HDR.pack(len(out) - 5, T_DLV)
                out[5:9] = _U32.pack(n)
                yield bytes(out)
                out = bytearray(9)
                n = 0
            out += head
            out += _U16.pack(len(chunk))
            out += struct.pack(f"<{len(chunk)}I", *chunk)
            n += 1
    if n:
        out[0:5] = _HDR.pack(len(out) - 5, T_DLV)
        out[5:9] = _U32.pack(n)
        yield bytes(out)


def pack_dlv_batch(records) -> bytes:
    """Single-frame variant (tests / small ticks)."""
    frames = list(pack_dlv_batches(records, max_body=float("inf")))
    return frames[0] if frames else pack_frame(T_DLV, _U32.pack(0))


def unpack_dlv_batch(body: bytes):
    """-> [(topic, payload, qos, retain, retained, from_client,
    props | None, [handles])]"""
    (n,) = _U32.unpack_from(body, 0)
    off = 4
    out = []
    for _ in range(n):
        (tl,) = _U16.unpack_from(body, off)
        off += 2
        topic = body[off : off + tl].decode()
        off += tl
        (pl,) = _U32.unpack_from(body, off)
        off += 4
        payload = body[off : off + pl]
        off += pl
        flags = body[off]
        off += 1
        (cl,) = _U16.unpack_from(body, off)
        off += 2
        client = body[off : off + cl].decode()
        off += cl
        props = None
        if flags & 0x10:
            (pbl,) = _U32.unpack_from(body, off)
            off += 4
            props = _decode_props(body[off : off + pbl])
            off += pbl
        (nh,) = _U16.unpack_from(body, off)
        off += 2
        handles = list(struct.unpack_from(f"<{nh}I", body, off))
        off += 4 * nh
        out.append(
            (topic, payload, flags & 3, bool(flags & 4), bool(flags & 8),
             client, props, handles)
        )
    return out


# -- native acceleration ------------------------------------------------
# The C codec (mqtt/_codec.c) implements the same wire format; the pure-
# Python functions above stay the semantic reference and differentially
# test it (tests/test_codec_native.py). Packing DLV batches in Python
# was the largest router-process cost in the serving profile.
from emqx_tpu.mqtt import codec_native as _nc  # noqa: E402

_py_pack_dlv_batches = pack_dlv_batches
_py_pack_pub_batch = pack_pub_batch
_py_unpack_pub_batch = unpack_pub_batch
_py_unpack_dlv_batch = unpack_dlv_batch

if _nc.pack_dlv_frames is not None:

    def pack_dlv_batches(records, max_body: float = MAX_BODY):  # noqa: F811
        if max_body == float("inf"):
            max_body = 1 << 62
        if not isinstance(records, list):
            records = list(records)
        if any(getattr(m, "properties", None) for m, _h in records):
            # props-carrying batches take the (rarer) Python packer;
            # the C packer handles the propless hot path
            return _py_pack_dlv_batches(records, max_body)
        return _nc.pack_dlv_frames(records, int(max_body))

    def pack_pub_batch(msgs, seq: int = 0) -> bytes:  # noqa: F811
        if not isinstance(msgs, list):
            msgs = list(msgs)
        if any(getattr(m, "properties", None) for m in msgs):
            return _py_pack_pub_batch(msgs, seq)
        return _nc.pack_pub_batch(msgs, seq)

    def unpack_pub_batch(body: bytes):  # noqa: F811
        seq, recs = _nc.unpack_pub_batch(body)
        # the C layer returns the raw props block (or None); decode here
        return seq, [
            r if r[6] is None else r[:6] + (_decode_props(r[6]),)
            for r in recs
        ]

    def unpack_dlv_batch(body: bytes):  # noqa: F811
        return [
            r if r[6] is None else r[:6] + (_decode_props(r[6]), r[7])
            for r in _nc.unpack_dlv_batch(body)
        ]


def pack_raw_batches(records, max_body: float = MAX_BODY):
    """records: [(frame_bytes, [handle, ...])] -> one or more T_RAW
    frames, each body bounded by ~max_body."""
    out = bytearray(9)
    n = 0
    for buf, handles in records:
        # nh is u16: split monster fan-outs across records (same rule
        # as pack_dlv_batches — a 10M-sub broker CAN put >65535
        # matching subscriptions on one worker)
        for lo in range(0, len(handles), 0xFFFF):
            chunk = handles[lo : lo + 0xFFFF]
            rec_len = 4 + len(buf) + 2 + 4 * len(chunk)
            if n and len(out) + rec_len > max_body:
                out[0:5] = _HDR.pack(len(out) - 5, T_RAW)
                out[5:9] = _U32.pack(n)
                yield bytes(out)
                out = bytearray(9)
                n = 0
            out += _U32.pack(len(buf))
            out += buf
            out += _U16.pack(len(chunk))
            out += struct.pack(f"<{len(chunk)}I", *chunk)
            n += 1
    if n:
        out[0:5] = _HDR.pack(len(out) - 5, T_RAW)
        out[5:9] = _U32.pack(n)
        yield bytes(out)


def unpack_raw_batch(body: bytes):
    """-> [(frame_bytes, [handles])]"""
    (n,) = _U32.unpack_from(body, 0)
    off = 4
    out = []
    for _ in range(n):
        (bl,) = _U32.unpack_from(body, off)
        off += 4
        buf = body[off : off + bl]
        off += bl
        (nh,) = _U16.unpack_from(body, off)
        off += 2
        handles = list(struct.unpack_from(f"<{nh}I", body, off))
        off += 4 * nh
        out.append((buf, handles))
    return out


# -- slab codec ---------------------------------------------------------
# The slab wire format is the protocol-plane fast path (ROADMAP item 1,
# docs/protocol_plane.md): one fixed-size header TABLE up front, then
# each variable field concatenated into its own contiguous REGION:
#
#   PUBB_S body: u32 seq, u32 n, n * pub_hdr(13B),
#                topics | payloads | clients | props
#   DLV_S  body: u32 n, n * dlv_hdr(17B),
#                topics | payloads | clients | props | handles(u32 LE)
#
#   pub_hdr: u16 tlen, u32 plen, u16 clen, u32 pblen, u8 flags
#   dlv_hdr: pub_hdr + u32 nh          (flags bits as the legacy records)
#
# Unpacking is a vectorized fixed-header scan: ONE np.frombuffer over
# the header table, four/five cumsums for the region offsets — no
# per-record struct.unpack, no per-record tuple. Accessors hand out
# memoryview/ndarray slices into the ONE read buffer; str decode and
# payload copies happen lazily at the consumer (broker/message.py
# SlabMessage), which is the zero-copy ingest contract. Packing builds
# the header table with vectorized numpy writes into a preallocated
# slab and joins each region once; DLV frame splitting slices the
# once-built regions, so a record straddling MAX_BODY is NEVER
# re-serialized for the next frame.

PUB_HDR_DT = np.dtype(
    [("tlen", "<u2"), ("plen", "<u4"), ("clen", "<u2"),
     ("pblen", "<u4"), ("flags", "u1")]
)  # itemsize 13
DLV_HDR_DT = np.dtype(
    [("tlen", "<u2"), ("plen", "<u4"), ("clen", "<u2"),
     ("pblen", "<u4"), ("flags", "u1"), ("nh", "<u4")]
)  # itemsize 17

# senders emit slab frames by default; the env kill-switch drops the
# whole fabric back to the per-record wire (both receivers always
# accept both — the differential tests and codec microbench rely on it)
SLAB_WIRE = os.environ.get("EMQX_TPU_NO_SLAB_FABRIC") != "1"
# slab DLV records chunk monster fan-outs so one record stays far below
# MAX_FRAME (the legacy u16 ntargets cap is gone — nh is u32)
SLAB_HANDLE_CHUNK = 1 << 20


def _region_offsets(base: int, lens: np.ndarray) -> np.ndarray:
    """-> int64 [n+1] absolute offsets: base + exclusive cumsum(lens)."""
    off = np.empty(len(lens) + 1, np.int64)
    off[0] = base
    np.cumsum(lens, out=off[1:])
    off[1:] += base
    return off


class _Slab:
    """Shared accessor base over one contiguous frame body."""

    __slots__ = (
        "n", "buf", "flat", "flags", "t_off", "t_len", "p_off", "p_len",
        "c_off", "c_len", "pb_off", "pb_len", "_ll",
    )

    def _init_regions(self, body, hdr, base: int) -> None:
        # the slab accessor IS the buffer's holder, not a borrower:
        # ownership transfers downstream via SlabMessage.own_buffers()
        # at the annotated escape sinks
        self.buf = memoryview(body)  # lint: disable=BV001
        self.flat = np.frombuffer(body, np.uint8)
        self.flags = hdr["flags"]
        self.t_len = hdr["tlen"].astype(np.int64)
        self.p_len = hdr["plen"].astype(np.int64)
        self.c_len = hdr["clen"].astype(np.int64)
        self.pb_len = hdr["pblen"].astype(np.int64)
        self.t_off = _region_offsets(base, self.t_len)
        self.p_off = _region_offsets(int(self.t_off[-1]), self.p_len)
        self.c_off = _region_offsets(int(self.p_off[-1]), self.c_len)
        self.pb_off = _region_offsets(int(self.c_off[-1]), self.pb_len)
        self._ll = None  # lazy plain-int offset lists (accessor path)

    def _lists(self):
        """Plain-int twins of the offset/length arrays, built ONCE on
        first per-record access (numpy scalar indexing costs ~5x a list
        index on the accessor path; the pure-scan consumers never pay
        this)."""
        ll = self._ll
        if ll is None:
            ll = self._ll = (
                self.t_off.tolist(), self.t_len.tolist(),
                self.p_off.tolist(), self.p_len.tolist(),
                self.c_off.tolist(), self.c_len.tolist(),
                self.pb_off.tolist(), self.pb_len.tolist(),
            )
        return ll

    def topic_bytes(self, i: int) -> memoryview:
        ll = self._lists()
        o = ll[0][i]
        return self.buf[o : o + ll[1][i]]

    def topic(self, i: int) -> str:
        return str(self.topic_bytes(i), "utf-8")

    def payload_view(self, i: int) -> memoryview:
        ll = self._lists()
        o = ll[2][i]
        return self.buf[o : o + ll[3][i]]

    def client(self, i: int) -> str:
        ll = self._lists()
        o = ll[4][i]
        return str(self.buf[o : o + ll[5][i]], "utf-8")

    def props(self, i: int):
        if not (int(self.flags[i]) & 0x10):
            return None
        ll = self._lists()
        o = ll[6][i]
        return _decode_props(bytes(self.buf[o : o + ll[7][i]]))

    def topic_refs(self):
        """-> (flat uint8 [body], t_off int64 [n], t_len int64 [n]) —
        the tokenizer's bulk-gather inputs (ops/tokenizer.encode_topics
        slab fast path)."""
        return self.flat, self.t_off[:-1], self.t_len


class PubSlab(_Slab):
    """Vectorized view over one T_PUBB_S body."""

    __slots__ = ("seq",)

    def __init__(self, body):
        (seq,) = _U32.unpack_from(body, 0)
        (n,) = _U32.unpack_from(body, 4)
        self.seq = seq
        self.n = n
        hdr = np.frombuffer(body, PUB_HDR_DT, count=n, offset=8)
        self._init_regions(body, hdr, 8 + PUB_HDR_DT.itemsize * n)
        if int(self.pb_off[-1]) != len(body):
            raise ValueError("slab pub frame length mismatch")

    def record(self, i: int):
        """Legacy per-record tuple (differential tests / compat)."""
        f = int(self.flags[i])
        return (
            self.topic(i), bytes(self.payload_view(i)), f & 3,
            bool(f & 4), bool(f & 8), self.client(i), self.props(i),
        )

    def records(self) -> List:
        return [self.record(i) for i in range(self.n)]


class DlvSlab(_Slab):
    """Vectorized view over one T_DLV_S body."""

    __slots__ = ("h_off", "h_len", "_handles")

    def __init__(self, body):
        (n,) = _U32.unpack_from(body, 0)
        self.n = n
        hdr = np.frombuffer(body, DLV_HDR_DT, count=n, offset=4)
        self._init_regions(body, hdr, 4 + DLV_HDR_DT.itemsize * n)
        self.h_len = hdr["nh"].astype(np.int64)
        self.h_off = _region_offsets(0, self.h_len)  # element offsets
        hbase = int(self.pb_off[-1])
        nh_total = int(self.h_off[-1])
        if hbase + 4 * nh_total != len(body):
            raise ValueError("slab dlv frame length mismatch")
        self._handles = np.frombuffer(
            body, "<u4", count=nh_total, offset=hbase
        )

    def handles(self, i: int) -> np.ndarray:
        return self._handles[int(self.h_off[i]) : int(self.h_off[i + 1])]

    def record(self, i: int):
        f = int(self.flags[i])
        return (
            self.topic(i), bytes(self.payload_view(i)), f & 3,
            bool(f & 4), bool(f & 8), self.client(i), self.props(i),
            self.handles(i).tolist(),
        )

    def records(self) -> List:
        return [self.record(i) for i in range(self.n)]


def unpack_pub_slab(body) -> PubSlab:
    return PubSlab(body)


def unpack_dlv_slab(body) -> DlvSlab:
    return DlvSlab(body)


def _msg_fields(m, dlv: bool):
    """One record's serialized pieces (shared by both slab packers)."""
    tb = getattr(m, "topic_bytes", None)
    t = tb() if tb is not None else m.topic.encode()
    pv = getattr(m, "payload_view", None)
    p = pv() if pv is not None else (m.payload or b"")
    c = (m.from_client or "").encode()
    props = getattr(m, "properties", None)
    flags = (m.qos & 3) | (4 if m.retain else 0) | (0x10 if props else 0)
    if dlv:
        flags |= 8 if m.headers.get("retained") else 0
    else:
        flags |= 8 if getattr(m, "dup", False) else 0
    pb = _encode_props(props) if props else b""
    return t, p, c, pb, flags


def pack_pub_slab(msgs, seq: int = 0) -> bytes:
    """Slab twin of pack_pub_batch: ONE T_PUBB_S frame, header table
    written vectorized, each region joined once."""
    if not isinstance(msgs, list):
        msgs = list(msgs)
    n = len(msgs)
    ts: List = []
    ps: List = []
    cs: List = []
    pbs: List = []
    flags = bytearray(n)
    for i, m in enumerate(msgs):
        t, p, c, pb, f = _msg_fields(m, dlv=False)
        ts.append(t)
        ps.append(p)
        cs.append(c)
        pbs.append(pb)
        flags[i] = f
    tl = np.fromiter(map(len, ts), np.int64, n)
    pl = np.fromiter(map(len, ps), np.int64, n)
    cl = np.fromiter(map(len, cs), np.int64, n)
    pbl = np.fromiter(map(len, pbs), np.int64, n)
    body_len = 8 + PUB_HDR_DT.itemsize * n + int(tl.sum() + pl.sum()
                                                 + cl.sum() + pbl.sum())
    out = bytearray(5 + body_len)
    _HDR.pack_into(out, 0, body_len, T_PUBB_S)
    _U32.pack_into(out, 5, seq)
    _U32.pack_into(out, 9, n)
    hdr = np.frombuffer(out, PUB_HDR_DT, count=n, offset=13)
    hdr["tlen"] = tl
    hdr["plen"] = pl
    hdr["clen"] = cl
    hdr["pblen"] = pbl
    hdr["flags"] = np.frombuffer(flags, np.uint8)
    pos = 13 + PUB_HDR_DT.itemsize * n
    for region in (ts, ps, cs, pbs):
        blob = b"".join(region)
        out[pos : pos + len(blob)] = blob
        pos += len(blob)
    return bytes(out)


def pack_dlv_slabs(records, max_body: float = MAX_BODY):
    """Slab twin of pack_dlv_batches: every record's pieces are
    serialized ONCE into shared region buffers; MAX_BODY splitting then
    slices those regions per frame — a record straddling the cap moves
    to the next frame as slices, never re-serialized (the legacy
    packer's retry-path property, now structural)."""
    ts: List = []
    ps: List = []
    cs: List = []
    pbs: List = []
    flags_l: List[int] = []
    hl: List = []
    for m, handles in records:
        if not len(handles):
            continue  # no targets: nothing on the wire (legacy parity)
        t, p, c, pb, f = _msg_fields(m, dlv=True)
        ha = np.asarray(handles, "<u4")
        # split monster fan-outs so one record can never approach
        # MAX_FRAME (nh is u32; the chunk bound replaces the u16 cap)
        for lo in range(0, len(ha), SLAB_HANDLE_CHUNK):
            ts.append(t)
            ps.append(p)
            cs.append(c)
            pbs.append(pb)
            flags_l.append(f)
            hl.append(ha[lo : lo + SLAB_HANDLE_CHUNK])
    n = len(ts)
    if not n:
        return
    tl = np.fromiter(map(len, ts), np.int64, n)
    pl = np.fromiter(map(len, ps), np.int64, n)
    cl = np.fromiter(map(len, cs), np.int64, n)
    pbl = np.fromiter(map(len, pbs), np.int64, n)
    nh = np.fromiter(map(len, hl), np.int64, n)
    hdr_all = np.zeros(n, DLV_HDR_DT)
    hdr_all["tlen"] = tl
    hdr_all["plen"] = pl
    hdr_all["clen"] = cl
    hdr_all["pblen"] = pbl
    hdr_all["flags"] = np.asarray(flags_l, np.uint8)
    hdr_all["nh"] = nh
    hdr_bytes = hdr_all.tobytes()
    regions = [b"".join(r) for r in (ts, ps, cs, pbs)]
    handles_bytes = (
        np.concatenate(hl).tobytes() if hl else b""
    )
    # region element offsets (per record), for per-frame slicing
    tco = _region_offsets(0, tl)
    pco = _region_offsets(0, pl)
    cco = _region_offsets(0, cl)
    pbco = _region_offsets(0, pbl)
    hco = _region_offsets(0, nh)
    rec_size = (DLV_HDR_DT.itemsize + tl + pl + cl + pbl + 4 * nh)
    csum = _region_offsets(0, rec_size)
    if max_body == float("inf"):
        max_body = 1 << 62
    i = 0
    while i < n:
        j = int(
            np.searchsorted(csum, csum[i] + int(max_body) - 9, side="right")
        ) - 1
        j = min(max(j, i + 1), n)
        parts = [
            b"",  # frame header patched below
            _U32.pack(j - i),
            hdr_bytes[DLV_HDR_DT.itemsize * i : DLV_HDR_DT.itemsize * j],
            regions[0][int(tco[i]) : int(tco[j])],
            regions[1][int(pco[i]) : int(pco[j])],
            regions[2][int(cco[i]) : int(cco[j])],
            regions[3][int(pbco[i]) : int(pbco[j])],
            handles_bytes[4 * int(hco[i]) : 4 * int(hco[j])],
        ]
        body_len = sum(len(x) for x in parts)
        parts[0] = _HDR.pack(body_len, T_DLV_S)
        yield b"".join(parts)
        i = j


def unpack_pub_frame(frame: bytes):
    """Whole-frame helper (tests, wirecompat): -> (seq, legacy record list)
    for either pub wire format."""
    body = frame[5:]
    if frame[4] == T_PUBB_S:
        s = unpack_pub_slab(body)
        return s.seq, s.records()
    return unpack_pub_batch(body)


def unpack_dlv_frame(frame: bytes):
    """Whole-frame helper (tests, wirecompat): -> legacy record list for
    either dlv wire format."""
    body = frame[5:]
    if frame[4] == T_DLV_S:
        return unpack_dlv_slab(body).records()
    return unpack_dlv_batch(body)


async def read_frame(reader) -> Tuple[int, bytes]:
    hdr = await reader.readexactly(5)
    length, ftype = _HDR.unpack(hdr)
    if length > MAX_FRAME:
        raise ValueError(f"fabric frame too large: {length}")
    body = await reader.readexactly(length) if length else b""
    return ftype, body
