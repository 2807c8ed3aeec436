"""The versioned wire-format registry (BPAPI discipline for bytes).

Every externalized format — anything that leaves this process as bytes
and is decoded by a DIFFERENT process, version, or machine — declares
here: a stable name, a version, and the structure the digest canon
(emqx_tpu/proto/digest.py) renders into a pinned digest string.
Reference analog: the frozen `*_proto_vN` BPAPI modules under
apps/emqx/src/bpapi/ — a layout change without a version bump is a
contract violation, caught before it ships, not at a rolling upgrade.

Three consumers anchor on these declarations:

- the WF/SS/BP checkers (tools/analysis, tier A) AST-extract the
  `register(...)` calls below, recompute digests from the structure
  literals, and cross-check them against BOTH the defining code (the
  actual `np.dtype`/`struct.Struct`/tag/dict literals at the `source`
  pointers) and the golden pins in
  tests/fixtures/analysis/wire/digests.json;
- the tier-B wire-compat audit (`python -m tools.analysis --wirecompat`)
  verifies the same digests against the LIVE objects and replays the
  committed byte corpus (tests/fixtures/wire_corpus/) through the
  current decoders;
- humans: the `source` field is a clickable pointer to the layout.

Rules (enforced by WF + the audit):
- structure literals here must mirror the defining module EXACTLY;
- changing a structure requires bumping the version AND regenerating
  the pins + corpus (`--wirecompat --update-corpus`);
- every registered format keeps >= 1 committed corpus file.

This module imports nothing from the broker (the digest canon is
stdlib-only), so the registry is loadable anywhere — including the
analyzer's test fixtures and a bare management shell.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from emqx_tpu.proto.digest import digest_for

# -- structure literals (mirrors of the defining modules) -------------------

# transport/fabric.py PUB_HDR_DT / DLV_HDR_DT — the slab header tables
# (ROADMAP item 2 turns these into the acceptor->owner IPC layout)
FABRIC_PUB_HDR_FIELDS = (
    ("tlen", "<u2"), ("plen", "<u4"), ("clen", "<u2"),
    ("pblen", "<u4"), ("flags", "u1"),
)
FABRIC_DLV_HDR_FIELDS = (
    ("tlen", "<u2"), ("plen", "<u4"), ("clen", "<u2"),
    ("pblen", "<u4"), ("flags", "u1"), ("nh", "<u4"),
)

# transport/fabric.py T_* — the frame-type byte after the length prefix
FABRIC_FRAME_TYPES = {
    "T_HELLO": 0, "T_SUB": 1, "T_UNSUB": 2, "T_PUBB": 3, "T_DLV": 4,
    "T_PUBB_ACK": 5, "T_SUB_ACK": 6, "T_SESS": 7, "T_RAW": 8,
    "T_PUBB_S": 9, "T_DLV_S": 10,
}

# cluster/tcp_transport.py frame kinds + cluster/node.py payload dispatch
CLUSTER_BUS_KINDS = {
    "hello": "hello", "call": "call", "cast": "cast", "reply": "reply",
}
CLUSTER_PAYLOAD_KINDS = {"membership": "membership", "rpc": "rpc"}
MEMBERSHIP_TAGS = {
    "join": "join", "heartbeat": "heartbeat",
    "heartbeat_ack": "heartbeat_ack", "leave": "leave",
}
CLUSTER_RPC_KINDS = {"announce": "announce", "call": "call"}

# cluster/node.py _register_protos — the frozen BPAPI tables. The BP
# checker asserts the in-code register() calls spell EXACTLY this.
BPAPI = {
    "broker": {1: ("forward", "forward_batch"),
               2: ("forward", "forward_batch", "forward_lane")},
    "route": {1: ("add_route", "delete_route", "dump"),
              2: ("add_route", "delete_route", "dump", "apply_batch")},
    "cm": {1: ("insert_channel", "delete_channel", "lookup_channel",
               "discard")},
    "conf": {1: ("append", "receive_apply", "entries_after")},
    "shared": {1: ("join", "leave", "dump")},
    "shard": {1: ("advertise", "dump")},
    "retain": {1: ("store", "dump"),
               2: ("store", "dump", "dump_page")},
    "sess": {1: ("insert_parked", "delete_parked", "resume_begin",
                 "resume_end", "dump_parked"),
             2: ("insert_parked", "delete_parked", "resume_begin",
                 "resume_end", "dump_parked", "park_remote",
                 "park_append")},
}

# BPAPI methods registered for REMOTE callers with no local send site:
# the BP sender-symmetry check exempts exactly these, each justified.
BPAPI_SERVE_ONLY = {
    # registered so peers (and the management API) can resolve a
    # client's home node; local lookups call the method directly
    ("cm", "lookup_channel"),
}

# broker/persistent_session.py NS_* — FileKv namespace names (the
# durable snapshot "table names"; a rename orphans committed state)
DURABLE_NAMESPACES = {
    "NS_SESSIONS": "persistent_sessions", "NS_RETAINED": "retained",
    "NS_DELAYED": "delayed", "NS_BANNED": "banned",
    "NS_DEGRADE": "degrade", "NS_SEGMENTS": "segments",
}

# storage/codec.py JSON shapes (durable snapshots + cluster handoff)
MSG_JSON_KEYS = (
    ("topic", "payload", "qos", "retain", "dup", "from_client",
     "from_username", "mid", "headers", "properties", "timestamp"),
)
SUBOPTS_JSON_KEYS = (
    ("qos", "no_local", "retain_as_published", "retain_handling"),
)
SESSION_JSON_KEYS = (
    # the session snapshot itself ...
    ("client_id", "created_at", "expiry_interval", "next_pid",
     "subscriptions", "mqueue", "inflight", "awaiting_rel"),
    # ... and each inflight entry (ages, not raw monotonic stamps —
    # the PR 11 clock-rebase contract)
    ("pid", "phase", "age", "msg"),
)

# broker/persistent_session.py flush payloads
SESSIONS_NS_KEYS = (("at", "sessions"),)
DURABLE_STATE_KEYS = (
    ("paths",),                                   # degrade
    ("messages",),                                # retained
    ("at", "messages"),                           # delayed envelope
    ("remaining_s", "msg"),                       # delayed entry
    ("entries",),                                 # banned envelope
    ("kind", "value", "reason", "until", "by"),   # banned entry
)

# ops/segments.py SegmentStateSnapshot.save sidecar meta
SEGMENT_META_KEYS = (("path", "at", "keys"),)

# broker/session_store.py SessionStore.capture — the pickled
# segment-snapshot state for the device-resident session plane
SESSION_STORE_CAPTURE_KEYS = (
    ("table", "slab", "free_mids", "slots", "slot_cid", "free_slots",
     "t0_age_ds"),
)

# cluster/node.py park_session — the parked-session record shipped by
# sess.park_remote during drain handoff
SESS_PARK_KEYS = (("session", "deadline", "pending", "marker"),)

# pickled classes (cluster forward / segment snapshots). fields = the
# __getstate__-visible instance surface; drops = fields __getstate__
# MUST null (live device handles — the PR 10 unpicklable-mesh class)
MESSAGE_STATE = (
    ("topic", "payload", "qos", "retain", "dup", "from_client",
     "from_username", "mid", "headers", "properties", "timestamp"),
    (),
)
ROUTER_STATE = (
    ("_exact", "_trie", "_index", "_matcher", "_matcher_config",
     "min_tpu_batch", "enable_tpu", "mesh"),
    ("_matcher", "mesh"),
)


@dataclass(frozen=True)
class WireFormat:
    """One registered externalized format."""

    name: str
    version: int
    kind: str        # dtype | struct | tags | schema | class_state | proto
    digest: str      # canonical structural digest (digest.py)
    source: str      # "path/to/defining_module.py:SYMBOL" pointer
    doc: str = ""
    structure: object = field(default=None, compare=False, repr=False)


_FORMATS: Dict[str, WireFormat] = {}


class FormatError(Exception):
    pass


def register(
    name: str,
    version: int,
    kind: str,
    structure,
    source: str,
    doc: str = "",
) -> WireFormat:
    """Declare a format. Re-registering a name is a programming error —
    evolution means a version bump in place, never a second entry."""
    if name in _FORMATS:
        raise FormatError(f"wire format {name!r} already registered")
    fmt = WireFormat(
        name=name, version=version, kind=kind,
        digest=digest_for(kind, structure), source=source, doc=doc,
        structure=structure,
    )
    _FORMATS[name] = fmt
    return fmt


def formats() -> List[WireFormat]:
    return [_FORMATS[k] for k in sorted(_FORMATS)]


def get(name: str) -> Optional[WireFormat]:
    return _FORMATS.get(name)


def digest_of(name: str) -> str:
    fmt = _FORMATS.get(name)
    if fmt is None:
        raise FormatError(f"unknown wire format {name!r}")
    return fmt.digest


def pin_doc() -> Dict:
    """The golden-pin document shape for digests.json (repo formats
    only — fixture pins are maintained by hand next to the fixtures)."""
    return {
        "formats": {
            f.name: {"version": f.version, "digest": f.digest}
            for f in formats()
        }
    }


# -- registrations ----------------------------------------------------------

register(
    "fabric.slab.pub_hdr", 1, "dtype", FABRIC_PUB_HDR_FIELDS,
    "emqx_tpu/transport/fabric.py:PUB_HDR_DT",
    "slab PUBB_S per-record header table row (13B packed)",
)
register(
    "fabric.slab.dlv_hdr", 1, "dtype", FABRIC_DLV_HDR_FIELDS,
    "emqx_tpu/transport/fabric.py:DLV_HDR_DT",
    "slab DLV_S per-record header table row (17B packed, u32 nh)",
)
register(
    "fabric.frame_hdr", 1, "struct", "<IB",
    "emqx_tpu/transport/fabric.py:_HDR",
    "fabric frame prelude: u32 LE body length + u8 frame type",
)
register(
    "fabric.u16", 1, "struct", "<H",
    "emqx_tpu/transport/fabric.py:_U16",
    "legacy per-record wire: u16 LE length fields",
)
register(
    "fabric.u32", 1, "struct", "<I",
    "emqx_tpu/transport/fabric.py:_U32",
    "legacy per-record wire: u32 LE length/seq/count fields",
)
register(
    "fabric.frame_types", 1, "tags", FABRIC_FRAME_TYPES,
    "emqx_tpu/transport/fabric.py:T_*",
    "frame-type byte values (slab + legacy + control frames)",
)
register(
    "cluster.bus.len_prefix", 1, "struct", ">I",
    "emqx_tpu/cluster/tcp_transport.py:_LEN",
    "cluster bus frame prelude: u32 BE pickled-payload length",
)
register(
    "cluster.bus.kinds", 1, "tags", CLUSTER_BUS_KINDS,
    # "#pos0": the BP checker enforces sender/handler symmetry for
    # tuple[0] discriminators, with handlers in the fragment-less path
    "emqx_tpu/cluster/tcp_transport.py#pos0",
    "bus frame discriminators: (kind, req_id, payload) tuples",
)
register(
    "cluster.payload.kinds", 1, "tags", CLUSTER_PAYLOAD_KINDS,
    "emqx_tpu/cluster/node.py#pos0",
    "node-level payload dispatch: payload[0] families",
)
register(
    "membership.tags", 1, "tags", MEMBERSHIP_TAGS,
    # "#key=K": tuple[1] discriminators, gated on tuple[0] == K
    "emqx_tpu/cluster/membership.py#key=membership",
    "membership gossip ops: (\"membership\", tag, ...) tuples",
)
register(
    "cluster.rpc.kinds", 1, "tags", CLUSTER_RPC_KINDS,
    "emqx_tpu/cluster/rpc.py#key=rpc",
    "rpc envelope ops: (\"rpc\", kind, ...) tuples",
)
register(
    "cluster.bpapi", 2, "proto", BPAPI,
    "emqx_tpu/cluster/node.py:_register_protos",
    "frozen BPAPI proto tables: api -> version -> methods",
)
register(
    "durable.kv.namespaces", 1, "tags", DURABLE_NAMESPACES,
    "emqx_tpu/broker/persistent_session.py:NS_*",
    "FileKv namespace names for the durable snapshot plane",
)
register(
    "codec.msg_json", 1, "schema", MSG_JSON_KEYS,
    "emqx_tpu/storage/codec.py:msg_to_json",
    "Message JSON shape (durable stores + cluster handoff)",
)
register(
    "codec.subopts_json", 1, "schema", SUBOPTS_JSON_KEYS,
    "emqx_tpu/storage/codec.py:subopts_to_json",
    "SubOpts JSON shape inside session snapshots",
)
register(
    "codec.session_json", 1, "schema", SESSION_JSON_KEYS,
    "emqx_tpu/storage/codec.py:session_to_json",
    "session snapshot JSON: metadata + inflight AGE entries (PR 11)",
)
register(
    "durable.sessions_ns", 1, "schema", SESSIONS_NS_KEYS,
    "emqx_tpu/broker/persistent_session.py:SessionPersistence.flush",
    "NS_SESSIONS payload envelope; per-session snaps add "
    "expiry_remaining_s (legacy: wall-clock deadline, PR 15)",
)
register(
    "durable.state", 1, "schema", DURABLE_STATE_KEYS,
    "emqx_tpu/broker/persistent_session.py:DurableState.flush",
    "retained/delayed/banned/degrade kv payload shapes",
)
register(
    "snapshot.segment_meta", 1, "schema", SEGMENT_META_KEYS,
    "emqx_tpu/ops/segments.py:SegmentStateSnapshot.save",
    "segment-snapshot kv pointer meta (sidecar path + generation)",
)
register(
    "snapshot.session_store", 1, "schema", SESSION_STORE_CAPTURE_KEYS,
    "emqx_tpu/broker/session_store.py:SessionStore.capture",
    "device-resident session plane capture (pickled sidecar state)",
)
register(
    "cluster.sess.park", 1, "schema", SESS_PARK_KEYS,
    "emqx_tpu/cluster/node.py:ClusterNode.park_session",
    "parked-session record shipped by sess v2 park_remote",
)
register(
    "message.pickle", 1, "class_state", MESSAGE_STATE,
    "emqx_tpu/broker/message.py:Message",
    "pickled Message surface (cluster forward; slab msgs materialize)",
)
register(
    "router.pickle", 1, "class_state", ROUTER_STATE,
    "emqx_tpu/broker/router.py:Router",
    "pickled Router surface; __getstate__ MUST null the device-handle "
    "fields (the PR 10 unpicklable-mesh bug class)",
)
register(
    "mqtt.slab_serializer.u16be", 1, "struct", ">H",
    "emqx_tpu/mqtt/slab_serializer.py:_U16BE",
    "MQTT remaining-length-adjacent u16 BE fields in the slab "
    "serializer fast path",
)
register(
    "transport.dtls.record_hdr", 1, "struct", "!BHHHIH",
    "emqx_tpu/transport/dtls.py:_REC",
    "DTLS 1.2 record header (type, version, epoch, 48-bit seq, len)",
)
