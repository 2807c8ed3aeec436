"""$event system messages (reference: apps/emqx_modules/src/
emqx_event_message.erl): republish broker lifecycle events as MQTT messages
on well-known topics so ordinary subscribers can watch them:

  $event/client_connected     $event/client_disconnected
  $event/session_subscribed   $event/session_unsubscribed
  $event/message_delivered    $event/message_acked
  $event/message_dropped

Each event class is individually enableable; payloads are JSON with the
reference's field names (clientid, username, topic, qos, ...).
"""

from __future__ import annotations

import base64
import json
import time
from dataclasses import dataclass, field
from typing import Set

from emqx_tpu.broker.message import Message


DEFAULT_EVENTS = frozenset(
    {
        "client_connected",
        "client_disconnected",
        "session_subscribed",
        "session_unsubscribed",
        "message_delivered",
        "message_acked",
        "message_dropped",
    }
)


def _payload_b64(payload: bytes) -> str:
    try:
        return payload.decode("utf-8")
    except UnicodeDecodeError:
        return base64.b64encode(payload).decode()


@dataclass
class EventMessage:
    broker: object
    enabled: Set[str] = field(default_factory=lambda: set(DEFAULT_EVENTS))

    def _emit(self, event: str, data: dict) -> None:
        if event not in self.enabled:
            return
        data["ts"] = int(time.time() * 1000)
        self.broker.publish(
            Message(topic=f"$event/{event}", payload=json.dumps(data).encode())
        )

    # -- hook callbacks ----------------------------------------------------
    def on_client_connected(self, client_info, channel) -> None:
        self._emit(
            "client_connected",
            {
                "clientid": client_info.get("client_id"),
                "username": client_info.get("username"),
                "ipaddress": client_info.get("peerhost"),
                "proto_ver": client_info.get("proto_ver"),
                "keepalive": client_info.get("keepalive"),
                "connected_at": int(time.time() * 1000),
            },
        )

    def on_client_disconnected(self, client_info, reason) -> None:
        self._emit(
            "client_disconnected",
            {
                "clientid": client_info.get("client_id"),
                "username": client_info.get("username"),
                "reason": str(reason),
                "disconnected_at": int(time.time() * 1000),
            },
        )

    def on_session_subscribed(self, client_info, topic, opts, _ch=None) -> None:
        self._emit(
            "session_subscribed",
            {
                "clientid": client_info.get("client_id"),
                "username": client_info.get("username"),
                "topic": topic,
                "qos": getattr(opts, "qos", 0),
            },
        )

    def on_session_unsubscribed(self, client_info, topic) -> None:
        self._emit(
            "session_unsubscribed",
            {
                "clientid": client_info.get("client_id"),
                "username": client_info.get("username"),
                "topic": topic,
            },
        )

    def on_message_delivered(self, client_info, msg) -> None:
        # enabled-check FIRST: this runs per delivery, and building the
        # payload dict (incl. base64) for a disabled event class was a
        # measurable share of the serving hot path
        if "message_delivered" not in self.enabled:
            return
        if msg.is_sys() or msg.topic.startswith("$event/"):
            return
        self._emit(
            "message_delivered",
            {
                "clientid": client_info.get("client_id"),
                "username": client_info.get("username"),
                "from_clientid": msg.from_client,
                "topic": msg.topic,
                "qos": msg.qos,
                "retain": msg.retain,
                "payload": _payload_b64(msg.payload),
                "publish_received_at": int(msg.timestamp * 1000),
            },
        )

    def on_message_acked(self, client_info, msg_or_pid) -> None:
        if "message_acked" not in self.enabled:
            return
        if isinstance(msg_or_pid, Message) and (
            msg_or_pid.is_sys() or msg_or_pid.topic.startswith("$event/")
        ):
            # same guard as delivered/dropped: acking a $event QoS1 delivery
            # must not spawn another $event publish (self-sustaining loop)
            return
        data = {
            "clientid": client_info.get("client_id"),
            "username": client_info.get("username"),
        }
        if isinstance(msg_or_pid, Message):
            data.update(
                {
                    "topic": msg_or_pid.topic,
                    "qos": msg_or_pid.qos,
                    "from_clientid": msg_or_pid.from_client,
                }
            )
        else:
            data["packet_id"] = msg_or_pid
        self._emit("message_acked", data)

    def on_message_dropped(self, msg, reason) -> None:
        if "message_dropped" not in self.enabled:
            return
        if msg.is_sys() or msg.topic.startswith("$event/"):
            return
        self._emit(
            "message_dropped",
            {
                "clientid": msg.from_client,
                "topic": msg.topic,
                "qos": msg.qos,
                "reason": str(reason),
                "payload": _payload_b64(msg.payload),
            },
        )

    def attach(self, hooks) -> None:
        """The client and session events always; the three per-message
        events only where enabled, so a disabled one costs the serving
        path no callback at all (an empty `message.acked` chain is what
        lets an ack run skip the hook). Call again after changing
        `enabled`: every callback is registered anew."""
        for name, cb in (
            ("client.connected", self.on_client_connected),
            ("client.disconnected", self.on_client_disconnected),
            ("session.subscribed", self.on_session_subscribed),
            ("session.unsubscribed", self.on_session_unsubscribed),
            ("message.delivered", self.on_message_delivered),
            ("message.acked", self.on_message_acked),
            ("message.dropped", self.on_message_dropped),
        ):
            hooks.delete(name, "event_message")
            if (
                not name.startswith("message.")
                or name.replace(".", "_") in self.enabled
            ):
                hooks.add(name, cb, tag="event_message")
