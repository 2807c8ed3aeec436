"""MQTT-over-WebSocket transport (reference: apps/emqx/src/emqx_ws_connection.erl,
listener wiring at apps/emqx/src/emqx_listeners.erl:239-248).

The reference runs a cowboy websocket handler that feeds the same
emqx_channel state machine as the TCP path. Here a `websockets` server
adapts each WS connection to the stream interface `Connection` expects, so
the parser/channel/keepalive logic is shared verbatim with TCP/TLS.

MQTT-over-WS rules (MQTT 5.0 spec §6, mirrored from emqx_ws_connection):
- subprotocol must be "mqtt" (the reference also accepts the legacy
  "mqttv3.1" names via `fail_if_no_subprotocol=false`; we accept absent
  subprotocol for lenient clients, matching that default-off check)
- payload is binary frames; text frames are a protocol error
- a single WS message may carry multiple or partial MQTT packets (the
  incremental Parser already handles both).
"""

from __future__ import annotations

import asyncio
import ssl as ssl_mod
from typing import Optional

# `websockets` is imported lazily (same pattern as dtls.py's
# `cryptography`): the module must stay importable on images without the
# package — a ws/wss listener fails at START time with an actionable
# error, not at import, and runtime ws tests skip cleanly.
try:
    from websockets.asyncio.server import serve
    from websockets.exceptions import ConnectionClosed
except ImportError:  # pragma: no cover - exercised on slim images
    serve = None

    class ConnectionClosed(Exception):  # placeholder: keeps the
        """Never raised when `websockets` is absent."""  # except clauses
        # below importable; real connections cannot exist without serve()

HAVE_WEBSOCKETS = serve is not None


def require_ws_support() -> None:
    """Raise a clear error when the websockets backend is unavailable;
    called when a ws/wss listener actually starts."""
    if serve is None:
        raise RuntimeError(
            "WebSocket listeners require the 'websockets' package; "
            "install it or remove the ws/wss listener from the config"
        )


from emqx_tpu.transport.connection import Connection  # noqa: E402
from emqx_tpu.transport.listener import build_ssl_context  # noqa: E402


class _WsStream:
    """Adapts a websockets ServerConnection to the asyncio stream reader and
    writer duck-types used by `Connection` (read / write / drain / close)."""

    def __init__(self, ws):
        self._ws = ws
        self._buf = bytearray()
        self._closed = False
        self._flush_task: Optional[asyncio.Task] = None

    # -- reader side -------------------------------------------------------
    async def read(self, _n: int) -> bytes:
        try:
            msg = await self._ws.recv()
        except ConnectionClosed:
            return b""
        if isinstance(msg, str):
            # MQTT requires binary WS frames; treat text as EOF-with-error
            await self._ws.close(code=1003)  # unsupported data
            return b""
        return msg

    # -- writer side -------------------------------------------------------
    def write(self, data: bytes) -> None:
        # asyncio StreamWriter.write transmits eagerly; mirror that by
        # scheduling a flush as soon as bytes are buffered, so callers that
        # never await drain() (fire-and-forget sends) still make progress
        self._buf += data
        if not self._closed and (self._flush_task is None or self._flush_task.done()):
            try:
                self._flush_task = asyncio.get_running_loop().create_task(
                    self._flush()
                )
            except RuntimeError:
                pass

    def writelines(self, segs) -> None:
        # the QoS>0 delivery path hands pre-serialized frame segments
        # (Connection.send_segments); the buffer joins them anyway
        self.write(b"".join(segs))

    # Upper bound on a single outgoing WS message: a delivery burst must not
    # coalesce into one message bigger than the peer's max_size (the MQTT
    # parser reassembles packets across WS messages either way)
    CHUNK = 32 * 1024

    async def _flush(self) -> None:
        while self._buf and not self._closed:
            out = bytes(self._buf[: self.CHUNK])
            del self._buf[: self.CHUNK]
            try:
                await self._ws.send(out)
            except ConnectionClosed:
                self._closed = True
                return

    async def drain(self) -> None:
        # Exactly one _flush coroutine may run at a time (write() and this
        # loop both create a task only when the previous one is done, with no
        # await between check and create), so MQTT byte order is preserved.
        while not self._closed and self._buf:
            task = self._flush_task
            if task is None or task.done():
                task = asyncio.get_running_loop().create_task(self._flush())
                self._flush_task = task
            await task
        if self._closed:
            raise ConnectionResetError("ws closed")

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        # flush anything the channel wrote right before closing (e.g. the
        # final DISCONNECT/CONNACK) then close the WS connection
        buf = bytes(self._buf)
        self._buf.clear()

        async def _shutdown():
            try:
                if buf:
                    await self._ws.send(buf)
            except ConnectionClosed:
                pass
            try:
                await self._ws.close()
            except Exception:
                pass

        try:
            asyncio.get_running_loop().create_task(_shutdown())
        except RuntimeError:
            pass

    async def wait_closed(self) -> None:
        try:
            await self._ws.wait_closed()
        except Exception:
            pass

    def get_extra_info(self, key: str):
        if key == "peername":
            return self._ws.remote_address
        return None


class WsListener:
    """A ws/wss listener feeding the shared Connection pump."""

    def __init__(self, broker, cm, config, channel_config, ctx=None):
        self.broker = broker
        self.cm = cm
        self.config = config
        self.channel_config = channel_config
        self.ctx = ctx
        from emqx_tpu.transport.listener import AdmissionControl

        self._admission = AdmissionControl(ctx, broker.metrics)
        self._server = None
        self._conns: set = set()

    @property
    def port(self) -> int:
        if self._server is not None:
            socks = list(self._server.sockets or [])
            if socks:
                return socks[0].getsockname()[1]
        return self.config.port

    async def start(self) -> None:
        require_ws_support()
        ctx: Optional[ssl_mod.SSLContext] = None
        if self.config.type == "wss":
            ctx = build_ssl_context(self.config)
            if self.ctx is not None and getattr(self.ctx, "psk", None) is not None:
                self.ctx.psk.wire_into(ctx)
        # One WS message may legally coalesce several MQTT packets; allow a
        # generous multiple of max_packet_size before the anti-OOM cap bites
        max_size = max(8 * self.channel_config.caps.max_packet_size, 1 << 20)
        self._server = await serve(
            self._on_ws,
            self.config.bind,
            self.config.port,
            ssl=ctx,
            subprotocols=["mqtt"],
            select_subprotocol=self._select_subprotocol,
            max_size=max_size,
        )

    @staticmethod
    def _select_subprotocol(connection, offered):
        # fail_if_no_subprotocol=false semantics: prefer "mqtt" (or the
        # legacy mqttv3.1* names), but let header-less clients through
        for sp in offered:
            if sp == "mqtt" or str(sp).startswith("mqttv3.1"):
                return sp
        return None

    def connection_count(self) -> int:
        return len(self._conns)

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
        for t in list(self._conns):
            t.cancel()
        if self._server is not None:
            await self._server.wait_closed()
            self._server = None

    async def _on_ws(self, ws) -> None:
        if not self._admission.admit(
            len(self._conns), self.config.max_connections
        ):
            await ws.close(code=1013)  # try again later
            return
        stream = _WsStream(ws)
        conn = Connection(
            self.broker, self.cm, stream, stream, self.channel_config,
            ctx=self.ctx,
        )
        task = asyncio.current_task()
        self._conns.add(task)
        try:
            await conn.run()
        finally:
            self._conns.discard(task)
