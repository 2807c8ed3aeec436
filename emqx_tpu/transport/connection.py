"""Per-connection pump: socket bytes <-> frames <-> channel.

Parity with the reference connection process (apps/emqx/src/
emqx_connection.erl: recvloop :356-390, parse->handle :462-493, serialize +
send, keepalive enforcement). The MQTT spec's 1.5x keepalive grace is
enforced here; an idle pre-CONNECT socket is closed after idle_timeout
(emqx_channel idle timer parity).
"""

from __future__ import annotations

import asyncio
import os
import time
from typing import Optional

from emqx_tpu.broker.channel import ACKS, Channel, ChannelConfig
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.mqtt.frame import FrameError, Parser, serialize
from emqx_tpu.observe import profiler as _prof
from emqx_tpu.transport import egress

try:
    _IOV_MAX = os.sysconf("SC_IOV_MAX")  # what asyncio's transport slices at
except (OSError, ValueError, AttributeError):
    _IOV_MAX = 16


class Connection:
    """One connected socket; owns the parser, the channel, and timers."""

    def __init__(self, broker, cm, reader, writer, config: ChannelConfig, ctx=None):
        self.reader = reader
        self.writer = writer
        peer = writer.get_extra_info("peername") or ("?", 0)
        self.channel = Channel(
            broker,
            cm,
            sink=self,
            conninfo={"peerhost": peer[0], "peerport": peer[1]},
            config=config,
        )
        self.parser = Parser(max_size=config.caps.max_packet_size)
        self.last_rx = time.time()
        self._closing = False
        self._pending: list = []  # serialised output not yet written
        self._tasks: list = []
        # rate limiting / congestion / forced GC (TransportContext wiring)
        self.limiters = None
        self.congestion = None
        self.forced_gc = None
        if ctx is not None:
            if ctx.limiters is not None:
                # None when all types are unlimited -> zero hot-path cost
                self.limiters = ctx.limiters.container(
                    "bytes_in", "message_in"
                )
            if ctx.alarms is not None:
                from emqx_tpu.transport.congestion import Congestion

                self.congestion = Congestion(alarms=ctx.alarms)
            if ctx.make_forced_gc is not None:
                self.forced_gc = ctx.make_forced_gc()

    # -- sink interface used by the channel -------------------------------
    # The sink coalesces: a send appends the serialised frame to this
    # connection's pending list, and a batch boundary hands the list to
    # the socket in one write (docs/protocol_plane.md "The in-process
    # sink"). Byte order is the call order: one list, appended in order.
    def send_packet(self, p) -> None:
        if self._closing:
            return
        try:
            frame = serialize(p, self.channel.version)
        except Exception:
            self.close("send_error")
            return
        self._append((frame,))

    def send_bytes(self, b: bytes) -> None:
        """Pre-serialized frame (the channel's QoS0 fan-out cache:
        serialize once per message, write to every subscriber socket)."""
        if not self._closing:
            self._append((b,))

    def send_segments(self, segs) -> None:
        """Pre-serialized frame segments (the batched slab serializer:
        shared heads/tails and slab frame views; large ones land on the
        socket by `writelines` without an intermediate join)."""
        if not self._closing:
            self._append(segs)

    def _append(self, segs) -> None:
        pend = self._pending
        if not pend:
            loop = asyncio._get_running_loop()
            if loop is None:
                # driven synchronously: no turn to end, write through
                # (inside the caller's `egress.send` section)
                self._write(list(segs))
                return
            # the safety net: whatever no boundary flushes first goes
            # out with the loop's end-of-turn call
            egress.of(loop).mark(self)
        pend.extend(segs)

    # a flush joins small frames into one `write` (one send()); past
    # JOIN_MAX bytes of large segments it hands them over uncopied, in
    # `writelines` calls the transport can pass to one sendmsg() each
    # (asyncio slices its buffer at SC_IOV_MAX)
    JOIN_MAX = 64 * 1024
    SMALL_SEGMENT = 1024
    IOV_MAX = _IOV_MAX

    def flush(self) -> None:
        """Hand everything pending to the socket: one write. Called at
        the batch boundaries (the end of a read chunk, of a settled
        batch's fan-out, of the ack drainer's resolved run), by the
        loop's end-of-turn call and before a close."""
        pend = self._pending
        if not pend:
            return
        self._pending = []
        if self._closing:
            return
        # the socket write's time stays in `egress.send`; its entries
        # are the packets, counted where they were serialised
        _prof.begin("egress.send")
        try:
            self._write(pend)
        finally:
            _prof.end(0)

    def _write(self, segs: list) -> None:
        n = len(segs)
        writes = 1
        try:
            if n == 1:
                self.writer.write(segs[0])
            else:
                size = sum(map(len, segs))
                if size <= self.JOIN_MAX or size < n * self.SMALL_SEGMENT:
                    self.writer.write(b"".join(segs))
                else:
                    writes = 0
                    for i in range(0, n, self.IOV_MAX):
                        self.writer.writelines(segs[i:i + self.IOV_MAX])
                        writes += 1
        except Exception:
            self.close("send_error")
            return
        self.channel.broker.metrics.inc("egress.writes", writes)

    def close(self, reason: str) -> None:
        if self._closing:
            return
        # the last packets (a DISCONNECT, a refusing CONNACK) reach the
        # peer before the FIN: the transport writes its buffer out first
        self.flush()
        if self._closing:
            return  # the flush's own write failed and closed
        self._closing = True
        try:
            self.writer.close()
        except Exception:
            pass

    # -- pump --------------------------------------------------------------
    async def run(self) -> None:
        keeper = asyncio.ensure_future(self._keepalive_loop())
        ticker = asyncio.ensure_future(self._tick_loop())
        try:
            while not self._closing:
                data = await self.reader.read(65536)
                if not data:
                    break
                self.last_rx = time.time()
                if self.forced_gc is not None:
                    self.forced_gc.inc(0, len(data))
                if self.limiters is not None:
                    # bytes_in: pause the read loop until tokens accrue
                    # (emqx_connection rate-limit pause, :103-120)
                    await self._limited("bytes_in", len(data))
                try:
                    # one section per read chunk, its packets the entries
                    _prof.begin("ingress.decode")
                    pkts = ()
                    try:
                        pkts = self.parser.feed(data)
                    finally:
                        _prof.end(len(pkts))
                    i, n = 0, len(pkts)
                    while i < n:
                        p = pkts[i]
                        i += 1
                        if p.type in ACKS and self.channel.state == "connected":
                            # the chunk's run of acks: one call, one section
                            j = i
                            while j < n and pkts[j].type in ACKS:
                                j += 1
                            if self.forced_gc is not None:
                                self.forced_gc.inc(j - i + 1, 0)
                            self.channel.handle_acks(pkts[i - 1:j])
                            i = j
                            continue
                        if (
                            self.limiters is not None
                            and p.type == pkt.PUBLISH
                        ):
                            await self._limited("message_in", 1)
                        if self.forced_gc is not None:
                            self.forced_gc.inc(1, 0)
                        await self.channel.handle_in(p)
                except FrameError as e:
                    self.channel.disconnect_reason = f"frame_error:{e.reason}"
                    if self.channel.version == pkt.MQTT_V5:
                        self.send_packet(
                            pkt.Disconnect(reason_code=pkt.RC_MALFORMED_PACKET)
                        )
                    break
                await self._drain()
        except (ConnectionError, asyncio.IncompleteReadError):
            pass
        finally:
            keeper.cancel()
            ticker.cancel()
            if self.congestion is not None:
                self.congestion.on_close(self.channel.client_id)
            self.close("sock_closed")
            try:
                await self.writer.wait_closed()
            except Exception:
                pass
            try:
                await self.channel.on_sock_closed()
            finally:
                self.channel.release_sink()

    async def _limited(self, type_: str, n: float) -> None:
        """Charge the limiter and pause for the returned interval.

        The charge always lands (token debt), so sustained throughput
        converges on the configured rate for any chunk size. The pause is
        counted as liveness — the client IS sending, we are throttling it —
        so keepalive must not fire mid-throttle."""
        wait = self.limiters.consume(type_, n)
        # sleep in short slices, refreshing last_rx each one, so keepalive
        # never fires during a long throttle pause (waits reach 60s)
        while wait > 0 and not self._closing:
            step = min(wait, 5.0)
            self.last_rx = time.time()
            await asyncio.sleep(step)
            wait -= step
        self.last_rx = time.time()

    async def _drain(self) -> None:
        self.flush()
        try:
            await self.writer.drain()
        except ConnectionError:
            self.close("sock_error")

    async def _keepalive_loop(self) -> None:
        # pre-CONNECT idle timeout (poll so keepalive arms right after CONNECT)
        start = time.time()
        while self.channel.state == "idle":
            if time.time() - start > self.channel.config.idle_timeout:
                self.close("idle_timeout")
                return
            await asyncio.sleep(0.2)
        while not self._closing:
            ka = self.channel.keepalive
            if ka <= 0:
                return
            await asyncio.sleep(ka / 2)
            if time.time() - self.last_rx > ka * 1.5:
                self.channel.disconnect_reason = "keepalive_timeout"
                self.close("keepalive_timeout")
                return

    async def _tick_loop(self) -> None:
        while not self._closing:
            await asyncio.sleep(
                max(1.0, self.channel.config.session.retry_interval / 2)
            )
            if self.channel.state == "connected":
                self.channel.tick()
                await self._drain()
            if self.congestion is not None:
                self.flush()  # the alarm reads the transport's buffer
                self.congestion.check(
                    getattr(self.writer, "transport", None),
                    self.channel.client_id,
                )
