"""Real TCP cluster transport (gen_rpc data-plane analog).

Implements the same bus interface as `transport.LocalBus` (attach/detach/
send/cast) over length-prefixed frames on TCP sockets, so two actual OS
processes — or machines — can cluster. Reference analog: gen_rpc's
multi-channel TCP with per-key stable channel selection
(apps/emqx/src/emqx_rpc.erl:66-80).

Design:
- one `TcpBus` per node: a listening socket + an acceptor thread; outbound
  connections are created on demand, `channels` sockets per peer, picked by
  `hash(channel_key)` so one topic's forwards never reorder while unrelated
  topics flow in parallel (emqx_broker.erl:278-293 keyed forwards);
- frames: 4-byte big-endian length + pickled (kind, req_id, payload);
  kinds: hello / call / cast / reply. Pickle implies the cluster port must
  only be reachable by trusted peers — the same trust model as distributed
  Erlang behind its cookie (EMQX deployments firewall the distribution
  ports identically);
- `send` is a synchronous call with timeout -> NodeUnreachable on connect
  failure, broken pipe, or deadline; one reconnect attempt per send covers
  peer restarts (gen_rpc {badtcp,...} -> error semantics). A `patient`
  send (the forward and route-replication lanes of cluster/node.py) keeps
  waiting for its reply while the connection stands and the caller's
  predicate holds: on TCP a request that was written is answered unless
  the connection dies, so a reply that is late is never a reason to send
  the request again (and have it applied twice);
- membership frames ride a socket of their own (`CH_MEMBERSHIP`): the
  accepting side serves one connection's frames in order, and a heartbeat
  queued behind a call that waits for the peer's event loop would read as
  a dead node;
- inbound handler runs sequentially per connection, preserving per-channel
  FIFO; replies carry either a value or a pickled exception message that
  re-raises as RemoteCallError at the caller.
"""

from __future__ import annotations

import pickle
import random
import socket
import struct
import threading
import time
from typing import Callable, Dict, Optional, Tuple

from emqx_tpu.cluster.transport import NodeUnreachable
from emqx_tpu.observe import faults as _faults
from emqx_tpu.observe.faults import FaultError

Handler = Callable[[str, object], Optional[object]]

_LEN = struct.Struct(">I")
MAX_FRAME = 64 * 1024 * 1024
# integer channel keys pick a socket outright (hash(n) == n): the default
# key "" is socket 0
CH_FORWARD, CH_ROUTE, CH_MEMBERSHIP = 1, 2, 3


class RemoteCallError(Exception):
    """The remote handler raised; message carries the remote repr."""


def _send_frame(sock: socket.socket, obj: object) -> None:
    data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    sock.sendall(_LEN.pack(len(data)) + data)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed")
        buf += chunk
    return buf


def _recv_frame(sock: socket.socket) -> object:
    (n,) = _LEN.unpack(_recv_exact(sock, 4))
    if n > MAX_FRAME:
        raise ConnectionError(f"frame too large: {n}")
    return pickle.loads(_recv_exact(sock, n))


class _PeerConn:
    """One outbound socket to a peer: framed, request-id multiplexed."""

    def __init__(self, bus: "TcpBus", dst: str, addr: Tuple[str, int]):
        self.bus = bus
        self.dst = dst
        self.sock = socket.create_connection(addr, timeout=bus.timeout)
        self.sock.settimeout(None)
        self.wlock = threading.Lock()
        self.lock = threading.Lock()
        self._next_id = 0
        self._pending: Dict[int, list] = {}  # rid -> [event, ok, value]
        self.alive = True
        # hello carries (name, listen_host, listen_port) so the accepting
        # side can auto-register the dialer as a peer — a seed node then
        # reaches joiners it was never configured with (autocluster join)
        _send_frame(
            self.sock, ("hello", 0, (bus.node, bus.host, bus.port))
        )
        t = threading.Thread(target=self._reader, daemon=True)
        t.start()

    def _reader(self) -> None:
        try:
            while True:
                kind, rid, payload = _recv_frame(self.sock)
                if kind == "reply":
                    ok, value = payload
                    with self.lock:
                        ent = self._pending.pop(rid, None)
                    if ent is not None:
                        ent[1], ent[2] = ok, value
                        ent[0].set()
        except (ConnectionError, OSError):
            pass
        finally:
            self.close()

    def close(self) -> None:
        self.alive = False
        try:
            self.sock.close()
        except OSError:
            pass
        with self.lock:
            pending, self._pending = self._pending, {}
        for ent in pending.values():
            ent[0].set()  # waiters see alive=False / no value

    def call(self, payload: object, timeout: float, patient=None) -> object:
        """`patient`: a predicate; while it holds and this connection
        stands, a reply later than `timeout` is waited for, not given up."""
        ev = threading.Event()
        ent = [ev, None, None]
        with self.lock:
            rid = self._next_id = self._next_id + 1
            self._pending[rid] = ent
        try:
            with self.wlock:
                _send_frame(self.sock, ("call", rid, payload))
        except OSError as e:
            self.close()
            raise NodeUnreachable(f"{self.bus.node} -> {self.dst}: {e}")
        while not ev.wait(timeout) and patient is not None:
            if not self.alive or not patient():
                break
        if not ev.is_set() or ent[1] is None:
            with self.lock:
                self._pending.pop(rid, None)
            if not self.alive:
                raise NodeUnreachable(f"{self.bus.node} -> {self.dst}: closed")
            raise NodeUnreachable(f"{self.bus.node} -> {self.dst}: timeout")
        if ent[1] is False:
            raise RemoteCallError(ent[2])
        return ent[2]

    def cast(self, payload: object) -> None:
        with self.wlock:
            _send_frame(self.sock, ("cast", 0, payload))


class TcpBus:
    """LocalBus-compatible transport over real TCP sockets."""

    def __init__(
        self,
        node: str,
        host: str = "127.0.0.1",
        port: int = 0,
        channels: int = 4,
        timeout: float = 5.0,
        send_retries: int = 2,
        send_backoff_s: float = 0.05,
        send_deadline_s: float = 0.0,
        metrics=None,
        degrade=None,
    ):
        """`send_retries`/`send_backoff_s`/`send_deadline_s`: each `send`
        retries transient transport failures with bounded exponential
        backoff + jitter under an overall deadline (0 = timeout *
        (retries + 1)) before NodeUnreachable — replacing the old
        single-reconnect-per-send. Gives-up count into
        `cluster.send.dead_letter`. `degrade`: an optional
        DegradeController — sends to a tripped destination fail FAST
        (no deadline burn) until the half-open probe recovers it."""
        self.node = node
        self.timeout = timeout
        self.channels = channels
        self.send_retries = max(0, int(send_retries))
        self.send_backoff_s = float(send_backoff_s)
        self.send_deadline_s = float(send_deadline_s)
        self.degrade = degrade
        if metrics is None:
            from emqx_tpu.broker.metrics import default_metrics

            metrics = default_metrics
        self.metrics = metrics
        self._send_rng = random.Random(0xC1)
        self._handler: Optional[Handler] = None
        self._peers: Dict[str, Tuple[str, int]] = {}
        self._conns: Dict[Tuple[str, int], _PeerConn] = {}
        self._inbound: set = set()
        self._lock = threading.Lock()
        self._stopping = False
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, port))
        self._srv.listen(64)
        self.host, self.port = self._srv.getsockname()
        self._accept_thread = threading.Thread(target=self._accept, daemon=True)
        self._accept_thread.start()

    # -- LocalBus interface -------------------------------------------------
    def attach(self, node: str, handler: Handler) -> None:
        assert node == self.node, "TcpBus serves exactly its own node"
        self._handler = handler

    def detach(self, node: str) -> None:
        if node == self.node:
            self._handler = None

    def nodes(self) -> list:
        with self._lock:
            return sorted([self.node, *self._peers])

    def add_peer(self, name: str, host: str, port: int) -> None:
        with self._lock:
            self._peers[name] = (host, port)

    def remove_peer(self, name: str) -> None:
        with self._lock:
            self._peers.pop(name, None)
            stale = [k for k in self._conns if k[0] == name]
            conns = [self._conns.pop(k) for k in stale]
        for c in conns:
            c.close()

    def send(
        self, src: str, dst: str, payload: object, channel_key="",
        patient=None,
    ) -> object:
        """Confirmed send with deadline + bounded retry/backoff.

        `patient` (a predicate, see `_PeerConn.call`): the reply of a
        request that went out is waited for as long as the connection
        stands and the predicate holds, outside the deadline; connect
        failures and broken connections still take the retry ladder.

        Runs on forward/replication worker threads (never the event
        loop), so the backoff sleeps are plain `time.sleep`. A breaker
        (when a DegradeController is attached) makes a partitioned
        destination fail fast instead of paying the full deadline per
        message; give-up counts into `cluster.send.dead_letter` — the
        bounded dead-letter record for the caller's at-least-once layer.
        """
        br = (
            self.degrade.cluster_breaker(dst)
            if self.degrade is not None
            else None
        )
        if br is not None and not br.allow():
            self.metrics.inc("cluster.send.dead_letter")
            raise NodeUnreachable(f"{self.node} -> {dst}: circuit open")
        deadline = time.monotonic() + (
            self.send_deadline_s
            or self.timeout * (self.send_retries + 1)
        )
        delay = self.send_backoff_s
        attempt = 0
        while True:
            try:
                # fault site: an injected partition/drop exercises the
                # same retry + dead-letter ladder as a real one; `corrupt`
                # loses the REPLY of a request the peer has applied (the
                # case the forward lanes' exactly-once rule exists for)
                act = _faults.hit("cluster.forward")
                if act == "drop":
                    raise FaultError("cluster.forward")
                budget = deadline - time.monotonic()
                if budget <= 0:
                    raise NodeUnreachable(
                        f"{self.node} -> {dst}: send deadline exceeded"
                    )
                result = self._conn_for(dst, channel_key).call(
                    payload, min(self.timeout, budget), patient
                )
                if act == "corrupt":
                    raise FaultError("cluster.forward")
                if br is not None:
                    br.record_success()
                return result
            except (NodeUnreachable, FaultError, OSError) as e:
                attempt += 1
                if (
                    attempt > self.send_retries
                    or time.monotonic() + delay >= deadline
                ):
                    if br is not None:
                        br.record_failure("send")
                    self.metrics.inc("cluster.send.dead_letter")
                    if isinstance(e, NodeUnreachable):
                        raise
                    raise NodeUnreachable(
                        f"{self.node} -> {dst}: {e}"
                    ) from e
                self.metrics.inc("cluster.send.retries")
                time.sleep(
                    delay * (1.0 + 0.5 * self._send_rng.random())
                )
                delay = min(delay * 2.0, self.timeout)

    def cast(
        self, src: str, dst: str, payload: object, channel_key=""
    ) -> bool:
        if not channel_key and type(payload) is tuple and payload[:1] == (
            "membership",
        ):
            channel_key = CH_MEMBERSHIP
        try:
            if _faults.hit("cluster.forward") == "drop":
                return False  # casts are lossy by contract
            self._conn_for(dst, channel_key).cast(payload)
            return True
        except (NodeUnreachable, FaultError, OSError):
            return False

    # -- internals ----------------------------------------------------------
    def _conn_for(self, dst: str, channel_key) -> _PeerConn:
        with self._lock:
            addr = self._peers.get(dst)
        if addr is None:
            raise NodeUnreachable(f"{self.node} -> {dst}: unknown peer")
        ch = hash(channel_key) % self.channels
        key = (dst, ch)
        with self._lock:
            conn = self._conns.get(key)
        if conn is not None and conn.alive:
            return conn
        # (re)connect — one attempt per send, covering peer restarts
        try:
            conn = _PeerConn(self, dst, addr)
        except OSError as e:
            raise NodeUnreachable(f"{self.node} -> {dst}: {e}")
        with self._lock:
            cur = self._conns.get(key)
            if cur is not None and cur.alive:
                conn.close()
                return cur
            self._conns[key] = conn
        return conn

    def _accept(self) -> None:
        while not self._stopping:
            try:
                sock, _addr = self._srv.accept()
            except OSError:
                return
            threading.Thread(
                target=self._serve_conn, args=(sock,), daemon=True
            ).start()

    def _serve_conn(self, sock: socket.socket) -> None:
        peer = "?"
        with self._lock:
            self._inbound.add(sock)
        try:
            kind, _rid, payload = _recv_frame(sock)
            if kind != "hello":
                return
            if isinstance(payload, tuple):
                peer, phost, pport = payload
                with self._lock:
                    # learn the dialer's listen address (don't clobber an
                    # explicit add_peer with a stale announce)
                    self._peers.setdefault(peer, (phost, pport))
            else:  # legacy hello: bare node name
                peer = payload
            wlock = threading.Lock()
            while True:
                kind, rid, payload = _recv_frame(sock)
                handler = self._handler
                if kind == "call":
                    try:
                        if handler is None:
                            raise RuntimeError("node not attached")
                        result = handler(peer, payload)
                        reply = ("reply", rid, (True, result))
                    except Exception as e:  # noqa: BLE001 — ship to caller
                        reply = ("reply", rid, (False, repr(e)))
                    with wlock:
                        _send_frame(sock, reply)
                elif kind == "cast" and handler is not None:
                    try:
                        handler(peer, payload)
                    except Exception:  # noqa: BLE001 — casts are lossy
                        pass
        except (ConnectionError, OSError):
            pass
        finally:
            with self._lock:
                self._inbound.discard(sock)
            try:
                sock.close()
            except OSError:
                pass

    def stop(self) -> None:
        self._stopping = True
        self._handler = None
        # shutdown() unblocks the acceptor thread stuck in accept(2) — a
        # bare close() would leave the kernel socket (and the port) alive
        # until the blocked syscall returns, failing later rebinds
        try:
            self._srv.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._srv.close()
        except OSError:
            pass
        self._accept_thread.join(timeout=2)
        with self._lock:
            conns = list(self._conns.values())
            self._conns.clear()
            inbound = list(self._inbound)
            self._inbound.clear()
        for c in conns:
            c.close()
        for s in inbound:
            try:
                s.close()
            except OSError:
                pass
