"""Versioned cluster RPC: BPAPI proto discipline over the transport.

Reference analog: every cross-node call in EMQX goes through frozen
`*_proto_vN` modules so rolling upgrades can negotiate the highest version
both sides support (apps/emqx/src/bpapi/README.md:6-50,
emqx_bpapi:supported_version). `emqx_rpc:call/cast/multicall`
(emqx_rpc.erl:22-30) is the thin wrapper underneath.

Here a proto is registered as (api_name, version) -> {method: handler}.
Callers go through `Rpc.call(node, api, method, *args)`; the dispatcher
picks the highest version the callee announced. Methods are explicit and
frozen per version — adding behavior means adding a new version, never
mutating an old one (the static-check discipline the reference enforces
with BPAPI snapshots becomes a runtime assertion here; see
tests/test_cluster.py for the immutability test).
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from emqx_tpu.cluster.transport import AsyncSender, ChannelPool, LocalBus, NodeUnreachable


class RpcError(Exception):
    pass


class BpapiRegistry:
    """(api, version) -> {method: handler}; frozen after announce."""

    def __init__(self) -> None:
        self._protos: Dict[Tuple[str, int], Dict[str, Callable]] = {}
        self._frozen: set[Tuple[str, int]] = set()

    def register(
        self, api: str, version: int, methods: Dict[str, Callable]
    ) -> None:
        key = (api, version)
        if key in self._frozen:
            raise RpcError(f"BPAPI {api} v{version} is frozen; bump the version")
        self._protos[key] = dict(methods)
        self._frozen.add(key)

    def versions(self, api: str) -> List[int]:
        return sorted(v for (a, v) in self._protos if a == api)

    def lookup(self, api: str, version: int, method: str) -> Callable:
        proto = self._protos.get((api, version))
        if proto is None or method not in proto:
            raise RpcError(f"unknown {api} v{version}.{method}")
        return proto[method]

    def announce(self) -> Dict[str, List[int]]:
        out: Dict[str, List[int]] = {}
        for (a, v) in self._protos:
            out.setdefault(a, []).append(v)
        return {a: sorted(vs) for a, vs in out.items()}


class Rpc:
    """Per-node RPC endpoint: sync call, async cast, multicall."""

    def __init__(self, node: str, bus: LocalBus) -> None:
        self.node = node
        self._bus = bus
        self.registry = BpapiRegistry()
        self._peer_versions: Dict[str, Dict[str, List[int]]] = {}
        self._channels = ChannelPool()
        self._sender = AsyncSender(bus, node)
        self._lock = threading.Lock()

    # -- version negotiation (emqx_bpapi:supported_version parity) ---------
    def supported_version(self, peer: str, api: str) -> int:
        with self._lock:
            known = self._peer_versions.get(peer)
        if known is None:
            try:
                known = self._bus.send(self.node, peer, ("rpc", "announce"))
            except NodeUnreachable as e:
                raise RpcError(str(e)) from e
            with self._lock:
                self._peer_versions[peer] = known
        mine = set(self.registry.versions(api))
        theirs = set(known.get(api, ()))
        common = mine & theirs
        if not common:
            raise RpcError(f"no common version for {api} with {peer}")
        return max(common)

    def forget_peer(self, peer: str) -> None:
        with self._lock:
            self._peer_versions.pop(peer, None)

    # -- wire handler ------------------------------------------------------
    def handle(self, from_node: str, msg) -> object:
        kind = msg[1]
        if kind == "announce":
            return self.registry.announce()
        if kind == "call":
            _, _, api, version, method, args = msg
            handler = self.registry.lookup(api, version, method)
            return ("ok", handler(*args))
        return None

    # -- caller side (emqx_rpc.erl:22-30 parity) ---------------------------
    def call(self, peer: str, api: str, method: str, *args) -> Any:
        return self.call_on(peer, api, method, args)

    def call_on(
        self, peer: str, api: str, method: str, args: tuple,
        channel="", patient=None,
    ) -> Any:
        """`call` on a socket of the caller's choice (`channel`, see
        tcp_transport.CH_*: a lane's traffic does not queue behind the
        rest) and, with `patient`, waiting for a live peer's late reply
        instead of sending the request again."""
        if peer == self.node:
            v = max(self.registry.versions(api))
            return self.registry.lookup(api, v, method)(*args)
        v = self.supported_version(peer, api)
        try:
            r = self._bus.send(
                self.node, peer, ("rpc", "call", api, v, method, args),
                channel, patient,
            )
        except NodeUnreachable as e:
            raise RpcError(str(e)) from e
        if not (isinstance(r, tuple) and r[0] == "ok"):
            raise RpcError(f"badrpc from {peer}: {r!r}")
        return r[1]

    def cast(self, peer: str, api: str, method: str, *args, key: str = "") -> None:
        """Async, per-key ordered (gen_rpc keyed channel semantics)."""
        if peer == self.node:
            v = max(self.registry.versions(api))
            self.registry.lookup(api, v, method)(*args)
            return
        try:
            v = self.supported_version(peer, api)
        except RpcError:
            return  # unreachable peer: cast is fire-and-forget
        self._channels.pick(key or method)
        self._sender.enqueue(peer, ("rpc", "call", api, v, method, args))

    def multicall(
        self, peers: List[str], api: str, method: str, *args
    ) -> Dict[str, Any]:
        """Call every peer; collect per-node results or error strings."""
        out: Dict[str, Any] = {}
        for p in peers:
            try:
                out[p] = self.call(p, api, method, *args)
            except RpcError as e:
                out[p] = ("badrpc", str(e))
        return out

    def flush(self) -> None:
        self._sender.flush()

    def stop(self) -> None:
        self._sender.stop()
