"""Multi-process connection workers: the host data plane at scale.

One Python event loop tops out near a thousand MQTT messages/s once it
also pays codec + per-subscriber serialization. The reference never has
this wall — every connection is a BEAM process spread over cores
(emqx_connection.erl:173-176). The equivalent here:

- N WORKER processes accept clients on a shared SO_REUSEPORT port (the
  kernel load-balances accepts). Each runs the full Connection/Channel/
  Session stack — codec, keepalive, QoS state, acks — against a
  `WorkerBroker` proxy instead of the real Broker.
- The ROUTER process keeps the single DeviceRouter, subscription tables,
  retainer, rules, and cluster links. Workers speak the batched fabric
  protocol (transport/fabric.py) to it over a unix socket: SUB/UNSUB
  register proxy subscribers; publishes arrive in batches that ride the
  ingest window onto the TPU kernel; deliveries return batched, one
  record per (message, worker), fanned to sockets worker-side.

Scope: worker listeners are the high-throughput serving path. Authn/
authz/banned guards are rebuilt per worker from the same config, so
admission semantics match. Workers survive a router-process restart:
connections hold, the fabric link re-dials, subscriptions and unacked
publish batches replay (emqx_machine_boot's restart-without-dropping-
esockd layering). Delivery overflow parks per subscriber with a bounded
drop-oldest queue (emqx_mqueue parity at the seam).
"""

from __future__ import annotations

import asyncio
import os
import tempfile
from typing import Dict, List, Optional, Tuple

from emqx_tpu.broker.message import Message
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.observe import profiler as _prof
from emqx_tpu.ops import topics as T
from emqx_tpu.transport import fabric as F

# ---------------------------------------------------------------------------
# router side
# ---------------------------------------------------------------------------


class WorkerFabric:
    """Router-process endpoint: UDS server the workers dial into.

    For every worker SUB it registers a proxy subscriber with the real
    Broker whose deliver() enqueues (msg, handle) into that worker's
    outbox; outboxes flush once per loop tick with one DLV record per
    message (per-subscriber QoS handling stays worker-side)."""

    def __init__(self, app, uds_path: str, expected_workers: int = 0):
        self.app = app
        self.broker = app.broker
        self.uds_path = uds_path
        self._server: Optional[asyncio.AbstractServer] = None
        self._writers: Dict[int, asyncio.StreamWriter] = {}
        # boot gate: a RESTARTED router must not dispatch one worker's
        # re-sent publish batches before ANOTHER worker's subscription
        # replay has registered (cross-link ordering — each link's own
        # FIFO already orders its SUBs before its PUBBs). PUBBs buffer
        # until every expected worker reports replay_done, or the
        # force-open timer fires (a worker lost for good must not wedge
        # publishers).
        self.expected_workers = expected_workers
        self._pub_gate_open = expected_workers == 0
        self._boot_ready: set = set()
        self._held_pubs: List = []
        self._gate_timer = None
        # wid -> {(full_sid, filter)}: explicit registry of the broker
        # subscriptions each worker proxies (worker-death cleanup walks
        # this, never a sid-prefix match that could catch an in-process
        # client whose id happens to start with "w{wid}|")
        self._fabric_subs: Dict[int, set] = {}
        # wid -> [(msg, [handles])]; one record per message per tick
        self._outbox: Dict[int, List] = {}
        self._outbox_last: Dict[int, Tuple[int, List[int]]] = {}
        self._flush_scheduled = False
        # congestion parking: wid -> {handle -> deque[msg|raw bytes]}
        # + drain tasks
        self._parked: Dict[int, Dict[int, object]] = {}
        self._drainers: Dict[int, asyncio.Task] = {}
        # QoS0 fast lane: wid -> [(frame_bytes, [handles])]
        self._raw_outbox: Dict[int, List] = {}
        self._raw_last: Dict[int, Tuple] = {}
        # emqx_cm across workers: cid -> owning wid (live channels);
        # takes pending the owner's state reply, keyed by a ROUTER-
        # generated token (worker request ids are only unique per
        # worker): token -> (owner_wid, cid, reply_fn); sessions
        # mid-resume (snapshot shipped, handoff bankers still live)
        self._owner: Dict[str, int] = {}
        # negotiated session expiry per live worker client (sent by the
        # worker after CONNACK): worker-crash parking keys on it
        self._owner_expiry: Dict[str, float] = {}
        self._take_pending: Dict[int, Tuple[int, str, object]] = {}
        self._next_take = 1
        self._resuming: Dict[str, dict] = {}
        self._tasks: set = set()

    async def start(self) -> None:
        try:
            os.unlink(self.uds_path)
        except FileNotFoundError:
            pass
        self._server = await asyncio.start_unix_server(
            self._on_worker, path=self.uds_path
        )
        if not self._pub_gate_open:
            self._gate_timer = asyncio.get_running_loop().call_later(
                10.0, self._open_pub_gate
            )
        # the router's own CM consults us at open_session so a client
        # live on a WORKER reconnecting via an in-process listener
        # (ws/ssl) still takes its session over (node-wide emqx_cm)
        cm = getattr(self.app, "cm", None)
        if cm is not None and hasattr(cm, "fabrics") and \
                self not in cm.fabrics:
            cm.fabrics.append(self)

    async def stop(self) -> None:
        cm = getattr(self.app, "cm", None)
        if cm is not None and hasattr(cm, "fabrics") and \
                self in cm.fabrics:
            cm.fabrics.remove(self)
        if self._server is not None:
            self._server.close()
        for t in list(self._tasks):
            t.cancel()
        for d in list(self._drainers.values()):
            d.cancel()
        self._drainers.clear()
        self._parked.clear()
        if self._server is not None:
            await self._server.wait_closed()
            self._server = None
        try:
            os.unlink(self.uds_path)
        except FileNotFoundError:
            pass

    async def _on_worker(self, reader, writer) -> None:
        task = asyncio.current_task()
        self._tasks.add(task)
        wid = -1
        try:
            ftype, body = await F.read_frame(reader)
            if ftype != F.T_HELLO:
                return
            wid = int.from_bytes(body[:2], "little")
            self._writers[wid] = writer
            while True:
                ftype, body = await F.read_frame(reader)
                if ftype == F.T_SUB:
                    h = self._on_sub(wid, body)
                    # confirm AFTER registration + retained enqueue:
                    # the worker releases the client's SUBACK on this
                    if not writer.is_closing():
                        writer.write(F.pack_json(F.T_SUB_ACK, {"h": h}))
                elif ftype == F.T_UNSUB:
                    self._on_unsub(wid, body)
                elif ftype in (F.T_PUBB, F.T_PUBB_S):
                    if self._pub_gate_open:
                        if ftype == F.T_PUBB_S:
                            await self._on_pub_slab(writer, body)
                        else:
                            await self._on_pub_batch(writer, body)
                    else:
                        self._held_pubs.append((writer, ftype, body))
                elif ftype == F.T_SESS:
                    import json

                    self._on_sess(wid, writer, json.loads(body))
        except (
            asyncio.IncompleteReadError,
            ConnectionResetError,
            asyncio.CancelledError,
        ):
            pass
        finally:
            self._tasks.discard(task)
            if wid >= 0:
                self._writers.pop(wid, None)
                self._outbox.pop(wid, None)
                self._raw_outbox.pop(wid, None)
                self._parked.pop(wid, None)
                d = self._drainers.pop(wid, None)
                if d is not None:
                    d.cancel()
                self._drop_worker_subs(wid)
                for cid in [
                    c for c, w in self._owner.items() if w == wid
                ]:
                    self._owner.pop(cid, None)
                    self._owner_expiry.pop(cid, None)
                # takes waiting on this (now dead) owner fail fast
                # instead of leaking / stalling requesters 30s
                for tk in [
                    t for t, (ow, _c, _r) in self._take_pending.items()
                    if ow == wid
                ]:
                    _ow, _cid, reply = self._take_pending.pop(tk)
                    reply(None, False)
            writer.close()

    # -- subscribe side ---------------------------------------------------
    def _sid(self, wid: int, sid: str) -> str:
        return f"w{wid}|{sid}"

    def _on_sub(self, wid: int, body: bytes) -> int:
        """Register a worker subscription; returns its handle (the read
        loop confirms it back as SUB_ACK after this returns)."""
        import json

        d = json.loads(body)
        handle = int(d["h"])
        opts = pkt.SubOpts(
            qos=int(d.get("qos", 0)),
            no_local=bool(d.get("nl", False)),
            retain_as_published=bool(d.get("rap", False)),
            retain_handling=int(d.get("rh", 0)),
        )
        filter_ = d["f"]
        _group, real = T.parse_share(filter_)
        # rh=1 semantics key on THIS CLIENT's prior subscription, which
        # only the worker-side session knows (channel.py sets
        # opts._existing); broker-wide existence would suppress replay
        # for every later client
        existing = bool(d.get("ex", False))
        # QoS0 fast lane ("fl": protocol version): the router ships a
        # pre-serialized PUBLISH the worker writes straight to the
        # subscriber socket. Retained replays stay on the message path
        # (their Message objects are store-owned; see channel._fb note).
        fl = d.get("fl")
        if fl:
            rap = bool(d.get("rap", False))

            def deliver(msg, _opts, _wid=wid, _h=handle, _v=int(fl),
                        _rap=rap):
                if msg.headers.get("retained"):
                    self.enqueue(_wid, _h, msg)
                else:
                    self.enqueue_raw(_wid, _h, _v, _rap, msg)
        else:

            def deliver(msg, _opts, _wid=wid, _h=handle):
                self.enqueue(_wid, _h, msg)

        full_sid = self._sid(wid, d["sid"])
        self.broker.subscribe(full_sid, d.get("cid", ""), filter_, opts,
                              deliver)
        self._fabric_subs.setdefault(wid, set()).add((full_sid, filter_))
        # retained replay (the worker-side channel hooks have no retainer;
        # semantics per emqx_retainer: never for $share, rh=2 never,
        # rh=1 only for fresh subscriptions)
        ret = getattr(self.app, "retainer", None)
        if (
            ret is not None
            and ret.enabled
            and not d.get("nr")  # link-reconnect replay: never retained
            and _group is None
            and opts.retain_handling != 2
            and not (opts.retain_handling == 1 and existing)
        ):
            for m in ret.match(real):
                import copy

                mm = copy.copy(m)
                mm.headers = dict(m.headers, retained=True)
                self.enqueue(wid, handle, mm)
        return handle

    def _on_unsub(self, wid: int, body: bytes) -> None:
        import json

        d = json.loads(body)
        full_sid = self._sid(wid, d["sid"])
        self.broker.unsubscribe(full_sid, d["f"])
        subs = self._fabric_subs.get(wid)
        if subs is not None:
            subs.discard((full_sid, d["f"]))

    def _drop_worker_subs(self, wid: int) -> None:
        """Worker died: every subscription it proxied is gone — but
        sessions with a positive expiry are RECONSTRUCTED and parked
        first (subscriptions + future offline banking survive the
        crash; in-flight/queued state died with the worker process).
        The reference's node keeps sessions across connection-process
        crashes the same way (the channel process dies, emqx_cm keeps
        the session)."""
        dropped = self._fabric_subs.pop(wid, set())
        # (cid, filter) -> opts, harvested before the registry drops
        crash_park: Dict[str, Dict] = {}
        for sid, f in dropped:
            cid = sid.split("|", 1)[1] if "|" in sid else sid
            expiry = self._owner_expiry.get(cid, 0)
            if expiry > 0:
                _g, real = T.parse_share(f)
                sub = self.broker._subs.get(real, {}).get(sid)
                if sub is not None:
                    crash_park.setdefault(cid, {})[f] = sub.opts
            self.broker.unsubscribe(sid, f)
        cm = getattr(self.app, "cm", None)
        if cm is None:
            return
        from emqx_tpu.broker.persistent_session import (
            make_detached_deliverer,
        )
        from emqx_tpu.broker.session import Session, SessionConfig

        import time as _t

        for cid, subs in crash_park.items():
            if cid in cm._detached or cm.get_channel(cid) is not None:
                continue
            if self._owner.get(cid) not in (None, wid):
                # already reconnected onto ANOTHER worker before this
                # cleanup ran: the live session wins, nothing to park
                continue
            scfg = getattr(
                getattr(self.app, "config", None), "session", None
            )
            sess = Session(cid, scfg or SessionConfig())
            expiry = self._owner_expiry.get(cid, 0)
            sess.config.expiry_interval = expiry
            sess.subscriptions = dict(subs)
            deliver = make_detached_deliverer(sess, None, cid)
            for f, opts in subs.items():
                self.broker.subscribe(cid, cid, f, opts, deliver)
            # monotonic like cm.on_channel_closed: detach deadlines must
            # survive wall-clock steps
            cm._detached[cid] = (sess, _t.monotonic() + expiry)
            self.broker.hooks.run("session.detached", cid)
            self.broker.metrics.inc("fabric.sess.crash_parked")

    # -- session ops (emqx_cm parity across workers) ----------------------
    # The router process is the node-level session registry: a client
    # reconnecting onto ANY worker (or an in-process listener) finds its
    # session — takeover of live channels, resume of parked ones, and
    # persistent parking into the app CM's detached store (WAL-backed
    # when session persistence is enabled). Reference:
    # emqx_cm.erl:245-273 open_session, :346-366 takeover_session.

    def _open_pub_gate(self) -> None:
        if self._gate_timer is not None:
            self._gate_timer.cancel()
            self._gate_timer = None
        if self._pub_gate_open:
            return
        if self._held_pubs:
            t = asyncio.get_running_loop().create_task(self._drain_held())
            self._tasks.add(t)
            t.add_done_callback(self._tasks.discard)
        else:
            self._pub_gate_open = True

    async def _drain_held(self) -> None:
        # the gate stays CLOSED while draining: new PUBBs keep appending
        # behind the held ones so per-link order is preserved
        try:
            while self._held_pubs:
                writer, ftype, body = self._held_pubs.pop(0)
                if not writer.is_closing():
                    if ftype == F.T_PUBB_S:
                        await self._on_pub_slab(writer, body)
                    else:
                        await self._on_pub_batch(writer, body)
        finally:
            self._pub_gate_open = True

    def _sess_reply(self, writer, r: int, sess_json, present: bool) -> None:
        if writer is not None and not writer.is_closing():
            writer.write(F.pack_json(F.T_SESS, {
                "op": "open_ack", "r": r, "sess": sess_json,
                "present": bool(present),
            }))

    def _on_sess(self, wid: int, writer, d: dict) -> None:
        op = d.get("op")
        if op == "open":
            self._sess_open(wid, writer, d)
        elif op == "state":
            self._sess_state(d)
        elif op == "park":
            self._sess_park(wid, d)
        elif op == "resume_done":
            self._sess_resume_done(wid, d["cid"])
        elif op == "replay_done":
            # this worker's boot/reconnect flight (SUB replays etc.) is
            # fully on the wire; once every expected worker reports in,
            # held publish batches flow (cross-link ordering gate)
            self._boot_ready.add(wid)
            if (
                not self._pub_gate_open
                and len(self._boot_ready) >= self.expected_workers
            ):
                self._open_pub_gate()
        elif op == "opened":
            # post-CONNACK: the session's negotiated expiry is final
            self._owner[d["cid"]] = wid
            self._owner_expiry[d["cid"]] = float(d.get("expiry", 0))
        elif op == "claim":
            # link-reconnect replay: the worker re-announces its live
            # channels (the drop-path cleared their owner entries)
            self._owner[d["cid"]] = wid
        elif op == "closed":
            if self._owner.get(d["cid"]) == wid:
                self._owner.pop(d["cid"], None)
                self._owner_expiry.pop(d["cid"], None)

    def _sess_open(self, wid: int, writer, d: dict) -> None:
        from emqx_tpu.storage.codec import session_to_json

        cid, clean, r = d["cid"], bool(d.get("clean")), int(d["r"])
        self._gc_resuming()
        cm = getattr(self.app, "cm", None)
        # live on a worker (possibly this one — the take round trip is
        # uniform): hand over or kill the old channel there
        own = self._owner.get(cid)
        if own is not None and own in self._writers:
            ow = self._writers[own]
            if clean:
                ow.write(F.pack_json(F.T_SESS, {"op": "discard",
                                                "cid": cid}))
                self._drop_parked(cid)
                self._owner[cid] = wid
                self._sess_reply(writer, r, None, False)
            else:
                def reply(sj, present, _w=writer, _r=r):
                    self._sess_reply(_w, _r, sj, present)

                self._begin_take(own, cid, reply)
                self._owner[cid] = wid
            return
        # live on an in-process listener of the router
        old = cm.get_channel(cid) if cm is not None else None
        if old is not None:
            cm._channels.pop(cid, None)
            sess = old.kick("discarded" if clean else "takenover")
            self.broker.hooks.run(
                "session.discarded" if clean else "session.takenover", cid
            )
            sj = None
            if sess is not None:
                if not clean:
                    sj = session_to_json(sess)
                self.broker.drop_session_subs(
                    cid, list(sess.subscriptions)
                )
            if clean:
                self._drop_parked(cid)
            self._owner[cid] = wid
            self._sess_reply(writer, r, sj, sj is not None)
            return
        if clean:
            self._drop_parked(cid)
            self._owner[cid] = wid
            self._sess_reply(writer, r, None, False)
            return
        # parked in the router CM's detached store (covers sessions
        # parked by ANY worker, in-process listeners, and
        # persistence-restored ones)
        ent = cm._detached.pop(cid, None) if cm is not None else None
        if ent is not None:
            sess, _deadline = ent
            sj = session_to_json(sess)
            # bankers stay live until resume_done: messages arriving
            # during the handoff keep banking into this Session object
            self._resuming[cid] = {
                "sess": sess,
                "n0": len(sess.mqueue),
                "wid": wid,
                "ts": asyncio.get_running_loop().time(),
            }
            self.broker.hooks.run("session.resumed", cid)
            self.broker.metrics.inc("fabric.sess.resumes")
            self._owner[cid] = wid
            self._sess_reply(writer, r, sj, True)
            return
        self._owner[cid] = wid
        self._sess_reply(writer, r, None, False)

    def _begin_take(self, owner_wid: int, cid: str, reply) -> None:
        """Send 'take' to the live owner; `reply(sess_json, present)`
        fires on its state reply (or on owner death)."""
        tk = self._next_take
        self._next_take += 1
        self._take_pending[tk] = (owner_wid, cid, reply)
        self._writers[owner_wid].write(
            F.pack_json(F.T_SESS, {"op": "take", "cid": cid, "r": tk})
        )

    def _sess_state(self, d: dict) -> None:
        """A worker handed over a live session after 'take'."""
        ent = self._take_pending.pop(int(d["r"]), None)
        if ent is None:
            return
        _owner_wid, _cid, reply = ent
        self.broker.metrics.inc("fabric.sess.takeovers")
        reply(d.get("sess"), d.get("sess") is not None)

    def _sess_park(self, wid: int, d: dict) -> None:
        """Worker client disconnected with expiry > 0: the session parks
        in the ROUTER's detached store — same store as in-process
        listeners, so persistence (WAL + snapshot + restore) and expiry
        sweep apply unchanged, and any future connect finds it."""
        from emqx_tpu.broker.persistent_session import (
            make_detached_deliverer,
        )
        from emqx_tpu.storage.codec import session_from_json

        cid = d["cid"]
        if self._owner.get(cid) == wid:
            self._owner.pop(cid, None)
        self._owner_expiry.pop(cid, None)
        cm = getattr(self.app, "cm", None)
        if cm is None:
            return
        scfg = getattr(
            getattr(self.app, "config", None), "session", None
        )
        from emqx_tpu.broker.session import SessionConfig

        import time as _t

        sess = session_from_json(d["sess"], scfg or SessionConfig())
        deadline = _t.monotonic() + float(d.get("expiry", 0))
        # plain banker now; the persistence hook (if attached) replaces
        # it under the same (sid, filter) key with the WAL-backed one
        deliver = make_detached_deliverer(sess, None, cid)
        for f, opts in sess.subscriptions.items():
            self.broker.subscribe(cid, cid, f, opts, deliver)
        cm._detached[cid] = (sess, deadline)
        self.broker.hooks.run("session.detached", cid)

    def _sess_resume_done(self, wid: int, cid: str) -> None:
        """The new worker installed the session and its SUB frames are
        registered (they precede resume_done on the FIFO link): drop the
        handoff bankers and forward anything banked after the snapshot."""
        ent = self._resuming.pop(cid, None)
        if ent is None:
            return
        sess = ent["sess"]
        self.broker.drop_session_subs(cid, list(sess.subscriptions))
        extras = list(sess.mqueue.peek_all())[ent["n0"]:]
        if not extras:
            return
        full_sid = self._sid(wid, cid)
        for sub_sid, f in list(self._fabric_subs.get(wid, ())):
            if sub_sid != full_sid:
                continue
            _group, real = T.parse_share(f)
            entry = self.broker._subs.get(real, {})
            sub = entry.get(full_sid)
            if sub is None:
                continue
            for m in extras:
                if T.match(m.topic, real):
                    try:
                        sub.deliver(m, sub.opts)
                    except Exception:
                        self.broker.metrics.inc("delivery.errors")

    # -- in-process takeover bridge (ChannelManager.fabrics) --------------
    def owns(self, cid: str) -> bool:
        """True when a live WORKER channel holds this client id."""
        return self._owner.get(cid) in self._writers

    def take_session(self, cid: str, clean: bool) -> "asyncio.Future":
        """Take (or discard) a live worker session on behalf of an
        in-process listener's CONNECT. Resolves with the serialized
        session json (None for clean/absent/dead-owner)."""
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        own = self._owner.get(cid)
        w = self._writers.get(own)
        if w is None or w.is_closing():
            fut.set_result(None)
            return fut
        if clean:
            w.write(F.pack_json(F.T_SESS, {"op": "discard", "cid": cid}))
            self._owner.pop(cid, None)
            fut.set_result(None)
            return fut

        def reply(sj, _present):
            if not fut.done():
                fut.set_result(sj)

        self._begin_take(own, cid, reply)
        self._owner.pop(cid, None)
        # safety: a wedged worker must not stall the CONNECT forever
        loop.call_later(
            10.0, lambda: fut.done() or fut.set_result(None)
        )
        return fut

    def _drop_parked(self, cid: str) -> None:
        cm = getattr(self.app, "cm", None)
        if cm is not None and cid in cm._detached:
            cm._drop_detached(cid)

    RESUME_GC_S = 120.0

    def _gc_resuming(self) -> None:
        """A resume the worker never completed (client vanished between
        CONNECT and install): re-park so the session isn't leaked."""
        now = asyncio.get_running_loop().time()
        cm = getattr(self.app, "cm", None)
        for cid in [
            c for c, e in self._resuming.items()
            if now - e["ts"] > self.RESUME_GC_S
        ]:
            ent = self._resuming.pop(cid)
            if cm is not None:
                import time as _t

                sess = ent["sess"]
                cm._detached[cid] = (
                    sess, _t.monotonic() + sess.config.expiry_interval
                )

    # -- publish side -----------------------------------------------------
    async def _on_pub_batch(self, writer, body: bytes) -> None:
        # `writer` is the CONNECTION's stream, not a wid lookup: a stale
        # ack task must die with its (closed) connection, never resolve a
        # respawned worker's identically-numbered batch
        # section `ingress.decode`, owner side: the frame's records ->
        # Messages, one entry per record
        _prof.begin("ingress.decode")
        msgs = ()
        try:
            seq, records = F.unpack_pub_batch(body)
            msgs = [
                Message(
                    topic=topic,
                    payload=payload,
                    qos=qos,
                    retain=retain,
                    dup=dup,
                    from_client=client,
                    properties=props or {},
                )
                for topic, payload, qos, retain, dup, client, props in records
            ]
        finally:
            _prof.end(len(msgs))
        # enqueue INLINE (per-publisher ordering is an MQTT contract);
        # only the confirm-wait runs as a task so the next frame parses
        # while this batch's ingest window flushes
        results = [await self.broker.apublish_enqueue(m) for m in msgs]
        if not any(r[2] > 0 for r in records):
            return  # pure-QoS0 batch: the worker holds no PUBACKs on it
        t = asyncio.get_running_loop().create_task(
            self._ack_pub_batch(writer, seq, results)
        )
        self._tasks.add(t)
        t.add_done_callback(self._tasks.discard)

    async def _on_pub_slab(self, writer, body: bytes) -> None:
        """Slab PUBB (T_PUBB_S): ONE vectorized header scan recovers
        every record; messages enter the ingest window as SlabMessages —
        topic bytes feed the tokenizer straight from this frame body
        (ops/tokenizer TopicRef gather) and payload copies defer until a
        subscriber needs them (zero-copy ingest, docs/protocol_plane.md)."""
        from emqx_tpu.broker.message import SlabMessage

        # section `ingress.decode`, owner side: the slab's header scan
        # and its SlabMessages, one entry per record
        _prof.begin("ingress.decode")
        msgs = ()
        try:
            slab = F.unpack_pub_slab(body)
            met = self.broker.metrics
            met.inc("fabric.slab.pub.frames")
            if slab.n:
                met.inc("fabric.slab.pub.records", slab.n)
                met.inc("ingest.zerocopy.records", slab.n)
                met.inc(
                    "ingest.zerocopy.deferred.bytes",
                    int(slab.t_len.sum() + slab.p_len.sum()),
                )
            flags = slab.flags
            qos_l = (flags & 3).tolist()
            retain_l = (flags & 4).astype(bool).tolist()
            dup_l = (flags & 8).astype(bool).tolist()
            props_l = (flags & 0x10).astype(bool).tolist()
            msgs = [
                SlabMessage(
                    slab, i, qos=qos_l[i], retain=retain_l[i],
                    dup=dup_l[i], from_client=slab.client(i),
                    properties=slab.props(i) if props_l[i] else None,
                )
                for i in range(slab.n)
            ]
        finally:
            _prof.end(len(msgs))
        # enqueue INLINE (per-publisher ordering), confirm-wait as a task
        # — same contract as the per-record path
        results = [await self.broker.apublish_enqueue(m) for m in msgs]
        if not any(qos_l):
            return  # pure-QoS0 batch: the worker holds no PUBACKs on it
        t = asyncio.get_running_loop().create_task(
            self._ack_pub_batch(writer, slab.seq, results)
        )
        self._tasks.add(t)
        t.add_done_callback(self._tasks.discard)

    async def _ack_pub_batch(self, writer, seq: int, results) -> None:
        """Confirm AFTER every message dispatched/banked (ingest futures
        resolve at the batch-window flush) with per-message delivery
        counts — the worker holds client PUBACKs on this."""
        counts = []
        for r in results:
            if isinstance(r, int):
                counts.append(r)
            else:
                try:
                    counts.append(int(await r))
                except Exception:
                    counts.append(0)
        if not writer.is_closing():
            try:
                writer.write(F.pack_pub_ack(seq, counts))
            except Exception:
                self.broker.metrics.inc("fabric.flush.errors")

    # -- delivery side ----------------------------------------------------
    def enqueue(self, wid: int, handle: int, msg) -> None:
        if wid not in self._writers:
            return
        box = self._outbox.setdefault(wid, [])
        last = self._outbox_last.get(wid)
        if last is not None and last[0] == id(msg) and box:
            last[1].append(handle)
        else:
            handles = [handle]
            box.append((msg, handles))
            self._outbox_last[wid] = (id(msg), handles)
        if not self._flush_scheduled:
            self._flush_scheduled = True
            asyncio.get_running_loop().call_soon(self._flush)

    def enqueue_raw(self, wid: int, handle: int, version: int, rap: bool,
                    msg) -> None:
        """QoS0 fast lane: serialize the PUBLISH once per (version,
        retain, topic) — the cache rides the Message — and queue the
        bytes for direct socket writes worker-side. Congested workers
        fall back to the message path (parked per subscriber there)."""
        if wid not in self._writers:
            return
        if wid in self._parked:
            return self.enqueue(wid, handle, msg)
        retain = bool(msg.retain and rap)
        fb = getattr(msg, "_fb", None)
        if fb is None:
            fb = {}
            msg._fb = fb
        # the (version, retain, topic) key is SHARED with the in-process
        # channel's QoS0 frame cache — safe because both producers emit
        # identical bytes: v5 frames here carry the full encoded
        # properties, exactly like channel.handle_deliver's serialize
        key = (version, retain, msg.topic)
        buf = fb.get(key)
        if buf is None:
            from emqx_tpu.mqtt import codec_native as _nc

            v5 = version == pkt.MQTT_V5
            if _nc.serialize_publish is not None:
                from emqx_tpu.mqtt.frame import encode_properties

                props = encode_properties(msg.properties) if v5 else b""
                buf = _nc.serialize_publish(
                    msg.topic.encode(), msg.payload or b"", 0,
                    1 if retain else 0, 0, 0, props, 1 if v5 else 0,
                )
            else:
                from emqx_tpu.mqtt.frame import serialize

                buf = serialize(
                    pkt.Publish(topic=msg.topic,
                                payload=msg.payload or b"",
                                qos=0, retain=retain, packet_id=None,
                                properties=dict(msg.properties)),
                    version,
                )
            fb[key] = buf
        box = self._raw_outbox.setdefault(wid, [])
        last = self._raw_last.get(wid)
        if last is not None and last[0] is buf and box:
            last[1].append(handle)
        else:
            handles = [handle]
            box.append((buf, handles))
            self._raw_last[wid] = (buf, handles)
        if not self._flush_scheduled:
            self._flush_scheduled = True
            asyncio.get_running_loop().call_soon(self._flush)

    # a worker that stops reading its UDS must not grow this process's
    # write buffer without bound. Past the high-water mark, deliveries
    # PARK in per-subscriber bounded queues (mqueue-overflow parity at
    # the fabric seam, emqx_mqueue.erl: per-session bound + drop-oldest)
    # and a drain task replays them in order once the pipe recovers —
    # one slow worker degrades only its over-quota subscribers, never
    # whole delivery batches
    WRITE_HIGH_WATER = 32 * 1024 * 1024
    PARK_CAP = 1000  # per subscriber handle (SessionConfig.max_mqueue)

    def _flush(self) -> None:
        # section `egress.send` for pool connections: the DLV frames of
        # one loop turn, packed and written; its entries are the deliveries
        _prof.begin("egress.send")
        n = 0
        try:
            n = self._flush_boxes()
        finally:
            _prof.end(max(1, n))

    def _flush_boxes(self) -> int:
        """-> deliveries (record x target handle) handed to the pipes."""
        n = 0
        self._flush_scheduled = False
        self._outbox_last.clear()
        self._raw_last.clear()
        boxes, self._outbox = self._outbox, {}
        raws, self._raw_outbox = self._raw_outbox, {}
        for wid in boxes.keys() | raws.keys():
            records = boxes.get(wid, ())
            raw_records = raws.get(wid, ())
            w = self._writers.get(wid)
            if w is None or w.is_closing():
                continue
            try:
                if (
                    wid in self._parked
                    or w.transport.get_write_buffer_size()
                    > self.WRITE_HIGH_WATER
                ):
                    # congested (or actively draining a prior backlog —
                    # direct writes would reorder per-subscriber flows):
                    # park per handle, bounded, dropping the OLDEST.
                    # Raw-lane bufs park as bufs (replayed verbatim).
                    if records:
                        self._park(wid, records)
                    if raw_records:
                        self._park(wid, raw_records)
                    continue
                for _, handles in records:
                    n += len(handles)
                for _, handles in raw_records:
                    n += len(handles)
                if records:
                    if F.SLAB_WIRE:
                        nf = 0
                        for frame in F.pack_dlv_slabs(records):
                            w.write(frame)
                            nf += 1
                        self.broker.metrics.inc(
                            "fabric.slab.dlv.frames", nf
                        )
                        self.broker.metrics.inc(
                            "fabric.slab.dlv.records", len(records)
                        )
                    else:
                        for frame in F.pack_dlv_batches(records):
                            w.write(frame)
                if raw_records:
                    for frame in F.pack_raw_batches(raw_records):
                        w.write(frame)
                    self.broker.metrics.inc(
                        "fabric.raw.records", len(raw_records)
                    )
            except Exception:
                # one worker's dead pipe (or a malformed record) must not
                # lose the OTHER workers' deliveries in this tick
                self.broker.metrics.inc("fabric.flush.errors")
        return n

    def _park(self, wid: int, records) -> None:
        import collections

        queues = self._parked.setdefault(wid, {})
        for msg, handles in records:
            # slab-escape site: parked deliveries outlive their fabric
            # read buffer (raw-lane bufs park as plain bytes)
            ob = getattr(msg, "own_buffers", None)
            if ob is not None:
                ob()
            for h in handles:
                q = queues.get(h)
                if q is None:
                    q = queues[h] = collections.deque()
                if len(q) >= self.PARK_CAP:
                    q.popleft()  # drop-oldest (emqx_mqueue default)
                    self.broker.metrics.inc("fabric.parked.dropped")
                q.append(msg)
        if wid not in self._drainers:
            t = asyncio.get_running_loop().create_task(
                self._drain_parked(wid)
            )
            self._drainers[wid] = t
            t.add_done_callback(
                lambda _t, _w=wid: self._drainers.pop(_w, None)
            )

    DRAIN_CHUNK = 256  # records per drain write burst

    async def _drain_parked(self, wid: int) -> None:
        """Replay a congested worker's parked deliveries in per-subscriber
        order once its pipe drains below the transport's write high-water
        mark."""
        while True:
            w = self._writers.get(wid)
            queues = self._parked.get(wid)
            if queues is None or not queues:
                self._parked.pop(wid, None)
                return
            if w is None or w.is_closing():
                # worker died: its subscriptions are being dropped; the
                # parked backlog dies with them
                self._parked.pop(wid, None)
                return
            try:
                await w.drain()
            except (ConnectionResetError, BrokenPipeError):
                self._parked.pop(wid, None)
                return
            if w.transport.get_write_buffer_size() > self.WRITE_HIGH_WATER:
                # still over OUR high-water (transport limits are lower):
                # yield and re-check rather than spin
                await asyncio.sleep(0.01)
                continue
            n = 0
            try:
                for h in list(queues):
                    q = queues.get(h)
                    run: list = []
                    while q and n < self.DRAIN_CHUNK:
                        run.append(q.popleft())
                        n += 1
                    if q is not None and not q:
                        del queues[h]
                    # a subscriber's queue may interleave Message
                    # records (DLV path) and raw-lane bufs: emit
                    # same-type runs in pop order so per-subscriber
                    # ordering holds
                    i = 0
                    while i < len(run):
                        j = i
                        is_raw = isinstance(run[i], (bytes, bytearray))
                        while j < len(run) and isinstance(
                            run[j], (bytes, bytearray)
                        ) == is_raw:
                            j += 1
                        seg = [(x, [h]) for x in run[i:j]]
                        packer = (
                            F.pack_raw_batches if is_raw
                            else (F.pack_dlv_slabs if F.SLAB_WIRE
                                  else F.pack_dlv_batches)
                        )
                        for frame in packer(seg):
                            w.write(frame)
                        i = j
                    if n >= self.DRAIN_CHUNK:
                        break
                if n:
                    self.broker.metrics.inc("fabric.parked.replayed", n)
            except Exception:
                self.broker.metrics.inc("fabric.flush.errors")
                self._parked.pop(wid, None)
                return


# ---------------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------------


class WorkerBroker:
    """Broker facade inside a worker: same surface Channel/CM consume
    (subscribe/unsubscribe/apublish/metrics/hooks), forwarding over the
    fabric link. Deliveries come back by subscription handle."""

    def __init__(self, hooks, metrics):
        self.hooks = hooks
        self.metrics = metrics
        self.cm = None  # WorkerChannelManager, set after construction
        self._link_w: Optional[asyncio.StreamWriter] = None
        self._subs: Dict[int, Tuple] = {}  # handle -> (deliver, opts)
        # QoS0 fast lane: handle -> sink with send_bytes (raw writes)
        self._raw_sinks: Dict[int, object] = {}
        self._byname: Dict[Tuple[str, str], int] = {}
        self._next_handle = 1
        # session RPC: reqid -> (future, safety timer)
        self._sess_reqs: Dict[int, Tuple["asyncio.Future", object]] = {}
        self._next_sess_req = 1
        # publish buffer entries: (msg, future) — the future resolves
        # with the message's delivery count when the router acks the
        # batch (PUBB_ACK), which is when the channel releases the
        # client's PUBACK
        self._pub_buf: List[Tuple[Message, Optional["asyncio.Future"]]] = []
        self._pub_scheduled = False
        self._next_seq = 1
        # seq -> (futures, safety TimerHandle cancelled on ack, msgs —
        # kept for re-send across a router-restart link blip)
        self._inflight: Dict[int, Tuple[list, object, list]] = {}
        # handle -> (future resolved by the router's SUB_ACK, safety
        # timer cancelled on ack); the channel holds the client's SUBACK
        # on the future: SUBACK == routable
        self._sub_acks: Dict[int, Tuple["asyncio.Future", object]] = {}
        self.ACK_TIMEOUT_S = 60.0

    # fabric glue
    def attach_link(self, writer) -> None:
        self._link_w = writer

    def detach_link(self) -> None:
        """Link lost (router blip): hold all local state; _send becomes a
        no-op until reattach_link replays it."""
        self._link_w = None

    def reattach_link(self, writer) -> None:
        """Re-dialed after a router restart: replay every live
        subscription (the new router process has empty tables) and
        re-send unacked QoS>0 publish batches (at-least-once across the
        blip; the 60s ack timer keeps bounding each batch)."""
        self._link_w = writer
        for (sid, filter_), h in list(self._byname.items()):
            ent = self._subs.get(h)
            if ent is None:
                continue
            _deliver, opts = ent
            ent_raw = self._raw_sinks.get(h)
            fl = ent_raw[1] if ent_raw else 0
            self._send(
                F.pack_json(
                    F.T_SUB,
                    {
                        "h": h,
                        "sid": sid,
                        "cid": sid,
                        "f": filter_,
                        "qos": opts.qos,
                        "nl": opts.no_local,
                        "rap": opts.retain_as_published,
                        "rh": opts.retain_handling,
                        "ex": True,
                        # replay of an ESTABLISHED subscription: never
                        # re-deliver retained messages the client already
                        # got at its real SUBSCRIBE
                        "nr": True,
                        **({"fl": fl} if fl else {}),
                    },
                )
            )
        for seq in sorted(self._inflight):
            futs, _timer, msgs = self._inflight[seq]
            if any(f is not None and not f.done() for f in futs):
                self._send(self._pack_pub(msgs, seq))
        # re-announce live channels: the router's drop-path cleared
        # their session-owner entries when the link fell
        if self.cm is not None:
            for cid in list(self.cm._channels):
                self._send(
                    F.pack_json(F.T_SESS, {"op": "claim", "cid": cid})
                )

    def _send(self, data: bytes) -> None:
        if self._link_w is not None and not self._link_w.is_closing():
            self._link_w.write(data)

    @staticmethod
    def _pack_pub(msgs, seq: int) -> bytes:
        """Publish batches ride the slab wire (one header table + joined
        regions; T_PUBB_S) unless the env kill-switch forces legacy."""
        if F.SLAB_WIRE:
            return F.pack_pub_slab(msgs, seq)
        return F.pack_pub_batch(msgs, seq)

    # session RPC ---------------------------------------------------------
    SESS_TIMEOUT_S = 30.0

    def sess_open(self, cid: str, clean: bool) -> "asyncio.Future":
        """Ask the router to resolve this client's session (takeover /
        resume / fresh) — emqx_cm.open_session, brokered node-wide.
        Resolves to (sess_json | None, present)."""
        loop = asyncio.get_running_loop()
        r = self._next_sess_req
        self._next_sess_req += 1
        fut = loop.create_future()
        timer = loop.call_later(
            self.SESS_TIMEOUT_S,
            lambda: fut.done() or fut.set_result((None, False)),
        )
        self._sess_reqs[r] = (fut, timer)
        self._send(F.pack_json(F.T_SESS, {
            "op": "open", "r": r, "cid": cid, "clean": bool(clean),
        }))
        return fut

    def sess_opened(self, cid: str, expiry: float) -> None:
        """Post-CONNACK: tell the router this session's negotiated
        expiry (worker-crash parking keys on it)."""
        self._send(F.pack_json(F.T_SESS, {
            "op": "opened", "cid": cid, "expiry": float(expiry),
        }))

    def sess_park(self, cid: str, sess_json, expiry: float) -> None:
        self._send(F.pack_json(F.T_SESS, {
            "op": "park", "cid": cid, "sess": sess_json,
            "expiry": float(expiry),
        }))

    def sess_resume_done(self, cid: str) -> None:
        self._send(F.pack_json(F.T_SESS, {"op": "resume_done",
                                          "cid": cid}))

    def sess_closed(self, cid: str) -> None:
        self._send(F.pack_json(F.T_SESS, {"op": "closed", "cid": cid}))

    def on_sess(self, d: dict) -> None:
        """Inbound session op from the router (pump_link)."""
        from emqx_tpu.storage.codec import session_to_json

        op = d.get("op")
        if op == "open_ack":
            ent = self._sess_reqs.pop(int(d["r"]), None)
            if ent is None:
                return
            fut, timer = ent
            timer.cancel()
            if not fut.done():
                fut.set_result((d.get("sess"), bool(d.get("present"))))
        elif op in ("take", "discard") and self.cm is not None:
            cid = d["cid"]
            ch = self.cm._channels.pop(cid, None)
            det = self.cm._detached.pop(cid, None)
            sj = None
            if ch is not None:
                sess = ch.kick(
                    "takenover" if op == "take" else "discarded"
                )
                self.hooks.run(
                    "session.takenover" if op == "take"
                    else "session.discarded",
                    cid,
                )
                if sess is not None:
                    if op == "take":
                        sj = session_to_json(sess)
                    self.drop_session_subs(
                        cid, list(sess.subscriptions)
                    )
            elif det is not None:
                sess, _dl = det
                if op == "take":
                    sj = session_to_json(sess)
                self.drop_session_subs(cid, list(sess.subscriptions))
            if op == "take":
                self._send(F.pack_json(F.T_SESS, {
                    "op": "state", "r": int(d["r"]), "cid": cid,
                    "sess": sj,
                }))

    # Broker surface ------------------------------------------------------
    # channels probe this before offering a raw-lane sink (the
    # in-process Broker has no fabric seam to shortcut)
    supports_raw_lane = True

    def subscribe(self, sid, client_id, filter_, opts, deliver,
                  replay_retained: bool = True, raw_sink=None,
                  raw_version: int = 0):
        """Returns a future resolved when the router CONFIRMS the
        subscription (SUB_ACK) — the channel awaits it before SUBACK, so
        a publish racing the SUBACK still delivers (the in-process
        broker's subscribe is synchronous for the same contract).
        `replay_retained=False` marks session-resume re-registrations,
        which must never re-deliver retained messages. `raw_sink` opts
        this subscription into the QoS0 fast lane: the router ships
        pre-serialized PUBLISH frames and on_raw writes them straight
        to the sink, bypassing the channel."""
        key = (sid, filter_)
        h = self._byname.get(key)
        if h is None:
            h = self._next_handle
            self._next_handle += 1
            self._byname[key] = h
        self._subs[h] = (deliver, opts)
        if raw_sink is not None:
            self._raw_sinks[h] = (raw_sink, int(raw_version))
        else:
            # re-subscribe that no longer qualifies (e.g. QoS upgrade)
            # must leave the fast lane
            self._raw_sinks.pop(h, None)
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        # NOTE: a down link (router restarting) does NOT fail fast — the
        # registration is recorded locally, reattach_link replays it, and
        # the 30s confirm timer bounds the client's SUBACK wait
        ent = self._sub_acks.get(h)
        if ent is not None and not ent[0].done():
            fut = ent[0]  # re-subscribe racing its own confirm
        else:
            timer = loop.call_later(
                30.0,
                lambda: fut.done() or fut.set_result(False),
            )
            self._sub_acks[h] = (fut, timer)
        self._send(
            F.pack_json(
                F.T_SUB,
                {
                    "h": h,
                    "sid": sid,
                    "cid": client_id,
                    "f": filter_,
                    "qos": opts.qos,
                    "nl": opts.no_local,
                    "rap": opts.retain_as_published,
                    "rh": opts.retain_handling,
                    # per-client resubscribe flag set by the worker-side
                    # channel (rh=1 retained-replay suppression)
                    "ex": bool(getattr(opts, "_existing", False)),
                    **({} if replay_retained else {"nr": True}),
                    **({"fl": raw_version} if raw_sink is not None
                       else {}),
                },
            )
        )
        return fut

    def on_sub_ack(self, h: int) -> None:
        ent = self._sub_acks.pop(h, None)
        if ent is None:
            return
        fut, timer = ent
        timer.cancel()
        if not fut.done():
            fut.set_result(True)

    def unsubscribe(self, sid, filter_) -> bool:
        h = self._byname.pop((sid, filter_), None)
        if h is None:
            return False
        self._subs.pop(h, None)
        self._raw_sinks.pop(h, None)
        ent = self._sub_acks.pop(h, None)
        if ent is not None:
            # unsubscribing a confirm-pending handle (e.g. the channel's
            # failed-subscribe rollback): cancel the timer and resolve
            # so nothing leaks or waits on an ack that can't arrive
            fut, timer = ent
            timer.cancel()
            if not fut.done():
                fut.set_result(False)
        self._send(F.pack_json(F.T_UNSUB, {"sid": sid, "f": filter_}))
        return True

    def drop_session_subs(self, sid, filters) -> None:
        for f in list(filters):
            self.unsubscribe(sid, f)

    def _enqueue_pub(self, msg: Message):
        """QoS>0 returns a Future resolved by the router's PUBB_ACK (the
        client's PUBACK waits on it); QoS0 is fire-and-forget — coupling
        it to the ack round-trip measured ~4x e2e throughput loss for a
        guarantee QoS0 never promises."""
        self.metrics.inc("messages.received")
        fut = None
        if msg.qos > 0:
            fut = asyncio.get_running_loop().create_future()
        self._pub_buf.append((msg, fut))
        if not self._pub_scheduled:
            self._pub_scheduled = True
            asyncio.get_running_loop().call_soon(self._flush_pubs)
        return fut if fut is not None else 0

    def _flush_pubs(self) -> None:
        self._pub_scheduled = False
        buf, self._pub_buf = self._pub_buf, []
        if not buf:
            return
        # chunk below the fabric frame cap: ~64 pipelined max-size
        # publishes in one tick would otherwise exceed the receiver's
        # MAX_FRAME and tear down the link
        start = 0
        while start < len(buf):
            size = 8
            end = start
            while end < len(buf):
                r = F.pub_record_size(buf[end][0])
                if end > start and size + r > F.MAX_BODY:
                    break
                size += r
                end += 1
            chunk = buf[start:end]
            start = end
            seq = self._next_seq
            self._next_seq += 1
            futs = [f for _, f in chunk]
            msgs = [m for m, _ in chunk]
            if any(f is not None for f in futs):
                # safety: a lost ack (router bug / torn link mid-restart)
                # must not wedge every publisher's PUBACK forever
                timer = asyncio.get_running_loop().call_later(
                    self.ACK_TIMEOUT_S, self._expire_batch, seq
                )
                self._inflight[seq] = (futs, timer, msgs)
            self._send(self._pack_pub(msgs, seq))

    def _expire_batch(self, seq: int) -> None:
        ent = self._inflight.pop(seq, None)
        if ent:
            self.metrics.inc("fabric.puback.timeouts")
            for f in ent[0]:
                if f is not None and not f.done():
                    # -1 = the 'never no-subscribers' sentinel (see
                    # channel._send_pub_ack): a late-but-delivered batch
                    # must not tell v5 clients NO_MATCHING_SUBSCRIBERS
                    f.set_result(-1)

    def on_pub_ack(self, seq: int, counts) -> None:
        ent = self._inflight.pop(seq, None)
        if not ent:
            return
        futs, timer, _msgs = ent
        timer.cancel()
        for f, n in zip(futs, counts):
            if f is not None and not f.done():
                f.set_result(n)

    async def apublish_enqueue(self, msg: Message):
        """-> int (dropped) or a Future resolving with the delivery count
        once the router CONFIRMS the batch — same contract as the real
        Broker's ingest path, so the channel's ack queue holds each
        QoS1/2 PUBACK until the message is actually routed."""
        msg = await self.hooks.arun_fold("message.publish", (), msg)
        if msg is None or msg.headers.get("allow_publish") is False:
            self.metrics.inc("messages.dropped")
            return 0
        return self._enqueue_pub(msg)

    async def apublish(self, msg: Message) -> int:
        r = await self.apublish_enqueue(msg)
        return r if isinstance(r, int) else await r

    def publish(self, msg: Message) -> int:
        msg = self.hooks.run_fold("message.publish", (), msg)
        if msg is None or msg.headers.get("allow_publish") is False:
            return 0
        self._enqueue_pub(msg)  # fire-and-forget (sync callers: will, sys)
        return 0

    # delivery ------------------------------------------------------------
    def on_raw(self, records) -> None:
        """QoS0 fast lane: pre-serialized PUBLISH frames from the
        router, written straight to subscriber sockets (the negotiated
        eligibility guarantees no channel-side work is being skipped:
        qos 0, no mountpoint, empty delivered/completed chains)."""
        sinks = self._raw_sinks
        sent = errs = 0
        for buf, handles in records:
            for h in handles:
                ent = sinks.get(h)
                if ent is None:
                    continue
                try:
                    ent[0].send_bytes(buf)
                    sent += 1
                except Exception:
                    errs += 1
        if sent:
            self.metrics.inc("packets.sent", sent)
        if errs:
            self.metrics.inc("delivery.errors", errs)

    def on_dlv_slab(self, slab) -> None:
        """Slab DLV (T_DLV_S): handles resolve FIRST, so a record whose
        targets all unsubscribed mid-flight skips decode entirely; one
        lazy SlabMessage per record is shared across its targets (str
        decode / payload copy happen at most once, on first need)."""
        from emqx_tpu.broker.message import SlabMessage

        subs = self._subs
        flags = slab.flags
        for i in range(slab.n):
            ents = [
                ent
                for h in slab.handles(i).tolist()
                if (ent := subs.get(h)) is not None
            ]
            if not ents:
                continue
            f = int(flags[i])
            msg = SlabMessage(
                slab, i, qos=f & 3, retain=bool(f & 4),
                from_client=slab.client(i), properties=slab.props(i),
            )
            if f & 8:
                msg.headers["retained"] = True
            for deliver, opts in ents:
                try:
                    deliver(msg, opts)
                except Exception:
                    self.metrics.inc("delivery.errors")

    def on_delivery(self, topic, payload, qos, retain, retained, client,
                    props, handles) -> None:
        msg = Message(
            topic=topic,
            payload=payload,
            qos=qos,
            retain=retain,
            from_client=client,
            properties=props or {},
        )
        if retained:
            msg.headers["retained"] = True
        for h in handles:
            ent = self._subs.get(h)
            if ent is None:
                continue
            deliver, opts = ent
            try:
                deliver(msg, opts)
            except Exception:
                self.metrics.inc("delivery.errors")


class WorkerChannelManager:
    """emqx_cm semantics ACROSS workers: session open/takeover/resume and
    persistent parking are brokered by the router process, so a client
    reconnecting onto a DIFFERENT worker (or an in-process listener of
    the router) still finds its session. Reference:
    emqx_cm.erl:245-273 open_session, :346-366 takeover_session —
    there the registry is node-level; here the router process is the
    node."""

    def __init__(self, broker: "WorkerBroker"):
        self.broker = broker
        broker.cm = self
        self._channels: Dict[str, object] = {}
        # after CONNACK the negotiated expiry is final (v5 property /
        # v4 clean_start zeroing applied): announce it for crash parking
        broker.hooks.add(
            "client.connected",
            lambda ci, ch: broker.sess_opened(
                ch.client_id, ch.session.config.expiry_interval
            ) if getattr(ch, "session", None) is not None else None,
            tag="worker_cm.opened",
        )
        # transient only (mid-takeover stash); authoritative parking
        # lives in the ROUTER's detached store
        self._detached: Dict[str, Tuple] = {}

    def get_channel(self, client_id: str):
        return self._channels.get(client_id)

    def channel_count(self) -> int:
        return len(self._channels)

    def client_ids(self):
        return list(self._channels)

    def open_session(self, channel):
        """Awaitable (the channel awaits it): one router round trip
        resolves discard/takeover/resume node-wide."""
        return self._open_async(channel)

    async def _open_async(self, channel):
        from emqx_tpu.broker.session import Session
        from emqx_tpu.storage.codec import session_from_json

        cid = channel.client_id
        sj, present = await self.broker.sess_open(
            cid, channel.clean_start
        )
        session = None
        if sj is not None:
            try:
                session = session_from_json(sj, channel.config.session)
            except Exception:
                self.broker.metrics.inc("fabric.sess.decode_errors")
        if session is not None:
            self.broker.hooks.run("session.resumed", cid)
            for f, opts in session.subscriptions.items():
                # re-registration of a live session: confirm futures are
                # intentionally not awaited (CONNACK carries `present`;
                # deliveries begin as each SUB registers) and retained
                # must not replay
                self.broker.subscribe(
                    cid, cid, f, opts, channel._make_deliverer(opts),
                    replay_retained=False,
                )
            # SUB frames precede resume_done on the FIFO link: the
            # router flushes handoff-banked messages to the handles
            # registered above
            self.broker.sess_resume_done(cid)
        else:
            session = Session(cid, channel.config.session)
            self.broker.hooks.run("session.created", cid)
            present = False
        # same-worker concurrent CONNECT race: both were awaiting the
        # router; the loser installed first and must be kicked
        old = self._channels.pop(cid, None)
        if old is not None and old is not channel:
            old.kick("takenover")
        self._channels[cid] = channel
        self.broker.metrics.gauge_set(
            "connections.count", len(self._channels)
        )
        return session, bool(present)

    def on_channel_closed(self, channel, reason: str) -> None:
        from emqx_tpu.storage.codec import session_to_json

        cid = channel.client_id
        if self._channels.get(cid) is not channel:
            return  # already replaced by takeover/discard
        del self._channels[cid]
        self.broker.metrics.gauge_set(
            "connections.count", len(self._channels)
        )
        sess = channel.session
        if sess is None:
            return
        expiry = sess.config.expiry_interval
        if expiry > 0:
            # park at the ROUTER: survives this worker, resumable from
            # any worker/listener, WAL-backed when persistence is on
            self.broker.sess_park(cid, session_to_json(sess), expiry)
            self.broker.drop_session_subs(
                cid, list(sess.subscriptions)
            )
            self.broker.hooks.run("session.detached", cid)
        else:
            self.broker.drop_session_subs(
                cid, list(sess.subscriptions)
            )
            self.broker.hooks.run("session.terminated", cid, reason)
            self.broker.sess_closed(cid)

    def kick_client(self, client_id: str) -> bool:
        ch = self._channels.pop(client_id, None)
        if ch is None:
            return False
        sess = ch.kick("kicked")
        if sess is not None:
            self.broker.drop_session_subs(
                client_id, list(sess.subscriptions)
            )
        self.broker.sess_closed(client_id)
        return True

    def sweep_expired(self, now=None) -> int:
        return 0  # expiry lives with the router's detached store


def worker_main(
    wid: int,
    bind: str,
    port: int,
    uds_path: str,
    config,
) -> None:
    """Entry point of a spawned connection worker (own interpreter; the
    TPU is never touched here — jax stays uninitialized)."""
    asyncio.run(_worker_async(wid, bind, port, uds_path, config))


async def _worker_async(wid, bind, port, uds_path, config) -> None:
    from emqx_tpu.app import build_guard_hooks
    from emqx_tpu.broker.hooks import Hooks
    from emqx_tpu.broker.metrics import Metrics
    from emqx_tpu.transport.connection import Connection

    hooks = Hooks()
    metrics = Metrics()
    broker = WorkerBroker(hooks, metrics)
    channel_config = build_guard_hooks(config, hooks)
    cm = WorkerChannelManager(broker)

    # fabric link to the router process (retry: the router may still be
    # binding the UDS when workers spawn)
    for attempt in range(100):
        try:
            reader, writer = await asyncio.open_unix_connection(uds_path)
            break
        except (FileNotFoundError, ConnectionRefusedError):
            await asyncio.sleep(0.05 * (attempt + 1))
    else:
        raise RuntimeError(f"worker {wid}: router fabric not reachable")
    writer.write(F.pack_frame(F.T_HELLO, wid.to_bytes(2, "little")))
    broker.attach_link(writer)
    # boot flight complete (nothing to replay on first dial): the router
    # holds cross-worker publish dispatch until every worker reports in
    writer.write(F.pack_json(F.T_SESS, {"op": "replay_done"}))

    # a router-process blip must not drop every client on this worker
    # (the reference's layered supervision restarts subsystems without
    # dropping esockd connections, emqx_machine_boot restart ordering):
    # hold connections, re-dial the (pid-stable) UDS path, replay SUBs
    # and unacked publish batches. Only a router gone past the window
    # ends the worker.
    RECONNECT_WINDOW_S = 60.0

    async def pump_link():
        nonlocal reader, writer
        loop = asyncio.get_running_loop()
        while True:
            try:
                while True:
                    ftype, body = await F.read_frame(reader)
                    if ftype == F.T_DLV:
                        for rec in F.unpack_dlv_batch(body):
                            broker.on_delivery(*rec)
                    elif ftype == F.T_DLV_S:
                        broker.on_dlv_slab(F.unpack_dlv_slab(body))
                    elif ftype == F.T_RAW:
                        broker.on_raw(F.unpack_raw_batch(body))
                    elif ftype == F.T_PUBB_ACK:
                        broker.on_pub_ack(*F.unpack_pub_ack(body))
                    elif ftype == F.T_SUB_ACK:
                        import json as _json

                        broker.on_sub_ack(int(_json.loads(body)["h"]))
                    elif ftype == F.T_SESS:
                        import json as _json

                        broker.on_sess(_json.loads(body))
            except (
                asyncio.IncompleteReadError,
                # OSError covers ConnectionResetError AND BrokenPipeError
                # — a write racing the router's shutdown surfaces on the
                # read waiter as EPIPE, and must trigger the re-dial, not
                # kill the worker (and its clients) with it
                OSError,
                ValueError,
            ):
                pass
            broker.detach_link()
            broker.metrics.inc("fabric.link.lost")
            deadline = loop.time() + RECONNECT_WINDOW_S
            nc = None
            while loop.time() < deadline:
                try:
                    nc = await asyncio.open_unix_connection(uds_path)
                    break
                except (FileNotFoundError, ConnectionRefusedError, OSError):
                    await asyncio.sleep(0.25)
            if nc is None:
                os._exit(0)  # router gone for good: nothing to serve
            reader, writer = nc
            writer.write(F.pack_frame(F.T_HELLO, wid.to_bytes(2, "little")))
            broker.reattach_link(writer)
            writer.write(F.pack_json(F.T_SESS, {"op": "replay_done"}))
            broker.metrics.inc("fabric.link.reconnected")

    link_task = asyncio.create_task(pump_link())

    conns: set = set()

    async def on_client(r, w):
        conn = Connection(broker, cm, r, w, channel_config)
        task = asyncio.current_task()
        conns.add(task)
        try:
            await conn.run()
        finally:
            conns.discard(task)

    server = await asyncio.start_server(
        on_client, bind, port, reuse_port=True
    )
    try:
        await asyncio.gather(server.serve_forever(), link_task)
    except asyncio.CancelledError:
        pass


# ---------------------------------------------------------------------------
# pool management (router side)
# ---------------------------------------------------------------------------


class WorkerPool:
    """Spawns and supervises the worker processes for one listener.

    Workers launch as `python -m emqx_tpu.transport.workers ...` with the
    app config re-serialized to JSON — plain subprocesses, no
    multiprocessing __main__ re-import (which breaks under embedding
    hosts) and no pickle coupling."""

    def __init__(self, app, bind: str, port: int, n_workers: int, config):
        self.app = app
        self.bind = bind
        self.port = port
        self.n = n_workers
        self.config = config
        # pid-free path: a RESTARTED router process rebinds the same
        # socket, so surviving workers can re-dial it. bind+port key the
        # broker instance on this host (pid in the name would break
        # restart re-dial; bind alone distinguishes two brokers sharing
        # a port number on different addresses)
        safe_bind = bind.replace(":", "_").replace("/", "_")
        base = f"emqx-tpu-fabric-{safe_bind}-{port}"
        self.uds_path = os.path.join(tempfile.gettempdir(), base + ".sock")
        self._cfg_path = os.path.join(tempfile.gettempdir(), base + ".json")
        self.fabric = WorkerFabric(app, self.uds_path,
                                   expected_workers=n_workers)
        self._procs: List = []

    # supervision: a crashed worker respawns (one-for-one, like the
    # reference's esockd supervisor over connection processes); a worker
    # that dies repeatedly within the window stays down to avoid a
    # crash-loop eating the host
    RESPAWN_WINDOW_S = 60.0
    MAX_RESPAWNS_PER_WINDOW = 5

    def _spawn(self, wid: int):
        import subprocess
        import sys

        return subprocess.Popen(
            [
                sys.executable,
                "-m",
                "emqx_tpu.transport.workers",
                "--wid", str(wid),
                "--bind", self.bind,
                "--port", str(self.port),
                "--uds", self.uds_path,
                "--config", self._cfg_path,
            ],
        )

    def _write_worker_config(self) -> None:
        import dataclasses
        import json

        with open(self._cfg_path, "w") as f:
            json.dump(dataclasses.asdict(self.config), f, default=str)

    async def start(self) -> None:
        await self.fabric.start()
        # config snapshot for the worker processes: written off-loop (the
        # dump can hit a slow tmpdir while listeners are already serving)
        await asyncio.get_running_loop().run_in_executor(
            None, self._write_worker_config
        )
        for wid in range(self.n):
            self._procs.append(self._spawn(wid))
        self._respawns: List[float] = []
        self._supervisor = asyncio.get_running_loop().create_task(
            self._supervise()
        )

    async def _supervise(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            await asyncio.sleep(2.0)
            for wid, p in enumerate(self._procs):
                if p.poll() is None:
                    continue
                now = loop.time()
                self._respawns = [
                    t for t in self._respawns
                    if now - t < self.RESPAWN_WINDOW_S
                ]
                if len(self._respawns) >= self.MAX_RESPAWNS_PER_WINDOW:
                    self.app.broker.metrics.inc("fabric.worker.crash_loop")
                    continue
                self._respawns.append(now)
                self.app.broker.metrics.inc("fabric.worker.respawns")
                self._procs[wid] = self._spawn(wid)

    def describe(self) -> dict:
        """Listener-style status row (mgmt REST surface)."""
        alive = sum(1 for p in self._procs if p.poll() is None)
        return {
            "id": f"tcp:workers:{self.port}",
            "type": "tcp",
            "name": f"workers:{self.port}",
            "bind": f"{self.bind}:{self.port}",
            "running": alive > 0,
            "workers": self.n,
            "workers_alive": alive,
            "workers_connected": len(self.fabric._writers),
            "max_connections": 0,
            "current_connections": 0,
            "port": self.port,
        }

    async def wait_ready(self, timeout: float = 30.0) -> None:
        """Block until every worker has dialed the fabric."""
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout
        while len(self.fabric._writers) < self.n:
            if loop.time() > deadline:
                raise TimeoutError(
                    f"{len(self.fabric._writers)}/{self.n} workers ready"
                )
            await asyncio.sleep(0.05)

    async def stop(self) -> None:
        sup = getattr(self, "_supervisor", None)
        if sup is not None:
            sup.cancel()
            try:
                await sup
            except asyncio.CancelledError:
                pass
            self._supervisor = None
        for p in self._procs:
            p.terminate()
        for p in self._procs:
            try:
                p.wait(timeout=5)
            except Exception:
                p.kill()
        self._procs.clear()
        await self.fabric.stop()
        try:
            os.unlink(self._cfg_path)
        except FileNotFoundError:
            pass


def _cli() -> None:
    import argparse
    import json

    from emqx_tpu.config.schema import load_config

    ap = argparse.ArgumentParser(prog="emqx_tpu.transport.workers")
    ap.add_argument("--wid", type=int, required=True)
    ap.add_argument("--bind", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--uds", required=True)
    ap.add_argument("--config", required=True)
    a = ap.parse_args()
    with open(a.config) as f:
        c = load_config(json.load(f))
    prof_dir = os.environ.get("EMQX_TPU_WORKER_PROFILE")
    if prof_dir:
        # perf tooling: profile this worker's whole life, dump on exit
        # (SIGTERM mapped to sys.exit so the pool's terminate() still
        # flushes the profile)
        import cProfile
        import signal as _sig

        pr = cProfile.Profile()

        def _dump(*_):
            pr.disable()
            pr.dump_stats(
                os.path.join(prof_dir, f"worker-{a.wid}.prof")
            )
            os._exit(0)

        _sig.signal(_sig.SIGTERM, _dump)
        pr.enable()
    worker_main(a.wid, a.bind, a.port, a.uds, c)


if __name__ == "__main__":
    _cli()
