"""The pub/sub kernel: subscribe/unsubscribe/publish/dispatch.

Parity with the reference kernel (apps/emqx/src/emqx_broker.erl):
- subscribe/unsubscribe maintain the subscriber registry + route table
  (emqx_broker.erl:127-160 ETS inserts + :441-454 route add)
- publish runs the 'message.publish' fold, matches routes, and dispatches
  to local subscribers (:204-215 publish, :505-530 do_dispatch)
- publish_batch is the TPU-era addition: many topics matched in one device
  kernel, then fanned out (the reference has no batch path — its hot loop
  is per-message, which is exactly what this design replaces)

Dispatch hands (session, opts, msg) triples to each subscriber's channel via
the session's registered deliver callback. Shared-subscription groups
($share/g/t) are delegated to SharedSub.
"""

from __future__ import annotations

import asyncio
from itertools import repeat
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from emqx_tpu.broker.hooks import Hooks, default_hooks
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.metrics import Metrics
from emqx_tpu.broker.router import Router
from emqx_tpu.broker.shared_sub import SharedSub
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.observe import profiler as _prof
from emqx_tpu.observe.spans import TRACE_HEADER
from emqx_tpu.ops import topics as T
from emqx_tpu.transport.egress import flush_dirty
from emqx_tpu.utils.tracepoints import tp

# deliverer: called with (msg, subopts); returns True if accepted
Deliverer = Callable[[Message, pkt.SubOpts], None]

_dispatch_pool_inst = None


def dispatch_pool():
    """Process-wide executor for device route launches (one device per
    process). BOUNDED and dedicated: the default asyncio executor is
    shared with every other run_in_executor caller (config writes,
    DNS), so device launches could queue behind
    unrelated blocking work — and an unbounded shared queue is exactly
    the backlog shape the r02/r04 bench notes flagged. Two workers are
    the double-buffer: batch N+1's tokenize/launch phase runs on the
    second worker while batch N's worker blocks in its readback."""
    global _dispatch_pool_inst
    if _dispatch_pool_inst is None:
        from concurrent.futures import ThreadPoolExecutor

        _dispatch_pool_inst = ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="tpu-dispatch"
        )
    return _dispatch_pool_inst


class Subscriber:
    __slots__ = (
        "sid", "deliver", "opts", "client_id", "slot", "filter",
        "semantic",
    )

    def __init__(self, sid: str, client_id: str, deliver: Deliverer, opts: pkt.SubOpts):
        self.sid = sid
        self.client_id = client_id
        self.deliver = deliver
        self.opts = opts
        self.slot = -1  # device bitmap slot (non-shared subs only)
        self.filter = ""  # the real (share-stripped) subscription filter
        # embedding-filtered subscription (docs/semantic_routing.md):
        # the slot lives in the SemanticTable, NOT the subscriber
        # table — delivery requires topic AND similarity
        self.semantic = False


class PendingDispatch:
    """A launched-but-unsettled batch dispatch (adispatch_begin).

    `ready`: side-effect-free future resolving when the device round
    trip completes (never triggers fan-out — safe to race/poll).
    `complete()`: coroutine performing the host fan-out + returning
    per-message delivery counts; callers invoke it in launch order.
    Awaiting the object is shorthand for awaiting complete()."""

    __slots__ = ("ready", "_complete")

    def __init__(self, ready, complete):
        self.ready = ready
        self._complete = complete

    def complete(self):
        return self._complete()

    def __await__(self):
        return self._complete().__await__()


def run_target(deliver):
    """Where `deliver` offers a delivery run: the object that takes a
    settled batch's deliveries through it in one call,
    `handle_deliver_run([(msg, opts), ...])` (docs/protocol_plane.md "The
    delivery run"), and the key they are collected under: the connection,
    not the subscription. A deliverer offers one by being the closure its
    target names: a function closed over the target alone whose code is
    the target's `run_deliverer` (`Channel._make_deliverer`'s). The
    closure a subscription already holds says it, so a million
    subscriptions carry no object and no slot more. None (the pool's, a
    gateway's, a persistent session's, the cluster's, a test's stub):
    called per message."""
    cells = getattr(deliver, "__closure__", None)
    if cells is not None and len(cells) == 1:
        target = cells[0].cell_contents
        if getattr(target, "run_deliverer", None) is deliver.__code__:
            return target
    return None


class DeliveryRuns:
    """A settled batch's deliveries to the connections that offer a run
    (`run_target`), collected in message order while the rows are
    dispatched (`hand`) and handed over one call a connection
    (`deliver`): before the batch's counts are final and before its
    write boundary.

    `counts[row]` is row's local fan-out. A collected delivery counts when
    it is collected; what a run gives back is settled per message after
    the runs and taken off again where nobody took it. An item the run
    reports as failed was offered to its connection: a plain
    subscription's then counts as when its deliverer raised, a pick fails
    over to the group's next member. A run that raised as a whole took
    nothing: its items go the per-message path, member and all.
    `pick_stats`: picks handed over, stale among them. Nothing overtakes a pending
    run: whoever delivers on the spot to a connection that may hold one
    (a flagged row's CPU dispatch, a host-side group pick) calls
    `deliver` first."""

    __slots__ = ("broker", "counts", "pick_stats", "row", "_runs")

    def __init__(self, broker: "Broker", rows: int):
        self.broker = broker
        self.counts = [0] * rows
        self.pick_stats = [0, 0]
        self.row = 0  # the row being dispatched
        # target -> its run as four flat lists (messages, options, rows,
        # origins: the Subscriber, or a pick's (real, group, idx)). No
        # object per delivery is held over the batch: 48,000 deliveries
        # a batch (`fanout_1k`) would be a young collector pass each
        self._runs: Dict = {}

    def hand(self, sub: "Subscriber", msg: Message, pick=None) -> None:
        """One delivery of row `self.row`: into its connection's run, or
        made on the spot where the deliverer offers none (raises what
        that raises: the caller's NACK). `pick`: it is a $share pick's."""
        target = run_target(sub.deliver)
        if target is None:
            sub.deliver(msg, sub.opts)
            return
        run = self._runs.get(target)
        if run is None:
            run = self._runs[target] = ([], [], [], [])
        run[0].append(msg)
        run[1].append(sub.opts)
        run[2].append(self.row)
        run[3].append(sub if pick is None else pick)

    def deliver(self) -> None:
        """Hand every pending run over, then settle what came back."""
        if not self._runs:
            return
        runs, self._runs = self._runs, {}
        back: List = []  # (msg, row, origin, candidates that refused it)
        handed = carried = 0
        for target, (msgs, opts, rows, origins) in runs.items():
            try:
                failed = target.handle_deliver_run(list(zip(msgs, opts)))
            except Exception:  # noqa: BLE001 — the whole run, per message
                back += zip(msgs, rows, origins, repeat(0))
                continue
            handed += 1
            carried += len(msgs) - len(failed)
            for j, _ in failed:
                back.append((msgs[j], rows[j], origins[j], 1))
        b = self.broker
        b.metrics.inc("dispatch.runs", handed)
        b.metrics.inc("dispatch.run.deliveries", carried)
        for msg, row, org, refused in back:
            if type(org) is tuple:
                took = b.shared.dispatch_picked(*org, msg, refused=refused)
                self.pick_stats[1] += 1 - took
            elif refused:
                b.metrics.inc("delivery.errors")
                took = 0
            else:
                took = b._deliver_one(org, msg)
            self.counts[row] += took - 1


class Broker:
    def __init__(
        self,
        router: Optional[Router] = None,
        hooks: Optional[Hooks] = None,
        metrics: Optional[Metrics] = None,
    ):
        # NOT `router or Router()`: Router defines __len__, so an EMPTY
        # router is falsy and would be silently swapped for a default one
        self.router = router if router is not None else Router()
        self.hooks = hooks or default_hooks
        self.metrics = metrics or Metrics()
        # filter -> {sid -> Subscriber}
        self._subs: Dict[str, Dict[str, Subscriber]] = {}
        self.shared = SharedSub()
        # device fan-out state: every non-shared Subscriber entry gets a
        # dense bitmap slot; (filter id, slot) rides to the device so the
        # route_step kernel resolves topic -> subscriber bits directly
        # (emqx_broker.erl:505-530 do_dispatch, as one gather+OR)
        from emqx_tpu.models.router_model import GroupTable, SubscriberTable

        # router.sub_table policy (docs/serving_pipeline.md): the CSR
        # representation serves through the compact readback contract,
        # so fanout_compact=False pins the dense matrix (the fallback)
        mc = self.router.matcher_config
        self.subtab = SubscriberTable(
            mode=(
                getattr(mc, "sub_table", "auto")
                if getattr(mc, "fanout_compact", True)
                else "dense"
            ),
        )
        # running plain-subscription count: subscription_count() used to
        # RECOMPUTE sum(len(entry)) per subscribe/unsubscribe, turning a
        # million-connection subscribe storm into O(N^2) gauge upkeep
        self._plain_subs = 0
        # subscriptions removed and sessions dropped, ever: what may have
        # left garbage among frozen objects (observe/gc_policy.py thaws on it)
        self.released = 0
        # $share groups mirrored as device lane segments so the kernel
        # resolves the member pick too (emqx_shared_sub.erl:234-285)
        self.grouptab = GroupTable()
        self._slot_subs: List[Optional[Subscriber]] = []
        self._free_slots: List[int] = []
        self._device = None  # lazy DeviceRouter
        self.mesh = None  # jax Mesh => SPMD serving (set by app/tests)
        # cluster mesh-slice label (ClusterNode.attach_mesh_slice):
        # stamped onto router.device_step spans by the mesh engine
        self.shard_label = None
        self.ingest = None  # BatchIngest, attached by the app
        # RetainedStormFeed (broker/retained_feed.py), attached by the
        # app: pending wildcard-subscribe replay storms ride the next
        # device launch via the fused kernel instead of paying their own
        self.retained_feed = None
        # SpanRecorder (observe/spans.py), attached by the app/tests:
        # causal span tracing across the batch boundary. None = off; the
        # hot path pays one attribute check per publish
        self.spans = None
        # ClusterNode, attached by the app when cluster.enable: routes
        # replicate on first/last subscriber, publishes forward to remote
        # route owners (emqx_broker.erl:278-293 forward regime)
        self.cluster = None
        # DegradeController (broker/degrade.py), attached by the app:
        # device-path circuit breaker + bounded retry policy. None =
        # legacy behavior (a failed launch fails its batch's publishes)
        self.degrade = None
        # SessionStore (broker/session_store.py), attached by the app
        # when session.device_store: pending inflight writes + QoS
        # retry/expiry sweeps ride serving launches as the fused
        # session-ack stage (no extra launch or readback per batch)
        self.session_store = None
        # SemanticRouting (broker/semantic.py), attached by the app
        # when semantic.enable: embedding-filter subscriptions ride the
        # serving launch as a fused similarity matmul; None = the
        # semantic stage never traces (docs/semantic_routing.md)
        self.semantic = None
        # RuleEngine's device-predicate seam (rules/engine.py
        # attach_device): compiled WHERE masks evaluate inside the
        # serving launch and fire at settle; None = hook-path rules
        self.rule_hook = None

    # -- subscribe side ---------------------------------------------------
    def subscribe(
        self,
        sid: str,
        client_id: str,
        filter_: str,
        opts: pkt.SubOpts,
        deliver: Deliverer,
        embedding=None,
        sem_threshold=None,
    ) -> None:
        """`embedding`/`sem_threshold`: an optional embedding filter
        (docs/semantic_routing.md) — the subscription then delivers on
        topic match AND similarity (its slot lives in the semantic
        table, not the fan-out table). Ignored (plain subscribe) when
        no SemanticRouting is attached or the filter is $shared."""
        group, real = T.parse_share(filter_)
        sub = Subscriber(sid, client_id, deliver, opts)
        sub.filter = real
        if embedding is not None and (
            self.semantic is None or group is not None
        ):
            # no semantic plane (or a $share filter, which resolves by
            # group pick, not slots): degrade to a plain subscription
            self.metrics.inc("semantic.subscribe.rejected")
            embedding = None
        if group is not None:
            # section `broker.share_subscribe`: what a table of groups
            # adds to a SUBSCRIBE (membership, the group's route, its
            # device row); entries = shared subscriptions
            _prof.begin("broker.share_subscribe")
            try:
                self._subscribe_shared(group, real, sub)
            finally:
                _prof.end()
        else:
            entry = self._subs.setdefault(real, {})
            prev = entry.get(sid)
            first = not entry
            entry[sid] = sub
            if prev is None:
                self._plain_subs += 1
            fid = (
                self.router.add_route(real)
                if first
                else None
            )
            if first and self.cluster is not None:
                self.cluster._replicate_add(real)
            if prev is not None:
                # re-subscribe with fresh opts: keep the slot, swap the sub
                sub.slot = prev.slot
                self._slot_subs[sub.slot] = sub
            else:
                sub.slot = self._alloc_slot(sub)
            if fid is None:
                # route already existed: resolve its id (one probe)
                fid = self.router.filter_id(real)
            if embedding is not None:
                # embedding-filtered subscription: the slot binds into
                # the semantic table (topic scope = this filter's fid;
                # '#' scopes degenerate to unscoped similarity-only)
                sub.semantic = True
                if prev is not None and not prev.semantic:
                    if fid is not None:
                        self.subtab.remove(fid, sub.slot)
                th = (
                    self.semantic.default_threshold
                    if sem_threshold is None
                    else float(sem_threshold)
                )
                self.semantic.attach(
                    sid, sub.slot, embedding, th,
                    fid=-1 if fid is None else fid, scope=real,
                )
            else:
                if prev is not None and prev.semantic:
                    # the re-subscribe dropped the embedding filter:
                    # back to plain fan-out
                    self.semantic.detach(sub.slot)
                if prev is None or prev.semantic:
                    if fid is not None:
                        self.subtab.add(fid, sub.slot)
        self.metrics.gauge_set("subscriptions.count", self.subscription_count())

    def _subscribe_shared(self, group: str, real: str, sub) -> None:
        # one route ref per group (matched by delete on group-empty)
        if self.shared.subscribe(group, real, sub):
            rk = self.shared.route_filter(group, real)
            self.router.add_route(rk)
            if self.cluster is not None:
                self.cluster._replicate_add(rk)
                self.cluster.shared_join(real, group)
        fid = self.router.filter_id(real)
        if fid is not None:
            gid = self.grouptab.ensure_group(fid, real, group)
            g = self.shared.group(real, group)  # just joined: it exists
            self.grouptab.set_len(gid, len(g.members))
            # a new row's base is 0: the device picks from the group's own
            # (random) start, like the host path
            self.grouptab.set_rr(gid, g.rr_index)
        self._shared_gauges()

    def _shared_gauges(self) -> None:
        self.metrics.gauge_set(
            "shared.subscriptions.count", self.shared.count()
        )
        self.metrics.gauge_set("grouptab.groups", len(self.grouptab))

    def unsubscribe(self, sid: str, filter_: str) -> bool:
        group, real = T.parse_share(filter_)
        if group is not None:
            fid = self.router.filter_id(real)
            removed, empty = self.shared.unsubscribe(group, real, sid)
            if empty:
                if fid is not None:
                    self.grouptab.drop_group(fid, real, group)
                rk = self.shared.route_filter(group, real)
                self.router.delete_route(rk)
                if self.cluster is not None:
                    # the leave ahead of the route's delete, which waits
                    # for the filter's last local group or subscriber
                    self.cluster.shared_leave(real, group)
                    if not self.has_local_subs(rk):
                        self.cluster._replicate_delete(rk)
            elif removed and fid is not None:
                gid = self.grouptab.gid_of(real, group)
                g = self.shared.group(real, group)
                if gid is not None and g is not None:
                    self.grouptab.set_len(gid, len(g.members))
                    # a member leaving shifts indices: re-derive the pin
                    # from the sid so it stays on the same live member
                    self.grouptab.repin(gid, g.members.keys(), g.sticky_sid)
            if removed:
                self.released += 1
                self._shared_gauges()
                self.metrics.gauge_set(
                    "subscriptions.count", self.subscription_count()
                )
            return removed
        entry = self._subs.get(real)
        if not entry or sid not in entry:
            return False
        sub = entry.pop(sid)
        self._plain_subs -= 1
        self.released += 1
        if sub.slot >= 0:
            if sub.semantic and self.semantic is not None:
                self.semantic.detach(sub.slot)
            else:
                fid = self.router.filter_id(real)
                if fid is not None:
                    self.subtab.remove(fid, sub.slot)
            self._free_slot(sub.slot)
        if not entry:
            del self._subs[real]
            self.router.delete_route(real)
            if self.cluster is not None and not self.shared.has_groups(real):
                self.cluster._replicate_delete(real)
        self.metrics.gauge_set("subscriptions.count", self.subscription_count())
        return True

    def _alloc_slot(self, sub: Subscriber) -> int:
        if self._free_slots:
            slot = self._free_slots.pop()
            self._slot_subs[slot] = sub
            return slot
        self._slot_subs.append(sub)
        return len(self._slot_subs) - 1

    def _free_slot(self, slot: int) -> None:
        self._slot_subs[slot] = None
        self._free_slots.append(slot)

    def subscription_count(self) -> int:
        return self._plain_subs + self.shared.count()

    def subscriptions(self) -> List[Tuple[str, str, pkt.SubOpts]]:
        out = []
        for f, entry in self._subs.items():
            for sub in entry.values():
                out.append((sub.client_id, f, sub.opts))
        out.extend(self.shared.subscriptions())
        return out

    # -- publish side -----------------------------------------------------
    def publish(self, msg: Message) -> int:
        """Route + dispatch one message; returns delivery count."""
        rec = self.spans
        sp = rec.publish_begin(msg) if rec is not None else None
        msg = self.hooks.run_fold("message.publish", (), msg)
        n = self._publish_folded(msg)
        if sp is not None:
            rec.finish_span(sp, n)
        return n

    async def apublish(self, msg: Message) -> int:
        """Async `publish` for the connection path: awaits async hooks
        (exhook sidecars) so a slow extension suspends only the publishing
        client's task, not the event loop. When a BatchIngest is attached,
        the folded message rides the adaptive batch window onto the device
        route path instead of a per-message CPU match."""
        r = await self.apublish_enqueue(msg)
        return r if isinstance(r, int) else await r

    async def apublish_enqueue(self, msg: Message):
        """Pipelined publish: fold + enqueue WITHOUT awaiting dispatch.

        Returns either an int (dispatched inline / dropped) or an
        asyncio.Future resolving to the delivery count when the batch
        flushes. This is what lets a connection keep parsing subsequent
        frames while earlier publishes ride the batch window — the analog
        of the reference's active-N=100 socket pipeline
        (emqx_connection.erl:125), without which one connection could never
        have more than one message in a batch.
        """
        # section `ingest.enqueue`: the message.publish fold (rewrite,
        # retainer, rules, delayed) and the append to the ingest's lane; a
        # hook that has to be awaited is awaited outside it
        _prof.begin("ingest.enqueue")
        try:
            rec = self.spans
            # span head BEFORE the fold: the publish span covers hook
            # time, and the stamped context header rides into exhook
            # sidecar calls
            sp = rec.publish_begin(msg) if rec is not None else None
            rh = self.rule_hook
            if rh is not None and rh.device_active():
                ing0 = self.ingest
                if ing0 is not None and ing0.running:
                    # device-compiled rule WHEREs defer to settle time:
                    # the batch evaluates them inside the serving launch
                    # (the hook-path evaluator skips marked messages)
                    msg.headers["_batch_rules"] = True
            msg, rest = self.hooks.fold_sync("message.publish", (), msg)
            if rest is None:
                return self._enqueue_folded(msg, sp)
        finally:
            _prof.end()
        msg = await rest
        _prof.begin("ingest.enqueue")
        try:
            return self._enqueue_folded(msg, sp)
        finally:
            _prof.end(0)  # the same message: no second entry

    def after_forward_confirms(self, fn) -> bool:
        """`cluster.rpc_mode: sync` (the reference's `[rpc, mode]`): run
        `fn()` on the running loop once every cross-node forward handed
        off since the last call was confirmed by its destination node,
        i.e. dispatched there. False, and `fn` is left to the caller,
        when nothing is waited for (no cluster, `async`, no remote
        subscriber, or confirmed already). Nothing blocks here: the
        confirmations resolve on the forward lanes' threads."""
        c = self.cluster
        pending = c.take_confirms() if c is not None else ()
        if not pending:
            return False
        loop = asyncio.get_running_loop()
        left = [len(pending)]

        def step():
            left[0] -= 1
            if not left[0]:
                fn()

        for f in pending:
            f.add_done_callback(
                lambda _f: loop.call_soon_threadsafe(step)
            )
        return True

    def _enqueue_folded(self, msg: Optional[Message], sp):
        """`apublish_enqueue` after the message.publish fold -> delivery
        count, or the ingest's future."""
        if msg is None or msg.headers.get("allow_publish") is False:
            self.metrics.inc("messages.dropped")
            if sp is not None:
                self.spans.finish_span(sp, 0, status="error")
            return 0
        ing = self.ingest
        if ing is not None and ing.running:
            # the publish span settles inside BatchIngest._finish (by
            # context header) when the batch dispatch completes
            return ing.enqueue(msg)
        n = self._dispatch_routed(msg)
        if sp is not None:
            self.spans.finish_span(sp, n)
        if self.cluster is not None:
            # rpc_mode sync: the count (and the PUBACK behind it) waits
            # for the forwards' confirmation
            box: list = []
            if self.after_forward_confirms(
                lambda: box[0].done() or box[0].set_result(n)
            ):
                box.append(asyncio.get_running_loop().create_future())
                return box[0]
        return n

    def _publish_folded(self, msg: Optional[Message]) -> int:
        """Shared tail of publish/apublish after the message.publish fold."""
        if msg is None or msg.headers.get("allow_publish") is False:
            self.metrics.inc("messages.dropped")
            return 0
        return self._dispatch_routed(msg)

    def _dispatch_routed(self, msg: Message, forward: bool = True) -> int:
        """Local dispatch + cluster forward. `forward=False` marks the
        RECEIVING half of a cluster forward — it must never re-forward,
        or every forwarded batch cascades node-to-node forever."""
        rec = self.spans
        t_ns = (
            rec.now_ns()
            if rec is not None and TRACE_HEADER in msg.headers
            else 0
        )
        n = self._route_dispatch(msg, self.router.match(msg.topic))
        if t_ns:
            rec.deliver(msg, n, start_ns=t_ns)
        if forward and self.cluster is not None:
            n += self.cluster.forward_batch_remote([msg])[0]
        if n == 0:
            self.hooks.run("message.dropped", msg, "no_subscribers")
            self.metrics.inc("messages.dropped.no_subscribers")
        return n

    def publish_batch(self, msgs: Sequence[Message]) -> int:
        """Batch publish: one TPU kernel for all topics, then fan out."""
        rh = self.rule_hook
        defer = rh is not None and rh.device_active()
        msgs2: List[Message] = []
        for m in msgs:
            if defer:
                m.headers["_batch_rules"] = True
            m = self.hooks.run_fold("message.publish", (), m)
            if m is not None and m.headers.get("allow_publish") is not False:
                msgs2.append(m)
        return sum(self.dispatch_batch_folded(msgs2))

    def dispatch_batch_folded(
        self, msgs: Sequence[Message], forward: bool = True
    ) -> List[int]:
        """Route + dispatch already-folded messages as one device step.

        The full flagship pipeline: tokenize + NFA match + bitmap fan-out in
        one jitted route_step, then host delivery straight from subscriber
        bits. Rows the kernel flags (too deep / overflow) fall back to the
        authoritative CPU path per row; batches too small to amortize a
        dispatch skip the device entirely. `forward=False` = receiving
        half of a cluster forward (never re-forward).
        """
        r = self.router
        if not (r.enable_tpu and len(msgs) >= r.min_tpu_batch):
            return self._dispatch_cpu_batch(msgs, forward)
        deg = self.degrade
        if deg is not None and not deg.device.allow():
            # breaker open: degraded serving from the authoritative CPU
            # trie at batch granularity (docs/robustness.md)
            self.metrics.inc("degrade.fallback.batches")
            tp("dispatch.degraded", n=len(msgs))
            return self._dispatch_cpu_batch(msgs, forward)
        dev = self._device_router()
        rec = self.spans
        t_launch = rec.now_ns() if rec is not None else 0
        try:
            results = dev.route(
                # topic_key(): zero-copy ingest — slab-backed messages
                # hand the tokenizer a TopicRef into the fabric read
                # buffer instead of paying a str decode per row
                [m.topic_key() for m in msgs], self._client_hashes(msgs),
                embeds=self._embeds(msgs), rules=self._rule_batch(msgs),
            )
        except Exception:  # noqa: BLE001 — degrade, don't fail the batch
            if deg is None:
                raise
            # sync callers get no backoff train (they may hold the event
            # loop); the async serving path owns the retry ladder
            deg.device.record_failure("route")
            self.metrics.inc("degrade.fallback.batches")
            tp("dispatch.degraded", n=len(msgs))
            return self._dispatch_cpu_batch(msgs, forward)
        if deg is not None:
            deg.device.record_success()
        dsp = None
        if rec is not None:
            # sync path has no ingest batch span: the device-step span
            # stands alone, linked to the sampled publishes directly
            dsp = rec.device_step(
                None, len(msgs), results, t_launch,
                links=rec.publish_links(msgs),
                extra=dev.span_attrs(),
            )
        return self._dispatch_device_results(
            msgs, results, forward, device_span=dsp
        )

    def _dispatch_cpu_batch(
        self, msgs: Sequence[Message], forward: bool = True
    ) -> List[int]:
        """The authoritative CPU slow path for a whole batch: per-message
        trie match + host fan-out, remote fan-out still batched per
        destination node. This is both the small-batch branch and the
        degradation target when the device path is broken or its breaker
        is open — it must never itself touch the device. Deferred
        device-compiled rules fire here through the vectorized HOST
        evaluator (the degrade ladder's middle rung); semantic
        recipients resolve per message inside `_route_dispatch` via the
        host twin."""
        if self.rule_hook is not None:
            self.rule_hook.fire_settled(msgs)
        if forward and self.cluster is not None and len(msgs) > 1:
            # keep remote fan-out batched per destination node even
            # on the CPU branch (one forward_batch per node, not one
            # per message per node)
            fwd = self.cluster.forward_batch_remote(msgs)
            rec = self.spans
            out = []
            for i, m in enumerate(msgs):
                t_ns = (
                    rec.now_ns()
                    if rec is not None and TRACE_HEADER in m.headers
                    else 0
                )
                n = self._route_dispatch(
                    m, self.router.match(m.topic)
                )
                if t_ns:
                    rec.deliver(m, n, start_ns=t_ns)
                n += fwd[i]
                if n == 0:
                    self.hooks.run("message.dropped", m, "no_subscribers")
                    self.metrics.inc("messages.dropped.no_subscribers")
                out.append(n)
        else:
            out = [self._dispatch_routed(m, forward) for m in msgs]
        flush_dirty()  # the batch's write boundary, as on the device path
        return out

    async def adispatch_batch_folded(
        self, msgs: Sequence[Message], forward: bool = True
    ) -> List[int]:
        """`dispatch_batch_folded` with the kernel launch + readback (and
        any jit recompile, which can take tens of seconds on a real chip)
        offloaded to an executor thread so the event loop keeps serving
        every other connection. Table packing/upload and delivery stay on
        the loop thread — they touch mutable broker state."""
        return await self.adispatch_begin(msgs, forward)

    def adispatch_begin(
        self, msgs: Sequence[Message], forward: bool = True,
        batch_span=None,
    ) -> "PendingDispatch":
        """Launch the device dispatch for a batch NOW (table snapshot +
        executor kernel submit) and return a PendingDispatch. This is
        the ingest pipeline's seam: batch N+1's upload+launch overlaps
        batch N's readback and host fan-out with device compute.

        The host FAN-OUT runs only inside `complete()` (equivalently:
        awaiting the object) — NEVER autonomously when the device work
        finishes — so callers settling batches in launch (FIFO) order
        preserve MQTT's per-publisher delivery ordering across batches.
        `ready` is a side-effect-free future signalling that the device
        round-trip finished (pipeline pacing only). The caller's ambient
        profiler ids (`batch_ids(batch=seq, rows=n)` in the ingest) ride
        every section of this batch, on this thread and the executor's."""
        loop = asyncio.get_running_loop()
        r = self.router
        deg = self.degrade

        def _cpu_pending(degraded: bool = False):
            _prof.dark_ledger.untook()  # no launch will light the chip
            ready = loop.create_future()
            ready.set_result(None)
            if degraded:
                self.metrics.inc("degrade.fallback.batches")
                tp("dispatch.degraded", n=len(msgs))

            async def _cpu():
                # CPU batches defer dispatch to settle time too: a small
                # batch settling before an in-flight device batch would
                # invert cross-batch delivery order. A DEGRADED batch
                # must bypass the device re-entry inside
                # dispatch_batch_folded, not just prefer CPU.
                if degraded:
                    if batch_span is not None:
                        batch_span.attrs["degraded"] = True
                    return self._dispatch_cpu_batch(msgs, forward)
                return self.dispatch_batch_folded(msgs, forward)

            return PendingDispatch(ready, _cpu)

        if not (r.enable_tpu and len(msgs) >= r.min_tpu_batch):
            return _cpu_pending()
        if deg is not None and not deg.device.allow():
            # breaker open: the whole batch serves from the CPU trie
            # (half-open probes re-enter here one batch at a time)
            return _cpu_pending(degraded=True)
        dev = self._device_router()
        ids = _prof.ambient_ids()
        try:
            with _prof.section("prepare") as sec:
                args = dev.prepare()
        except Exception:  # noqa: BLE001 — no good epoch: degrade
            if deg is None:
                raise
            deg.device.record_failure("delta_sync")
            return _cpu_pending(degraded=True)
        # waterfall `prepare` (observe/profiler.py): table snapshot +
        # upload cost this launch paid before any device work
        self.metrics.observe("profile.stage.prepare.seconds", sec.seconds)
        feed = self.retained_feed
        storm = None
        if feed is not None and dev.supports_retained_fusion:
            # pending wildcard-subscribe replays ride THIS launch: the
            # fused kernel answers them in the same program + readback
            # (fused_route_retained_step single-device; dist_fused_step
            # on the mesh engine, chunk rows scanning sharded over 'dp')
            storm = feed.take_job()
        store = self.session_store
        rider = None
        if store is not None and storm is None and getattr(
            dev, "supports_session_fusion", False
        ):
            # pending session-table writes (+ a requested retry/expiry
            # sweep) fuse into THIS launch as the session-ack stage —
            # ack batches never pay their own device launch, and the
            # sweep lists ride the same coalesced readback
            rider = store.take_rider()
            if rider is not None and batch_span is not None:
                batch_span.attrs["session.rider.rows"] = rider.rows
                if rider.sweep_k:
                    batch_span.attrs["session.sweep"] = True
        rec = self.spans
        t_launch = rec.now_ns() if rec is not None else 0
        # topic_key(): slab-backed messages defer str decode — the
        # tokenizer gathers their bytes straight from the fabric slab
        topics = [m.topic_key() for m in msgs]
        hashes = self._client_hashes(msgs)
        embeds = self._embeds(msgs)
        rules = self._rule_batch(msgs)

        def _route(*a):
            # the executor thread's launch / device_execute / readback
            # sections carry this batch's ids
            with _prof.batch_ids(**ids):
                return dev.route_prepared(*a)

        fut = loop.run_in_executor(
            dispatch_pool(),
            _route,
            args,
            topics,
            hashes,
            storm,
            rider,
            embeds,
            rules,
        )
        if storm is not None:
            feed.attach(storm, fut)

        async def _complete():
            srd = rider
            try:
                results = await fut
            except Exception:  # noqa: BLE001 — the retry ladder owns it
                if deg is None:
                    if srd is not None:
                        store.abort(srd)
                    raise
                results = None
            if results is None and srd is not None:
                # the failed launch carried the session rider: nothing
                # is lost (host arrays are authoritative) — its writes
                # stay queued and ride a later launch or the segment
                # scatter path; retries relaunch bare
                store.abort(srd)
                srd = None
            if results is None:
                # bounded exponential backoff + jitter, then degrade:
                # each retry re-prepares (the failure may have been a
                # torn sync; rollback serves the last good epoch) and
                # relaunches WITHOUT the storm (its waiters already fell
                # back to the CPU walk via feed.attach's done-callback)
                for delay in deg.retry_delays():
                    await asyncio.sleep(delay)
                    try:
                        args2 = dev.prepare()
                        results = await loop.run_in_executor(
                            dispatch_pool(),
                            _route,
                            args2,
                            topics,
                            hashes,
                            None,
                            None,
                            embeds,
                            rules,
                        )
                        break
                    except Exception:  # noqa: BLE001 — keep retrying
                        results = None
            if results is None:
                # retries exhausted: trip the breaker, serve this batch
                # from the CPU trie — the publishes SUCCEED (identical
                # recipient sets, slower path), they don't fail
                deg.device.record_failure("launch")
                self.metrics.inc("degrade.fallback.batches")
                tp("dispatch.degraded", n=len(msgs))
                if batch_span is not None:
                    batch_span.attrs["degraded"] = True
                return self._dispatch_cpu_batch(msgs, forward)
            if deg is not None:
                deg.device.record_success()
            if srd is not None and results.session is not None:
                # adopt the updated device mirror + act on the sweep
                # (back on the loop — the single-writer discipline)
                store.commit(srd, results.session)
            if storm is not None:
                # no-op when the storm already failed over (retry path)
                feed.resolve(storm, results.retained)
            dsp = None
            if rec is not None:
                # the batch span (ingest fan-in) parents the device-step
                # span; batch-less callers get a standalone span linked
                # straight to the sampled publishes
                dsp = rec.device_step(
                    batch_span, len(msgs), results, t_launch,
                    links=rec.publish_links(msgs)
                    if batch_span is None
                    else (),
                    extra=dev.span_attrs(),
                )
            # waterfall `host_dispatch`: the settle-time fan-out of this
            # device batch (delivery resolution + writes)
            with _prof.section("host_dispatch", **ids) as sec:
                res = self._dispatch_device_results(
                    msgs, results, forward, device_span=dsp
                )
            self.metrics.observe(
                "profile.stage.host_dispatch.seconds", sec.seconds
            )
            return res

        return PendingDispatch(fut, _complete)

    def _device_router(self):
        if self._device is None:
            from emqx_tpu.models.router_model import (
                DeviceRouter,
                MeshServingRouter,
            )

            # mesh set => the scale-out engine: sharded table mirrors,
            # SPMD dist step, fused retained storms over the mesh
            cls = DeviceRouter if self.mesh is None else MeshServingRouter
            self._device = cls(
                self.router.index,
                self.subtab,
                self.router.matcher_config,
                grouptab=self.grouptab,
                share_strategy=self.shared.strategy,
                mesh=self.mesh,
                metrics=self.metrics,
                semtab=(
                    self.semantic.table
                    if self.semantic is not None
                    else None
                ),
            )
            if self.mesh is not None and self.shard_label:
                self._device.shard_label = self.shard_label
        return self._device

    def _embeds(self, msgs):
        """Per-message query embeddings for the fused semantic stage —
        None (and zero per-row cost) when no semantic plane is live."""
        sem = self.semantic
        if sem is None or not len(sem.table):
            return None
        return sem.embed_batch(msgs)

    def _rule_batch(self, msgs):
        """Compiled rule programs + the batch's feature matrix for the
        in-launch WHERE masks — None when no rule compiled."""
        rh = self.rule_hook
        if rh is None:
            return None
        return rh.device_progs(msgs)

    def _client_hashes(self, msgs):
        """Publisher-id hashes for the device $share pick — skipped
        entirely when no groups exist or the strategy doesn't use them."""
        if not len(self.grouptab) or self.shared.strategy != "hash_clientid":
            return None
        from emqx_tpu.broker.shared_sub import stable_hash

        return [stable_hash(m.from_client) for m in msgs]

    def _dispatch_device_results(
        self, msgs, results, forward: bool = True, device_span=None
    ) -> List[int]:
        """Fan one routed batch out to local subscribers.

        `results` is a `RouteResult`. On the compact path
        (`results.slots`) non-overflow rows dispatch straight from their
        slot-id lists — zero `unpackbits` — while overflow rows decode
        the dense rows of the masked second transfer; with compaction
        off every row decodes `results.bitmaps`. The match/fid memos are
        PER BATCH: the same (topic, filter) staleness re-verify and the
        same fid -> (name, has_groups) resolution used to repeat once
        per delivery."""
        matched, flags = results.matched, results.flags
        picks = results.picks
        r = self.router
        # deferred device-compiled rules fire FIRST (reference order:
        # rules run in the publish fold, before dispatch) — with the
        # in-launch masks when the batch carried them, else the host
        # evaluator ladder (rules/engine.fire_settled)
        if self.rule_hook is not None:
            self.rule_hook.fire_settled(msgs, masks=results.rule_masks)
        # semantic plane live for this batch: winner slots are already
        # unioned into the compact rows; rows only need the host-side
        # dedup net (mesh shards can union the same slot twice) and the
        # flight-recorder series
        sem = results.sem_count is not None
        if sem:
            hits = int(np.asarray(results.sem_count).sum())
            if hits:
                self.metrics.inc("semantic.hits", hits)
            topk = (
                self.semantic.table.topk
                if self.semantic is not None
                else 0
            )
            if topk:
                trunc = int(
                    np.count_nonzero(
                        np.asarray(results.sem_count) > topk
                    )
                )
                if trunc:
                    self.metrics.inc("semantic.topk.truncated", trunc)
        fwd = (
            self.cluster.forward_batch_remote(msgs)
            if forward and self.cluster is not None
            else None
        )
        fell_back = 0
        touched_gids: set = set()
        match_memo: Dict[Tuple[str, str], bool] = {}
        fid_memo: Dict[int, Tuple[Optional[str], bool]] = {}
        compact = results.slots is not None
        rec = self.spans
        # batch-level fan-out prep (docs/protocol_plane.md): ONE
        # .tolist() per device output matrix up front — the per-message
        # loop below then runs on plain ints, with per-row metric
        # observes batched at the end. The old per-row
        # numpy mask/filter chains were a top per-message dispatch cost.
        flags_l = np.asarray(flags).tolist()
        slots_ll = results.slots.tolist() if compact else None
        ovf_l = results.overflow.tolist() if compact else None
        # matched filter-id rows only matter when shared groups exist
        # AND the device didn't already resolve the picks
        need_fids = picks is None and bool(self.shared._table)
        matched_l = matched.tolist() if need_fids else None
        # the batch's deliveries to one connection are one run
        # (docs/protocol_plane.md "The delivery run"): collected per row,
        # handed over after the rows; a row's count is final only then
        runs = DeliveryRuns(self, len(msgs))
        counts, pick_stats = runs.counts, runs.pick_stats
        stamps: List[Tuple[int, int]] = []  # traced rows: (row, start)
        for i, m in enumerate(msgs):
            if rec is not None and TRACE_HEADER in m.headers:
                stamps.append((i, rec.now_ns()))
            if flags_l[i]:
                fell_back += 1
                tp("dispatch.fallback", topic=m.topic)
                runs.deliver()  # the CPU dispatch delivers on the spot
                counts[i] = self._route_dispatch(m, r.match(m.topic))
                continue
            msg_picks = (
                (picks[0][i], picks[1][i]) if picks is not None else None
            )
            if compact and not ovf_l[i]:
                # -1 pads skip inside the dispatch loop
                bits, slots = None, slots_ll[i]
            elif compact:
                bits = results.dense_rows[results.dense_index[i]]
                # semantic winners live in the device slot row (the
                # dense fallback covers only the TOPIC fan-out):
                # union them back in — dup topic slots dedup below
                slots = slots_ll[i] if sem else None
            else:
                bits, slots = results.bitmaps[i], None
            # matched rows are SPARSE (-1 holes between engines)
            fids = (
                [f for f in matched_l[i] if f >= 0]
                if matched_l is not None
                else ()
            )
            runs.row = i
            self._dispatch_row(
                m, bits, fids, msg_picks, touched_gids,
                slots=slots, match_memo=match_memo, fid_memo=fid_memo,
                runs=runs, dedup=sem,
            )
        runs.deliver()
        for i, t_ns in stamps:
            rec.deliver(
                msgs[i], counts[i], start_ns=t_ns, device_span=device_span,
                fallback=bool(flags_l[i]),
            )
        out = counts if fwd is None else [n + f for n, f in zip(counts, fwd)]
        if 0 in out:
            for m, n in zip(msgs, out):
                if n == 0:
                    self.hooks.run("message.dropped", m, "no_subscribers")
                    self.metrics.inc("messages.dropped.no_subscribers")
        fanouts = (
            [n for n, flag in zip(counts, flags_l) if not flag]
            if fell_back
            else counts
        )
        if fanouts:
            # batched flight-recorder upkeep: same series, one lock
            self.metrics.inc("messages.received", len(fanouts))
            self.metrics.observe_many("dispatch.fanout", fanouts)
            delivered = sum(fanouts)
            if delivered:
                self.metrics.inc("messages.delivered", delivered)
        if touched_gids:
            self._sync_group_counters(touched_gids)
        if pick_stats[0]:
            self.metrics.inc("shared.picks", pick_stats[0])
            if pick_stats[1]:
                self.metrics.inc("shared.picks.stale", pick_stats[1])
        self._count_leadership()
        if fell_back:
            self.metrics.inc("messages.routed.device_fallback", fell_back)
        self.metrics.inc("messages.routed.device", len(msgs) - fell_back)
        # the batch's write boundary: every in-process connection it
        # touched, one socket write each
        flush_dirty()
        tp("dispatch.batch", n=len(msgs), fallback=fell_back)
        return out

    def _dispatch_row(  # readback-site
        self, msg: Message, bits: Optional[np.ndarray], fids, picks=None,
        touched_gids: Optional[set] = None, *, slots=None,
        match_memo: Optional[Dict] = None,
        fid_memo: Optional[Dict] = None,
        runs: Optional[DeliveryRuns] = None, dedup: bool = False,
    ) -> int:
        """Deliver one routed message from its device outputs: subscriber
        slot list (compact path) or bitmap (dense path) -> plain subs;
        matched filter ids -> shared groups.
        When `picks` is given ((gids, idxs) from the device $share pick),
        group delivery goes straight to the picked member with host-side
        failover only; otherwise the host runs the full pick.
        `slots` may be a plain int list (batch callers pre-.tolist() the
        whole slot matrix; -1 pads are skipped here). With `runs` given
        (the batch's `DeliveryRuns`, at this row) a delivery whose
        deliverer offers a run is collected there and not made here, the
        fan-out lands in `runs.counts`, final once the runs are handed
        over, and the per-row metric calls are
        batched by the caller instead. `bits` AND `slots` together =
        the semantic overflow contract: the dense row carries the topic
        fan-out, the slot list carries the device row's semantic
        winners, and `dedup` guards double delivery (also set for mesh
        batches, where two 'tp' shards can emit the same slot)."""
        if runs is None:
            self.metrics.inc("messages.received")
        if match_memo is None:
            match_memo = {}
        if fid_memo is None:
            fid_memo = {}
        n = 0
        topic = msg.topic
        if bits is not None:
            # dense decode. ascontiguousarray: readback rows can be
            # strided (fancy-indexed fallback rows) and ndarray.view
            # raises on non-contiguous buffers
            if not bits.flags.c_contiguous:
                bits = np.ascontiguousarray(bits)
            dense = np.nonzero(
                np.unpackbits(bits.view(np.uint8), bitorder="little")
            )[0].tolist()
            if slots is None:
                slots = dense
            else:
                # dense topic fan-out + the device row's semantic
                # winners (overflow rows on the semantic plane)
                if not isinstance(slots, list):
                    slots = np.asarray(slots).tolist()
                slots = dense + slots
        elif not isinstance(slots, list):
            slots = np.asarray(slots).tolist()
        slot_subs = self._slot_subs
        nsubs = len(slot_subs)
        seen = set() if dedup else None
        hand = runs.hand if runs is not None else None
        for slot in slots:
            # -1 pads (compact rows) and slots past the local table
            # (another node's lanes) skip here — plain int compares,
            # no per-row numpy filter pass
            if slot < 0 or slot >= nsubs:
                continue
            if seen is not None:
                if slot in seen:
                    continue
                seen.add(slot)
            sub = slot_subs[slot]
            if sub is None:
                continue
            if sub.opts.no_local and sub.client_id == msg.from_client:
                continue
            # staleness net: the kernel ran against a snapshot, and slots /
            # filter ids freed during an in-flight batch can be reused by
            # unrelated subscriptions — verify the sub's filter really
            # matches before delivering (misdelivery is worse than a
            # topic-match check per delivery). Exact filters (the serving
            # common case) short-circuit on string equality; the full
            # matcher is memoized per batch (pure fn of (topic, filter))
            f = sub.filter
            if topic != f:
                ok = match_memo.get((topic, f))
                if ok is None:
                    ok = T.match(topic, f)
                    match_memo[(topic, f)] = ok
                if not ok:
                    continue
            n += self._deliver_one(sub, msg, hand)
        if picks is not None:
            # device-resolved $share picks: host does delivery + failover.
            # Section `shared.dispatch_picked`: one entry per pick handed
            # over; a pick is stale when its group is gone, its filter no
            # longer matches, or no member took the message. The section
            # holds the group look-up, the re-verify, the member's choice
            # and the hand-over; an in-process member's send happens in
            # its connection's run, after the rows
            gids, idxs = picks
            handed = served = 0
            _prof.begin("shared.dispatch_picked")
            try:
                for gid, idx in zip(gids, idxs):
                    if gid < 0:
                        continue
                    handed += 1
                    info = self.grouptab.info(int(gid))
                    if info is None:
                        continue  # group dropped while the batch was in flight
                    real, gname = info
                    # staleness net, same as slots: re-verify the filter
                    ok = match_memo.get((topic, real))
                    if ok is None:
                        ok = T.match(topic, real)
                        match_memo[(topic, real)] = ok
                    if not ok:
                        continue
                    d = self.shared.dispatch_picked(
                        real, gname, int(idx), msg, hand
                    )
                    n += d
                    served += d
                    if touched_gids is not None:
                        touched_gids.add(int(gid))
            finally:
                _prof.end(handed)
            if runs is not None:
                # the caller batches the counters: one add per launch
                runs.pick_stats[0] += handed
                runs.pick_stats[1] += handed - served
        else:
            for fid in fids:
                fid = int(fid)
                ent = fid_memo.get(fid)
                if ent is None:
                    name = self.router.filter_name(fid)
                    ent = (
                        name,
                        name is not None and self.shared.has_groups(name),
                    )
                    fid_memo[fid] = ent
                name, has_g = ent
                if not has_g:
                    continue
                ok = match_memo.get((topic, name))
                if ok is None:
                    ok = T.match(topic, name)
                    match_memo[(topic, name)] = ok
                if ok:
                    if runs is not None:
                        runs.deliver()  # a host pick delivers on the spot
                    n += self.shared.dispatch_groups(name, msg)
        if runs is not None:
            # += : a run handed over inside this row may have given
            # some of the row's deliveries back already
            runs.counts[runs.row] += n  # caller batches the metric upkeep
            return n
        self.metrics.observe("dispatch.fanout", n)
        if n:
            self.metrics.inc("messages.delivered", n)
        return n

    def _sync_group_counters(self, gids) -> None:
        """Push advanced round-robin bases / sticky pins back to the
        device mirror — called once per BATCH with the touched gid set,
        so churn is one bounded write per group per batch."""
        for gid in gids:
            info = self.grouptab.info(gid)
            if info is None:
                continue
            g = self.shared.group(*info)
            if g is None:
                continue
            self.grouptab.set_rr(gid, g.rr_index)
            if self.shared.strategy == "sticky" and g.sticky_sid is not None:
                self.grouptab.repin(gid, g.members.keys(), g.sticky_sid)

    def dispatch(self, filters: List[str], msg: Message) -> int:
        """Deliver to local subscribers of pre-matched filters.

        This is the receiving half of a cross-node forward: the publisher
        node already ran the route match, the owner node fans out to its
        local subscriber tables (emqx_broker:dispatch, emqx_broker.erl:
        505-530 via the forward path :278-293).
        """
        rec = self.spans
        t_ns = (
            rec.now_ns()
            if rec is not None and TRACE_HEADER in msg.headers
            else 0
        )
        n = self._route_dispatch(msg, filters)
        if t_ns:
            # the context rode the forward in the message headers: this
            # deliver span keeps the ORIGIN node's trace_id
            rec.deliver(msg, n, start_ns=t_ns, remote=True)
        return n

    def _count_leadership(self) -> None:
        """The cluster leadership answers (`SharedSub._is_leader` with a
        leader check set) into `shared.leader.led` / `.deferred`, where
        the dispatch's own counters are added: once a device batch, beside
        `shared.picks`, and once a message on the host path, beside
        `messages.received`. An answer given after its batch settled (a
        refused pick's failover) rides the next one."""
        c = self.shared.leader_counts
        if c[0] or c[1]:
            led, deferred = c
            c[0] = c[1] = 0
            if led:
                self.metrics.inc("shared.leader.led", led)
            if deferred:
                self.metrics.inc("shared.leader.deferred", deferred)

    def has_local_subs(self, route_key: str) -> bool:
        """Any local subscriber (plain or shared-group) on this filter?"""
        return bool(self._subs.get(route_key)) or self.shared.has_groups(
            route_key
        )

    def _route_dispatch(self, msg: Message, filters: List[str]) -> int:
        self.metrics.inc("messages.received")
        if msg.headers.get("_batch_rules") and self.rule_hook is not None:
            # a deferred-rule message settling OUTSIDE the batch paths
            # (sync publish, device-flagged fallback rows whose batch
            # carried no masks): fire through the host ladder
            self.rule_hook.fire_settled([msg])
        n = 0
        for f in filters:
            # one matched filter may carry plain subscribers AND shared groups
            entry = self._subs.get(f)
            if entry:
                for sub in list(entry.values()):
                    if sub.opts.no_local and sub.client_id == msg.from_client:
                        continue
                    if sub.semantic:
                        # embedding-filtered: delivery needs similarity
                        # too — resolved by the host twin below
                        continue
                    n += self._deliver_one(sub, msg)
            n += self.shared.dispatch_groups(f, msg)
        sem = self.semantic
        if sem is not None and len(sem.table):
            # the authoritative host twin (CPU fallback / per-message
            # path): topic-scope AND similarity, global top-k
            for slot in sem.host_route([msg])[0]:
                sub = (
                    self._slot_subs[slot]
                    if 0 <= slot < len(self._slot_subs)
                    else None
                )
                if sub is None:
                    continue
                if sub.opts.no_local and sub.client_id == msg.from_client:
                    continue
                n += self._deliver_one(sub, msg)
        self.metrics.observe("dispatch.fanout", n)
        if n:
            self.metrics.inc("messages.delivered", n)
        self._count_leadership()
        return n

    def _deliver_one(self, sub: Subscriber, msg: Message, hand=None) -> int:
        """One raising deliverer must not poison the rest of the fan-out
        (or, on the batch path, every other message in the batch).
        `hand`: the batch's `DeliveryRuns.hand`, in the deliverer's place."""
        try:
            if hand is None:
                sub.deliver(msg, sub.opts)
            else:
                hand(sub, msg)
            return 1
        except Exception:
            self.metrics.inc("delivery.errors")
            return 0

    def drop_session_subs(self, sid: str, filters: Sequence[str]) -> None:
        """Bulk cleanup when a session dies (emqx_broker_helper pmon parity)."""
        self.released += 1
        for f in list(filters):
            self.unsubscribe(sid, f)
