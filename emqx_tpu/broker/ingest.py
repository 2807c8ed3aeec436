"""Ingest-side publish batch aggregation (SLO-adaptive batch window).

SURVEY.md §7 hard part (c): the device route path wants big batches, but a
publishing client wants low latency. This aggregator sits between the
channel's publish and the router: concurrent publishes from all connections
collect into priority lanes, flushed when either `max_batch` messages are
pending or the window has elapsed since the flusher woke — so a lone
publisher pays at most one window of added latency while a firehose fills
batches immediately and never sleeps.

The window is no longer a fixed policy: with an `SloController` attached
(broker/slo.py), it adapts each flush cycle to hold a configured
enqueue->settle p99 target — decaying toward zero when idle (immediate
partial launches), deepening under storm, and walking the graded
backpressure ladder (widen -> defer low lanes -> shed) instead of the old
binary `IngestShed` cliff.

Priority lanes: `control` (QoS2 control flow, $SYS) > `normal` (QoS1) >
`low` (QoS0 firehose when `qos0_low`, explicitly tagged messages). The
flusher assembles batches in lane order with an anti-starvation reserve,
so a retained-storm or QoS0 flood can never queue a PUBREL or a $SYS
heartbeat behind itself (docs/robustness.md "Priority lanes").

The reference has no analog — its hot loop is per-message per-process
(emqx_broker.erl:204-215); this is the TPU-era replacement for that regime,
turning N concurrent publishes into one route_step kernel launch
(emqx_tpu.models.router_model.DeviceRouter).

Backpressure: `submit` awaits the flush result, so a publisher's PUBACK
reflects actual dispatch; the pending lanes are bounded by the shed ladder
(SLO mode) or the legacy overload gate.

Flight recorder: every latency/throughput tradeoff this loop makes is
recorded into the broker's metrics (docs/observability.md) — batch size and
occupancy, window hold time, pipeline depth, per-message AND per-lane
enqueue->settle latency, lane depths, and launch/dispatch failures — plus
`ingest.launch`/`ingest.settle` tracepoints keyed by batch seq for causal
assertions in tests.
"""

from __future__ import annotations

import asyncio
import logging
import time
from collections import deque
from typing import List, Optional, Tuple

from emqx_tpu.broker.degrade import OPEN, IngestShed
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.metrics import Metrics
from emqx_tpu.broker.slo import (
    LANE_CONTROL,
    LANE_LOW,
    LANE_NAMES,
    LANE_NORMAL,
    RUNG_NAMES,
)
from emqx_tpu.observe import faults as _faults
from emqx_tpu.observe import profiler as _prof
from emqx_tpu.observe.spans import TRACE_HEADER
from emqx_tpu.utils.tracepoints import tp

log = logging.getLogger("emqx_tpu.ingest")

LANE_DEPTH_SERIES = tuple(f"ingest.lane.depth.{n}" for n in LANE_NAMES)
LANE_SETTLE_SERIES = tuple(
    f"ingest.lane.settle.seconds.{n}" for n in LANE_NAMES
)


class BatchIngest:
    def __init__(
        self,
        broker,
        max_batch: int = 4096,
        window_us: int = 1000,
        pipeline: int = 2,
        olp=None,
        slo=None,
        qos0_low: bool = False,
    ):
        self.broker = broker
        self.max_batch = max_batch
        self.window_s = window_us / 1e6
        # overload-protection signal (broker/olp.py): with the broker's
        # DegradeController attached (and no SLO controller), enqueues
        # shed once the pending backlog passes the shed bound while
        # olp.is_overloaded() holds or the device breaker is open —
        # backpressure instead of unbounded queue growth behind a broken
        # fast path. With an SloController the graded ladder owns
        # admission instead (shed is the LAST rung).
        self.olp = olp
        # SLO-adaptive batching (broker/slo.py): adapts window_s each
        # flush cycle + owns the defer/shed ladder. None = legacy fixed
        # window (unit tests, knob off).
        self.slo = slo
        # lane policy: route QoS0 publishes to the low-priority lane
        # (the firehose a $SYS heartbeat must never queue behind)
        self.qos0_low = qos0_low
        # device dispatches in flight at once: batch N+1's table upload +
        # kernel launch overlaps batch N's readback and host fan-out
        # with device compute. Settlement stays strictly FIFO so
        # per-publisher delivery order holds across batches.
        self.pipeline = max(1, pipeline)
        self.metrics: Metrics = getattr(broker, "metrics", None) or Metrics()
        # per-lane pending lists of (msg, puback future, enqueue
        # perf_counter timestamp, lane). `_pending` stays the NORMAL
        # lane's list (the historical name — shed/backlog tests and the
        # stop() drain reach it directly).
        self._lane_hi: List[Tuple] = []
        self._pending: List[Tuple] = []
        self._lane_lo: List[Tuple] = []
        self._inflight: deque = deque()  # (seq, batch, pending, batch_span)
        self._event = asyncio.Event()
        self._task: Optional[asyncio.Task] = None
        self._seq = 0
        # anti-starvation bound for the low lane under sustained
        # control/normal pressure (SloController overrides from config)
        self.starvation_s = slo.starvation_s if slo is not None else 0.05
        self.running = False

    def start(self) -> None:
        if self._task is None:
            self.running = True
            self._task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self) -> None:
        self.running = False
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        # drain launched-but-unsettled batches first (FIFO), then
        # anything still pending (defer gates ignored: shutdown delivers
        # everything), so no publisher hangs on shutdown
        while self._inflight:
            seq, batch, pd, bsp = self._inflight.popleft()
            await self._finish(seq, batch, pd.complete(), bsp)
        while self._backlog():
            batch = self._take_batch(time.perf_counter(), force=True)
            await self._settle(batch)

    # -- lanes --------------------------------------------------------------
    def _backlog(self) -> int:
        return len(self._lane_hi) + len(self._pending) + len(self._lane_lo)

    def lane_of(self, msg: Message) -> int:
        """Priority-lane classification (docs/robustness.md): QoS2
        control flow and $SYS ride the control lane (they must never
        queue behind a firehose); QoS0 rides low when the lane policy is
        armed; explicit `ingest_lane` headers win."""
        ln = msg.headers.get("ingest_lane")
        if ln == "control":
            return LANE_CONTROL
        if ln == "low":
            return LANE_LOW
        if msg.qos == 2 or msg.is_sys():
            return LANE_CONTROL
        if msg.qos == 0 and self.qos0_low:
            return LANE_LOW
        return LANE_NORMAL

    def _lane_list(self, lane: int) -> List[Tuple]:
        if lane == LANE_CONTROL:
            return self._lane_hi
        if lane == LANE_LOW:
            return self._lane_lo
        return self._pending

    def enqueue(self, msg: Message, lane: Optional[int] = None) -> asyncio.Future:
        """Enqueue one folded message; the future resolves with its
        delivery count when the batch flushes.

        Admission (docs/robustness.md): with an SloController attached,
        the graded ladder decides — control never sheds, low sheds at
        the queue bound on the `shed` rung, normal at twice the bound,
        and `shed_hard_mult` x bound is the absolute valve. Without a
        controller the legacy binary gate holds: while the broker is
        overloaded (olp) or the device breaker is open, a backlog past
        the shed bound refuses new enqueues with `IngestShed` on the
        returned future — the publisher's PUBACK fails (QoS>=1 clients
        retry) instead of the pending list growing without bound."""
        act = _faults.hit("ingest.enqueue")  # raise -> publisher's task
        fut = asyncio.get_running_loop().create_future()
        if lane is None:
            lane = self.lane_of(msg)
        shed = act == "drop"
        deg = getattr(self.broker, "degrade", None)
        if not shed and deg is not None:
            bound = deg.shed_queue_batches * self.max_batch
            if self.slo is not None:
                if self.slo.shed(lane, self._backlog(), bound):
                    shed = True
                    self.metrics.inc("slo.shed")
            elif (
                len(self._pending) >= bound
                and (
                    (self.olp is not None and self.olp.is_overloaded())
                    or deg.device.state == OPEN
                )
            ):
                shed = True
        if shed:
            self.metrics.inc("ingest.shed")
            fut.set_exception(
                IngestShed("ingest backlog shed (overload/degraded)")
            )
            return fut
        self._lane_list(lane).append((msg, fut, time.perf_counter(), lane))
        self._event.set()
        return fut

    async def submit(self, msg: Message) -> int:
        return await self.enqueue(msg)

    def _take_batch(self, now: float, force: bool = False) -> List[Tuple]:
        with _prof.section("ingest.take", batch=self._seq):
            return self._assemble(now, force)

    def _assemble(self, now: float, force: bool) -> List[Tuple]:
        """Assemble up to max_batch in lane-priority order. The low lane
        joins unless the SLO ladder defers it (never past its defer age
        bound); a starvation reserve guarantees the low lane slots once
        its head has waited `starvation_s` behind full priority lanes.
        `force` (shutdown drain) ignores the defer gate."""
        cap = self.max_batch
        batch: List[Tuple] = []
        hi, no, lo = self._lane_hi, self._pending, self._lane_lo
        if hi:
            take = hi[:cap]
            del hi[: len(take)]
            batch.extend(take)
        room = cap - len(batch)
        if room > 0 and no:
            # anti-starvation reserve: when the low lane's head already
            # waited past the bound, hold slots open so a saturated
            # normal lane cannot push it out forever
            reserve = 0
            if lo and len(no) >= room and (now - lo[0][2]) >= self.starvation_s:
                reserve = max(1, cap // 16)
                self.metrics.inc("ingest.lane.starvation.breaks")
            n_take = min(len(no), max(0, room - reserve))
            if n_take:
                batch.extend(no[:n_take])
                del no[:n_take]
            room = cap - len(batch)
        if room > 0 and lo:
            slo = self.slo
            if (
                not force
                and slo is not None
                and slo.defer_low(now - lo[0][2])
            ):
                # `defer` rung: the low lane sits this launch out so the
                # storm drains control/normal first (delayed, not lost)
                self.metrics.inc("slo.deferrals")
            else:
                take = lo[:room]
                del lo[: len(take)]
                batch.extend(take)
        return batch

    async def _settle(self, batch) -> None:
        seq, bsp = self._next_seq(batch)
        with _prof.batch_ids(batch=seq, rows=len(batch)):
            pd = self.broker.adispatch_begin(
                [m for m, _, _, _ in batch], batch_span=bsp
            )
        await self._finish(seq, batch, pd, bsp)

    def _next_seq(self, batch):
        """Assign the batch seq + record launch-side telemetry. Returns
        (seq, batch_span): the span is the fan-in node — every sampled
        publish in the batch LINKS into it (same seq key as the
        `ingest.launch` tracepoint), and it parents the device-step span.
        None when nothing in the batch is sampled."""
        n = len(batch)
        seq = self._seq
        self._seq += 1
        self.metrics.observe("ingest.batch.size", n)
        self.metrics.observe("ingest.batch.occupancy", n / self.max_batch)
        # waterfall `queue_wait` (observe/profiler.py): per-message
        # enqueue -> launch wait (window accumulation + lane queueing)
        now = time.perf_counter()
        self.metrics.observe_many(
            "profile.stage.queue_wait.seconds",
            [now - t0 for _, _, t0, _ in batch],
        )
        tp("ingest.launch", batch=seq, n=n)
        rec = getattr(self.broker, "spans", None)
        bsp = (
            rec.batch_begin(seq, [m for m, _, _, _ in batch], self.max_batch)
            if rec is not None
            else None
        )
        if bsp is not None and self.slo is not None:
            # controller state rides the batch span: a trace shows the
            # window/rung THIS batch launched under
            bsp.attrs["slo.window_us"] = round(self.slo.window_s * 1e6, 1)
            bsp.attrs["slo.rung"] = RUNG_NAMES[self.slo.rung]
        return seq, bsp

    async def _finish(self, seq: int, batch, aw, bsp=None) -> None:
        rec = getattr(self.broker, "spans", None)
        try:
            results = await aw
        except Exception as e:  # noqa: BLE001 — flusher must survive
            log.exception("batch dispatch failed; failing %d publishes", len(batch))
            self.metrics.inc("ingest.dispatch.errors")
            for m, fut, _, _ in batch:
                if not fut.done():
                    fut.set_exception(e)
                if rec is not None:
                    rec.publish_finish(
                        m.headers.get(TRACE_HEADER), 0, status="error"
                    )
            if rec is not None and bsp is not None:
                rec.finish(bsp, {"error": str(e)}, status="error")
            return
        def resolve():
            with _prof.section("ingest.finish", batch=seq, rows=len(batch)):
                self._resolve(seq, batch, results, bsp, rec)

        # cluster.rpc_mode sync: the publishers' futures (their PUBACKs)
        # resolve once the batch's forwards are confirmed; the flusher
        # goes on to the next batch meanwhile
        wait = getattr(self.broker, "after_forward_confirms", None)
        if wait is None or not wait(resolve):
            resolve()

    def _resolve(self, seq: int, batch, results, bsp, rec) -> None:
        """Settle one dispatched batch: every publisher's future, the
        settle latencies, the spans."""
        now = time.perf_counter()
        lane_lats: List[List[float]] = [[], [], []]
        for (m, fut, t0, lane), n in zip(batch, results):
            if not fut.done():
                fut.set_result(n)
            lane_lats[lane].append(now - t0)
            if rec is not None:
                # settle the publish span by its context header (the
                # fan-in edge back to the publisher's trace)
                rec.publish_finish(m.headers.get(TRACE_HEADER), n)
        self.metrics.observe_many(
            "ingest.settle.seconds", [now - t0 for _, _, t0, _ in batch]
        )
        for lane, lats in enumerate(lane_lats):
            if lats:
                # per-lane tails: the chaos tests assert the control
                # lane stays bounded while the low lane storms
                self.metrics.observe_many(LANE_SETTLE_SERIES[lane], lats)
        if rec is not None and bsp is not None:
            rec.finish(bsp)
        tp("ingest.settle", batch=seq, n=len(batch))

    def _engage_threshold(self) -> int:
        # below this pending count the device path won't engage anyway
        # (broker.dispatch_batch_folded falls back per-message), so waiting
        # a window would tax latency for zero batching gain
        return max(2, self.broker.router.min_tpu_batch)

    def _device_idle(self) -> bool:
        """Every in-flight dispatch's DEVICE work is done (their host
        fan-out may still be queued behind the FIFO settle)."""
        return all(pd.ready.done() for _, _, pd, _ in self._inflight)

    async def _run(self) -> None:
        while True:
            slo = self.slo
            if slo is not None:
                deg = getattr(self.broker, "degrade", None)
                self.window_s = slo.tick(
                    backlog=self._backlog(),
                    breaker_open=(
                        deg is not None and deg.device.state == OPEN
                    ),
                )
            if not self._inflight and not self._backlog():
                await self._event.wait()
            # one loop tick: every connection task that is ready to publish
            # gets to enqueue before we decide whether a window is worth it
            await asyncio.sleep(0)
            backlog = self._backlog()
            if (
                self.window_s > 0
                and not self._inflight
                and backlog >= self._engage_threshold()
                and backlog < self.max_batch
            ):
                # real concurrency: hold the window open to fill the batch
                t0 = time.perf_counter()
                await asyncio.sleep(self.window_s)
                self.metrics.observe(
                    "ingest.window.wait.seconds", time.perf_counter() - t0
                )
            # Launch rules. While a dispatch's DEVICE work is in flight,
            # only a FULL batch may launch: eagerly draining small batches
            # would multiply device round-trips and shrink per-dispatch
            # amortization (measured: e2e throughput collapsed ~3x when
            # the pipeline launched every pending dribble). But the
            # moment every in-flight dispatch's device work is DONE, a
            # PARTIAL batch launches too — batch N's host fan-out hasn't
            # run yet (FIFO settle below), so the partial overlaps it
            # with device compute instead of leaving the chip dark under
            # mid-load (the old full-batch/settle-boundary-only rule).
            batch: List = []
            if (
                not self._inflight
                or self._backlog() >= self.max_batch
                or (
                    self._backlog()
                    and len(self._inflight) < self.pipeline
                    and self._device_idle()
                )
            ):
                batch = self._take_batch(time.perf_counter())
            if batch:
                for lane, series in enumerate(LANE_DEPTH_SERIES):
                    self.metrics.gauge_set(
                        series, len(self._lane_list(lane))
                    )
                # LAUNCH now (prepare + executor submit), settle later:
                # a full next batch's launch overlaps this one's
                # round-trip. Fan-out happens ONLY at settle
                # (pd.complete()), in FIFO order — pd.ready is the
                # side-effect-free pacing signal (per-publisher
                # cross-batch ordering).
                seq, bsp = self._next_seq(batch)
                try:
                    with _prof.batch_ids(batch=seq, rows=len(batch)):
                        pd = self.broker.adispatch_begin(
                            [m for m, _, _, _ in batch], batch_span=bsp
                        )
                except Exception as e:  # noqa: BLE001 — flusher survives
                    log.exception("batch launch failed")
                    self.metrics.inc("ingest.launch.errors")
                    rec = getattr(self.broker, "spans", None)
                    for m, fut, _, _ in batch:
                        if not fut.done():
                            fut.set_exception(e)
                        if rec is not None:
                            rec.publish_finish(
                                m.headers.get(TRACE_HEADER), 0,
                                status="error",
                            )
                    if rec is not None and bsp is not None:
                        rec.finish(bsp, {"error": str(e)}, status="error")
                else:
                    self._inflight.append((seq, batch, pd, bsp))
                    self.metrics.gauge_set(
                        "ingest.pipeline.depth", len(self._inflight)
                    )
            if not self._inflight:
                if not self._backlog():
                    self._event.clear()
                elif not batch:
                    # everything pending is lane-deferred: nothing is
                    # launchable until the defer age bound releases it —
                    # bounded poll, never a busy spin
                    await asyncio.sleep(max(self.window_s, 0.001))
                continue
            if len(self._inflight) >= self.pipeline:
                seq, b, pd, bsp = self._inflight.popleft()
                await self._finish(seq, b, pd.complete(), bsp)
            elif not batch or not self._backlog():
                # dispatch in flight, nothing launchable: settle when
                # the device work completes OR re-check the moment new
                # publishes arrive (they may fill a full batch). The
                # event is cleared first so only NEW enqueues wake us —
                # otherwise a partial backlog would busy-spin this loop.
                self._event.clear()
                oldest_ready = self._inflight[0][2].ready
                ev = asyncio.ensure_future(self._event.wait())
                try:
                    await asyncio.wait(
                        {oldest_ready, ev},
                        return_when=asyncio.FIRST_COMPLETED,
                    )
                finally:
                    if not ev.done():
                        # retrieve the cancellation or the loop logs
                        # "Task was destroyed but it is pending" for
                        # every launch-in-flight/new-enqueue race.
                        # gather(return_exceptions) swallows EV's
                        # CancelledError but still re-raises OUR OWN
                        # task's cancellation (stop() must not hang)
                        ev.cancel()
                        await asyncio.gather(ev, return_exceptions=True)
                if oldest_ready.done():
                    if (
                        self._backlog()
                        and len(self._inflight) < self.pipeline
                        and self._device_idle()
                    ):
                        # device idle + launchable backlog: loop back so
                        # the partial LAUNCHES before this settle's host
                        # fan-out runs (the launch rule above fires on
                        # exactly this condition)
                        continue
                    seq, b, pd, bsp = self._inflight.popleft()
                    await self._finish(seq, b, pd.complete(), bsp)
