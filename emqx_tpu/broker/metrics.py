"""Broker metrics: counters + gauges + fixed-bucket histograms.

Parity with the reference's counter families (apps/emqx/src/emqx_metrics.erl:
89-104: bytes/packets/messages/deliveries; emqx_stats.erl gauges). Names use
the reference's dotted style so the management API and Prometheus exporter
surface familiar series.

Two additions over the reference's flat counter tables:

- a fixed-bucket `Histogram` (count/sum/cumulative buckets, lock-safe,
  p50/p95/p99 accessors) for the hot-path flight recorder — ingest batch
  occupancy, device match latency, dispatch fan-out;
- an explicit metric-kind REGISTRY: every series name is declared once with
  its kind (counter | gauge | histogram), so the exporters render `# TYPE`
  lines from declarations instead of guessing from name substrings, and
  the MN checker (`python -m tools.analysis --checks metrics`) can
  statically reject typo'd series names.
"""

from __future__ import annotations

import bisect
import threading
import time
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

COUNTER = "counter"
GAUGE = "gauge"
HISTOGRAM = "histogram"

# shared bucket ladders (upper bounds; +Inf is implicit)
LATENCY_BUCKETS: Tuple[float, ...] = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)
SIZE_BUCKETS: Tuple[float, ...] = (
    1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096,
)
RATIO_BUCKETS: Tuple[float, ...] = (
    0.01, 0.05, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0,
)
FANOUT_BUCKETS: Tuple[float, ...] = (
    0, 1, 2, 4, 8, 16, 32, 64, 256, 1024, 4096,
)
# device->host transfer sizes (bytes; pow4 ladder from 4KB to 256MB —
# a dense 4096-row bitmap batch at 1M slots is ~512MB, compacted ~1MB)
READBACK_BUCKETS: Tuple[float, ...] = (
    4096, 16384, 65536, 262144, 1048576, 4194304,
    16777216, 67108864, 268435456,
)


@dataclass(frozen=True)
class MetricSpec:
    name: str
    kind: str  # COUNTER | GAUGE | HISTOGRAM
    help: str = ""
    # histogram-only: upper bucket bounds; None => LATENCY_BUCKETS
    buckets: Optional[Tuple[float, ...]] = None
    # histogram-only: "seconds" lets the StatsD exporter render timers
    unit: str = ""


_REGISTRY: Dict[str, MetricSpec] = {}


def declare(
    name: str,
    kind: str,
    help: str = "",
    buckets: Optional[Sequence[float]] = None,
    unit: str = "",
) -> MetricSpec:
    """Register a series name with its kind. Re-declaring with the same
    kind is a no-op; a conflicting kind is a programming error."""
    if kind not in (COUNTER, GAUGE, HISTOGRAM):
        raise ValueError(f"unknown metric kind {kind!r}")
    prev = _REGISTRY.get(name)
    if prev is not None:
        if prev.kind != kind:
            raise ValueError(
                f"metric {name!r} re-declared as {kind}, was {prev.kind}"
            )
        return prev
    s = MetricSpec(
        name=name,
        kind=kind,
        help=help,
        buckets=tuple(buckets) if buckets is not None else None,
        unit=unit,
    )
    _REGISTRY[name] = s
    return s


def spec(name: str) -> Optional[MetricSpec]:
    return _REGISTRY.get(name)


def kind_of(name: str) -> Optional[str]:
    s = _REGISTRY.get(name)
    return s.kind if s is not None else None


def registry() -> Dict[str, MetricSpec]:
    """Snapshot of every declared series (runtime mirror of the set the
    MN checker collects statically)."""
    return dict(_REGISTRY)


class Histogram:
    """Fixed-bucket histogram: counts per upper bound + sum + total count.

    Prometheus-shaped (cumulative `_bucket{le=...}` + `_sum`/`_count`),
    lock-safe (`observe` runs from executor threads on the device-dispatch
    path). Percentiles interpolate linearly inside the landing bucket —
    exact enough for p50/p95/p99 dashboards, never a per-sample store.
    """

    __slots__ = ("bounds", "_counts", "sum", "count", "_lock")

    def __init__(self, buckets: Sequence[float] = LATENCY_BUCKETS):
        bounds = tuple(sorted(float(b) for b in buckets))
        if not bounds:
            raise ValueError("histogram needs at least one bucket bound")
        self.bounds = bounds  # immutable after construction
        self._counts = [0] * (len(bounds) + 1)  # guarded-by: _lock
        self.sum = 0.0  # guarded-by: _lock
        self.count = 0  # guarded-by: _lock
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        i = bisect.bisect_left(self.bounds, value)
        with self._lock:
            self._counts[i] += 1
            self.sum += value
            self.count += 1

    def observe_many(self, values: Sequence[float]) -> None:
        """Batch observe under one lock acquisition (settle loops record
        thousands of per-message latencies per batch)."""
        if not len(values):
            return
        idxs = [bisect.bisect_left(self.bounds, v) for v in values]
        with self._lock:
            for i in idxs:
                self._counts[i] += 1
            self.sum += float(sum(values))
            self.count += len(values)

    def add(self, total: float, count: int) -> None:
        """`count` observations summing to `total`, already aggregated by
        the caller (the section accumulators of observe/profiler.py: a
        mean and a share are what is read of them). They land in the
        overflow bucket: such a series' buckets carry no information."""
        with self._lock:
            self._counts[-1] += count
            self.sum += total
            self.count += count

    def percentile(self, q: float) -> float:
        """q in [0, 1]. 0.0 when empty; the last finite bound when the
        quantile lands in the +Inf overflow bucket."""
        with self._lock:
            counts = list(self._counts)
            total = self.count
        if total == 0:
            return 0.0
        rank = q * total
        cum = 0
        for i, c in enumerate(counts):
            if c == 0:
                continue
            prev_cum = cum
            cum += c
            if cum >= rank:
                if i >= len(self.bounds):  # +Inf bucket
                    return self.bounds[-1]
                lo = self.bounds[i - 1] if i > 0 else 0.0
                hi = self.bounds[i]
                frac = (rank - prev_cum) / c if c else 1.0
                return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
        return self.bounds[-1]

    @property
    def p50(self) -> float:
        return self.percentile(0.50)

    @property
    def p95(self) -> float:
        return self.percentile(0.95)

    @property
    def p99(self) -> float:
        return self.percentile(0.99)

    def snapshot(self) -> Dict:
        """-> {"count", "sum", "buckets": [(le, cumulative_count), ...]}
        with a final (inf, count) entry — exactly the exposition shape."""
        with self._lock:
            counts = list(self._counts)
            total = self.count
            s = self.sum
        out: List[Tuple[float, int]] = []
        cum = 0
        for le, c in zip(self.bounds, counts):
            cum += c
            out.append((le, cum))
        out.append((float("inf"), total))
        return {"count": total, "sum": s, "buckets": out}


class Metrics:
    def __init__(self) -> None:
        self._counters: Dict[str, int] = defaultdict(int)  # guarded-by: _lock
        self._gauges: Dict[str, float] = {}  # guarded-by: _lock
        self._histograms: Dict[str, Histogram] = {}  # guarded-by: _lock
        self._lock = threading.Lock()
        self.started_at = time.time()

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] += n

    def get(self, name: str) -> int:
        return self._counters.get(name, 0)

    def gauge_set(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value

    def gauge(self, name: str) -> float:
        with self._lock:
            return self._gauges.get(name, 0.0)

    # -- histograms --------------------------------------------------------
    def _histogram(self, name: str) -> Histogram:
        # double-checked locking: the dict read is GIL-atomic and the
        # slow path re-checks under _lock
        h = self._histograms.get(name)  # lint: disable=LK001
        if h is None:
            with self._lock:
                h = self._histograms.get(name)
                if h is None:
                    s = _REGISTRY.get(name)
                    h = Histogram(
                        s.buckets
                        if s is not None and s.buckets is not None
                        else LATENCY_BUCKETS
                    )
                    self._histograms[name] = h
        return h

    def observe(self, name: str, value: float) -> None:
        self._histogram(name).observe(value)

    def observe_many(self, name: str, values: Sequence[float]) -> None:
        self._histogram(name).observe_many(values)

    def add(self, name: str, total: float, count: int) -> None:
        self._histogram(name).add(total, count)

    def histogram(self, name: str) -> Optional[Histogram]:
        with self._lock:
            return self._histograms.get(name)

    def histograms(self) -> Dict[str, Dict]:
        """name -> Histogram.snapshot() for every recorded histogram."""
        with self._lock:
            items = list(self._histograms.items())
        return {name: h.snapshot() for name, h in items}

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            out = dict(self._counters)
            out.update(self._gauges)
        out["uptime_seconds"] = time.time() - self.started_at
        return out


default_metrics = Metrics()


# -- series declarations ---------------------------------------------------
# Every name passed to Metrics.inc/gauge_set/observe anywhere in emqx_tpu/
# must be declared here (enforced by the MN checker in tools/analysis, run
# as a tier-1 test). Grouped by subsystem.

# packets / messages (emqx_metrics.erl families)
declare("packets.sent", COUNTER, "MQTT packets written to clients")
declare("egress.writes", COUNTER,
        "socket writes by the in-process sink (Connection.flush: one per "
        "connection per batch boundary); packets.sent over this is how "
        "many packets one write carries")
declare("packets.received", COUNTER, "MQTT packets read from clients")
declare("channel.ack.runs", COUNTER,
        "runs of PUBACK / PUBREC / PUBCOMP handled in one pass over the "
        "session window (Channel._in_acks: a read chunk's run, or a lone "
        "ack); the entries of section channel.ack_in over this is how "
        "many acks one pass carries")
declare("dispatch.runs", COUNTER,
        "delivery runs handed over: a settled batch's deliveries to one "
        "in-process connection, one call of Channel.handle_deliver_run "
        "(Broker.DeliveryRuns.deliver; one add per batch)")
declare("dispatch.run.deliveries", COUNTER,
        "deliveries those runs carried (one add per batch); over "
        "dispatch.runs this is how many one pass over a session window "
        "carries, over messages.delivered the share of deliveries that "
        "went in runs. A delivery made per message (its deliverer offers "
        "no run: the pool's, a gateway's; or its run gave it back) is in "
        "neither")
declare("shared.picks", COUNTER,
        "device-resolved $share picks handed to the host for delivery "
        "(Broker._dispatch_row's picks branch; one add per launch); over "
        "the count of ingest.batch.size this is picks per launch")
declare("shared.picks.stale", COUNTER,
        "picks that delivered nothing: the group was dropped or its "
        "filter no longer matched while the batch was in flight, or no "
        "member took the message (on a cluster node also: another node "
        "leads this message's pick)")
declare("grouptab.uploads", COUNTER,
        "whole re-uploads of the device's group arrays: a launch found "
        "GroupTable's epoch bumped (growth, or its op-log past "
        "OPLOG_MAX) and could not scatter deltas")
declare("messages.received", COUNTER, "messages entering dispatch")
declare("messages.delivered", COUNTER, "deliveries handed to subscribers")
declare("messages.dropped", COUNTER, "messages dropped before dispatch")
declare("messages.dropped.no_subscribers", COUNTER)
declare("messages.dropped.not_authorized", COUNTER)
declare("messages.dispatch_error", COUNTER)
declare("messages.routed.device", COUNTER,
        "batch rows routed by the device kernel")
declare("messages.routed.device_fallback", COUNTER,
        "batch rows the device flagged; routed by the CPU trie")
declare("route.nfa.matches", COUNTER,
        "matches the residual NFA engine's columns of a launch's "
        "`matched` gave (rows it flagged left out; one add per launch, "
        "from the readback's host copy); 0 while no filter is residual. "
        "Over the count of ingest.batch.size: NFA matches per launch")
declare("route.nfa.flagged", COUNTER,
        "rows the residual NFA engine flagged (each once): they are "
        "served by the CPU trie and counted in "
        "messages.routed.device_fallback")
declare("route.nfa.flagged.too_deep", COUNTER,
        "flagged rows by cause: more levels than matcher.max_levels (16)")
declare("route.nfa.flagged.frontier_overflow", COUNTER,
        "flagged rows by cause: more live NFA states at one level than "
        "matcher.frontier (32)")
declare("route.nfa.flagged.match_overflow", COUNTER,
        "flagged rows by cause: more residual filters matched than "
        "matcher.max_matches (64) columns hold")
declare("route.nfa.levels", COUNTER,
        "levels the residual NFA walk took (one add per launch): its "
        "launch's deepest row, at most matcher.max_levels (16). Over the "
        "count of ingest.batch.size: levels per launch")
declare("route.nfa.frontier_peak", COUNTER,
        "the most live NFA states any row of a launch had after a walked "
        "level, before the matcher.frontier (32) cap (one add per launch; "
        "the max over a mesh's shards). Over the count of "
        "ingest.batch.size: the peak per launch")
declare("messages.forward.failed", COUNTER)
declare("delivery.errors", COUNTER)

# admission / overload
declare("limiter.refused.connection", COUNTER)
declare("limiter.dropped.message_routing", COUNTER)
declare("olp.refused", COUNTER)
declare("olp.lag_ms", GAUGE,
        "last sampled event-loop lag (the Olp overload signal)")
declare("olp.trips", COUNTER,
        "overload trips: lag crossed the watermark from a calm state")
declare("node.drained", COUNTER)

# -- fault injection + graceful degradation (observe/faults.py,
# broker/degrade.py; docs/robustness.md) ----------------------------------
declare("faults.injected", COUNTER,
        "fault-site fires across every armed rule (soak audit trail)")
declare("degrade.state.device", GAUGE,
        "device-path breaker state: 0 closed, 1 half-open, 2 open "
        "(open = batches served by the CPU trie)")
declare("degrade.state.cluster_send", GAUGE,
        "cluster-send breaker state (most recent transition across "
        "destinations): 0 closed, 1 half-open, 2 open")
declare("degrade.trips.device", COUNTER,
        "device-path breaker closed -> open transitions")
declare("degrade.trips.cluster_send", COUNTER,
        "cluster-send breaker closed -> open transitions (any dest)")
declare("degrade.probe.ok", COUNTER,
        "half-open probes that succeeded (recovery evidence)")
declare("degrade.probe.fail", COUNTER,
        "half-open probes that failed (dwell restarted)")
declare("degrade.retries", COUNTER,
        "bounded backoff retry attempts before degrading a batch")
declare("degrade.fallback.batches", COUNTER,
        "whole batches served by the CPU trie because the device path "
        "failed or its breaker was open")
declare("device.warmup.failed", COUNTER,
        "start-up route-step warm-ups that raised (the broker serves on "
        "with a cold kernel; a run that must prove the device reads 0)")
declare("ingest.shed", COUNTER,
        "enqueues refused at the ingest gate (olp overloaded or device "
        "breaker open past the queue bound) — backpressure, not loss")
# SLO-driven adaptive batching (broker/slo.py; docs/robustness.md) -------
declare("slo.window_us", GAUGE,
        "current adaptive ingest window (microseconds)")
declare("slo.ladder.rung", GAUGE,
        "backpressure ladder rung: 0 normal, 1 widen, 2 defer, 3 shed")
declare("slo.p99.observed_ms", GAUGE,
        "enqueue->settle p99 over the last SLO evaluation window")
declare("slo.p99.target_ms", GAUGE,
        "configured p99 target the controller holds")
declare("slo.eval.windows", COUNTER,
        "SLO controller evaluation windows closed")
declare("slo.violations", COUNTER,
        "evaluation windows whose observed p99 missed the target")
declare("slo.adjustments", COUNTER,
        "window-size changes the controller applied")
declare("slo.deferrals", COUNTER,
        "launches the low-priority lane sat out on the defer rung")
declare("slo.shed", COUNTER,
        "enqueues refused by the graded shed rung (subset of ingest.shed)")
declare("retained.storm.deferred", COUNTER,
        "storm fuses/flushes deferred by the SLO ladder")
declare("router.sync.rollback", COUNTER,
        "dirty prepares that failed or tore and rolled back to the "
        "last good epoch snapshot")
declare("cluster.send.retries", COUNTER,
        "cluster send attempts retried after a transport failure")
declare("cluster.send.dead_letter", COUNTER,
        "cluster sends given up after deadline/retry budget (the "
        "bounded dead-letter count)")

# the cluster's forward lanes and route replication (cluster/node.py)
declare("cluster.forward.batches", COUNTER,
        "per-destination batches handed to the forward lanes")
declare("cluster.forward.messages", COUNTER,
        "messages handed to the forward lanes (one per message and "
        "destination node)")
declare("cluster.forward.retries", COUNTER,
        "forward calls sent again: the connection broke or the peer "
        "could not be reached while alive by membership; or a group "
        "rerouted to a dead node's successor")
declare("cluster.forward.duplicates", COUNTER,
        "repeated forward batches the receiver answered with the first "
        "one's result, without a second dispatch")
declare("cluster.forward.unconfirmed", GAUGE,
        "messages handed to the forward lanes and not yet confirmed by "
        "their destination node, all peers")
declare("cluster.forward.confirm.seconds", HISTOGRAM,
        "hand-off of a forward batch to its destination's confirmation "
        "(the dispatch there), per batch",
        buckets=LATENCY_BUCKETS, unit="seconds")
declare("cluster.route.batches", COUNTER,
        "`route` v2 apply_batch calls received from peers")
declare("cluster.route.ops", COUNTER,
        "(op, filter) pairs received in those batches")

# worker fabric (transport/workers.py)
declare("fabric.sess.crash_parked", COUNTER)
declare("fabric.sess.resumes", COUNTER)
declare("fabric.sess.takeovers", COUNTER)
declare("fabric.sess.decode_errors", COUNTER)
declare("fabric.flush.errors", COUNTER)
declare("fabric.parked.dropped", COUNTER)
declare("fabric.parked.replayed", COUNTER)
declare("fabric.puback.timeouts", COUNTER)
declare("fabric.raw.records", COUNTER)
declare("fabric.link.lost", COUNTER)
declare("fabric.link.reconnected", COUNTER)
declare("fabric.worker.crash_loop", COUNTER)
declare("fabric.worker.respawns", COUNTER)

# -- slab protocol plane (transport/fabric.py slab codec, zero-copy
# ingest, batched delivery/resend serialization; docs/protocol_plane.md)
declare("fabric.slab.pub.frames", COUNTER,
        "T_PUBB_S frames unpacked via the vectorized slab codec")
declare("fabric.slab.pub.records", COUNTER,
        "publish records recovered by slab header scans (no per-record "
        "struct.unpack, no tuple materialization)")
declare("fabric.slab.dlv.frames", COUNTER,
        "T_DLV_S delivery frames packed from once-serialized regions")
declare("fabric.slab.dlv.records", COUNTER,
        "delivery records packed via the slab codec (one per "
        "(message, worker) — fan-out stays worker-side)")
declare("ingest.zerocopy.records", COUNTER,
        "messages entering ingest as slab-backed views: topic bytes "
        "feed the tokenizer straight from the fabric read buffer")
declare("ingest.zerocopy.deferred.bytes", COUNTER,
        "topic+payload bytes whose str-decode/copy was deferred at "
        "ingest (paid later only if a consumer materializes)")
declare("dispatch.serialize.batches", COUNTER,
        "batched PUBLISH serialization passes (one slab build for a "
        "whole resend/delivery batch)")
declare("dispatch.serialize.frames", COUNTER,
        "outbound PUBLISH frames emitted by the slab serializer / "
        "split-frame fan-out (serialize once, patch the packet id)")
declare("dispatch.serialize.bytes", COUNTER,
        "bytes serialized by the batched slab passes")

# cluster
declare("cluster.nodedown.routes_purged", COUNTER)
declare("cluster.retain.bootstrap_failed", COUNTER)
declare("cluster.retain.dump_truncated", COUNTER)

# gauges (emqx_stats.erl analogs + monitor extras)
declare("connections.count", GAUGE)
declare("subscriptions.count", GAUGE)
declare("shared.subscriptions.count", GAUGE,
        "$share subscriptions held: members over every (real filter, "
        "group), SharedSub's running count; part of subscriptions.count")
declare("grouptab.groups", GAUGE,
        "(real filter, group) pairs with a row in the device's group "
        "table (GroupTable)")
declare("route.shapes.active", GAUGE,
        "width of the shape index's device slice (ShapeIndex.m_active: "
        "the active shapes, pow2-bucketed, MAX_SHAPES = 64 at the most), "
        "set when DeviceRouter.prepare finds the tables changed")
declare("route.residual.filters", GAUGE,
        "distinct filters the shape index could not take (a 65th shape, "
        "a hash collision): the residual NFA engine matches them inside "
        "the same route step (`with_nfa`); set with route.shapes.active")
declare("route.nfa.frontier_width", GAUGE,
        "frontier lanes a row carries in the residual NFA walk: the "
        "residual trie's exact bound on live states, rounded up to a "
        "multiple of 8, at most matcher.frontier (32); 0 while no filter "
        "is residual; set with route.shapes.active")
declare("route.nfa.match_width", GAUGE,
        "match columns the residual NFA walk appends to: the residual "
        "trie's exact bound on the filters one topic matches, rounded up "
        "to a multiple of 8, at most matcher.max_matches (64); 0 while no "
        "filter is residual; set with route.shapes.active")
declare("topics.count", GAUGE)
declare("retained.count", GAUGE)
declare("delayed.count", GAUGE)
declare("sessions.restored", GAUGE)
declare("cpu.usage", GAUGE)
declare("mem.usage", GAUGE)
declare("tasks.count", GAUGE)
declare("uptime_seconds", GAUGE)

# -- hot-path flight recorder (ingest -> matcher -> dispatch) --------------
declare("ingest.batch.size", HISTOGRAM,
        "messages per launched ingest batch", buckets=SIZE_BUCKETS)
declare("ingest.batch.occupancy", HISTOGRAM,
        "launched batch size / max_batch", buckets=RATIO_BUCKETS)
declare("ingest.window.wait.seconds", HISTOGRAM,
        "time the adaptive batch window was held open",
        buckets=LATENCY_BUCKETS, unit="seconds")
declare("ingest.settle.seconds", HISTOGRAM,
        "per-message enqueue -> settle (PUBACK-visible) latency",
        buckets=LATENCY_BUCKETS, unit="seconds")
declare("ingest.pipeline.depth", GAUGE,
        "device dispatches in flight after the last launch")
declare("ingest.lane.depth.control", GAUGE,
        "pending control-lane messages (QoS2 flow / $SYS) at launch")
declare("ingest.lane.depth.normal", GAUGE,
        "pending normal-lane messages at launch")
declare("ingest.lane.depth.low", GAUGE,
        "pending low-lane messages (QoS0 firehose / tagged) at launch")
declare("ingest.lane.settle.seconds.control", HISTOGRAM,
        "control-lane enqueue->settle latency (the bounded-tail gate)",
        unit="seconds")
declare("ingest.lane.settle.seconds.normal", HISTOGRAM,
        "normal-lane enqueue->settle latency", unit="seconds")
declare("ingest.lane.settle.seconds.low", HISTOGRAM,
        "low-lane enqueue->settle latency (defer-eligible)",
        unit="seconds")
declare("ingest.lane.starvation.breaks", COUNTER,
        "launches that reserved low-lane slots past the starvation bound")
declare("ingest.launch.errors", COUNTER,
        "batch launches that raised before reaching the device")
declare("ingest.dispatch.errors", COUNTER,
        "batch dispatches that raised at settle time")

declare("router.batch.size", HISTOGRAM,
        "topic rows per serving-path device batch", buckets=SIZE_BUCKETS)
declare("router.device.seconds", HISTOGRAM,
        "serving-path route_step wall time (launch + readback)",
        buckets=LATENCY_BUCKETS, unit="seconds")
declare("router.sync.seconds", HISTOGRAM,
        "serving-path table snapshot + delta upload time",
        buckets=LATENCY_BUCKETS, unit="seconds")
declare("router.sync.skipped", COUNTER,
        "prepares that skipped pack/delta-sync entirely (every source "
        "table's generation counter unchanged — the steady state)")
declare("router.prepare.dirty", COUNTER,
        "prepares that re-snapshotted at least one table (churn since "
        "the last batch)")

# segmented update path (ops/segments.py, docs/update_path.md)
declare("router.segment.hot.fill", GAUGE,
        "live entries in the shape-index hot segment (subscribes since "
        "the last compaction)")
declare("router.segment.hot.capacity", GAUGE,
        "hot-segment slot capacity (pow2; grows by doubling, re-uploads "
        "alone via the per-array resync marker)")
declare("router.segment.tombstones", GAUGE,
        "tombstoned packed-table slots awaiting compaction (unsubscribed "
        "entries masked out of the match)")
declare("router.compact.runs", COUNTER,
        "background segment-compaction cycles applied (hot segment "
        "merged into a rebuilt packed table off the critical path)")
declare("router.compact.aborted", COUNTER,
        "compaction cycles discarded (a structural rebuild raced the "
        "background build; retried next interval)")
declare("router.compact.merged", COUNTER,
        "hot-segment entries merged into the packed table by compaction")
declare("router.compact.seconds", HISTOGRAM,
        "wall seconds per compaction cycle (capture + executor build + "
        "pre-upload + journal-replay apply)",
        buckets=LATENCY_BUCKETS, unit="seconds")
declare("router.compact.lag.seconds", GAUGE,
        "seconds the compaction trigger has been pending (0 when the "
        "hot segment is under threshold; sustained growth means "
        "compaction cannot keep up with churn)")

# sparse (CSR) subscriber table (ops/csr_table.py, router.sub_table
# policy; docs/serving_pipeline.md "subscriber-table memory budget")
declare("router.sparse.flips", COUNTER,
        "subscriber-table representation flips served (dense bitmap "
        "matrix <-> CSR slot lists; auto mode flips at most once)")
declare("router.sparse.overflow.rows", COUNTER,
        "sparse-path rows whose fan-out exceeded the Kslot/gather "
        "window and rebuilt their recipient set from the host table")
declare("router.sparse.bytes", GAUGE,
        "device footprint of the CSR subscriber table (slot column + "
        "region lanes + hot segment) — the sub_table_bytes number")
declare("router.sparse.fill", GAUGE,
        "live subscriptions in the CSR table")
declare("router.sparse.tombstones", GAUGE,
        "tombstoned CSR entries (packed column + hot) awaiting "
        "compaction")
declare("router.sparse.hot.fill", GAUGE,
        "live entries in the CSR hot segment (subscribes since the "
        "last compaction)")

# scale-out sharded serving (parallel/mesh.py dist_fused_step,
# cluster/route_sync.ShardOwnership, docs/scale_out.md)
declare("mesh.shard.count", GAUGE,
        "device shards in the local serving mesh (dp x tp product; 0 "
        "when SPMD serving is off)")
declare("mesh.shard.fill", GAUGE,
        "max per-tp-shard subscriber-lane occupancy (nonzero words / "
        "words in the fullest lane slice; sustained skew vs the min "
        "means one chip carries the fan-out wall)")
declare("mesh.shard.scatter.launches", COUNTER,
        "O(delta) scatter launches that landed on SHARDED mirrors "
        "(churn reaching the mesh without a full table re-upload)")
declare("mesh.shard.compact.runs", COUNTER,
        "background compaction cycles whose rebuilt tables pre-uploaded "
        "straight into the sharded layout (placement hook present)")
declare("mesh.shard.rebalance", COUNTER,
        "shard ownership moves after a node loss (rendezvous re-own; "
        "each move is one slice adopting a survivor)")
declare("mesh.shard.reroutes", COUNTER,
        "publish forwards rerouted from a dead shard owner to its "
        "rendezvous successor (the stall the re-own ladder removes)")

# -- device-resident session store (broker/session_store.py,
# ops/session_table.py; docs/sessions.md) ----------------------------------
declare("session.mqueue.dropped", COUNTER,
        "QoS>=1 deliveries a full session queue dropped (MQueue.in_; "
        "also the message.dropped hook, reason queue_full)")
declare("session.store.sessions", GAUGE,
        "live session slots registered in the store")
declare("session.store.inflight", GAUGE,
        "live inflight/awaiting-rel rows in the session table")
declare("session.store.tombstones", GAUGE,
        "acked (cleared) session rows awaiting compaction")
declare("session.ack.rides", COUNTER,
        "session write batches fused onto a serving launch "
        "(session_ack_step riding session_route_step: zero extra "
        "launches, zero extra readbacks)")
declare("session.ack.rows", COUNTER,
        "row writes (delivery inserts + PUBACK/PUBREC/PUBCOMP/PUBREL "
        "clears) applied via fused rides")
declare("session.ack.scatters", COUNTER,
        "session deltas applied via the segment scatter path instead "
        "(mesh engine, idle broker, or degraded device path)")
declare("session.sweep.device", COUNTER,
        "QoS retry/expiry sweeps that rode a serving launch")
declare("session.sweep.host", COUNTER,
        "host-array fallback sweeps (idle broker, non-fusing engine, "
        "or device path degraded)")
declare("session.sweep.due", COUNTER,
        "rows a sweep found due for retransmit (uncapped count)")
declare("session.redeliveries", COUNTER,
        "QoS1/2 retransmits sent from sweep hits (host re-verified)")
declare("session.expired.swept", COUNTER,
        "sessions the expiry sweep flagged past their deadline")
declare("session.resume.replayed", COUNTER,
        "sessions resumed via segment replay (store install: one full "
        "upload re-arms every inflight window)")

# retained-replay storm feed (broker/retained_feed.py)
declare("retained.storm.filters", COUNTER,
        "wildcard replay filters batched through the storm feed")
declare("retained.storm.fused", COUNTER,
        "storm jobs fused into a serving launch "
        "(fused_route_retained_step: zero extra launches)")
declare("retained.storm.flushed", COUNTER,
        "storm jobs answered by a standalone match_many flush (no "
        "publish launch arrived inside the window)")

declare("dispatch.fanout", HISTOGRAM,
        "deliveries per dispatched message", buckets=FANOUT_BUCKETS)
declare("dispatch.readback.bytes", HISTOGRAM,
        "device->host bytes read back per routed batch (compact slot "
        "lists + masked overflow rows, or full dense bitmaps)",
        buckets=READBACK_BUCKETS)
declare("dispatch.compact.rows", COUNTER,
        "batch rows dispatched from the compact slot list (no dense "
        "bitmap decode)")
declare("dispatch.compact.overflow.rows", COUNTER,
        "rows whose fan-out exceeded the Kslot cap (dense-row fallback "
        "via the masked second transfer)")

# -- device runtime telemetry (observe/device_watch.py) --------------------
declare("device.compile.count", COUNTER,
        "jit backend compiles observed (boot warmup + any retraces); "
        "nonzero growth in steady state is a retrace storm")
declare("device.compile.seconds", HISTOGRAM,
        "wall seconds per observed backend compile (window mean when "
        "only totals are available)",
        buckets=LATENCY_BUCKETS, unit="seconds")
declare("device.compile.cache_size", GAUGE,
        "summed jit-cache entries across @device_contract kernels and "
        "built mesh step programs (flat in steady state)")
declare("device.compile.in_launch.count", COUNTER,
        "backend compiles that ran inside an open `launch` section "
        "(route-step programs)")
declare("device.compile.in_readback.count", COUNTER,
        "backend compiles that ran inside an open `readback` section "
        "(the per-B dynamic_slice programs)")
declare("device.hbm.peak.bytes", GAUGE,
        "allocator peak_bytes_in_use where the backend reports it, else "
        "the running maximum of device.hbm.bytes")
declare("device.hbm.bytes", GAUGE,
        "live device memory: allocator bytes_in_use, or summed live "
        "array nbytes on backends without memory stats")
declare("device.transfer.bytes", COUNTER,
        "cumulative device->host readback bytes across all readback "
        "sites (rate = sustained link bandwidth consumed)")

# -- runtime race harness (observe/racetrack.py) ---------------------------
declare("racetrack.events", COUNTER,
        "accesses probed while the race harness is armed (the race test "
        "suite and chaos_soak; disarmed production cost is zero)")
declare("race.reports", COUNTER,
        "candidate data races reported by the armed lockset/HB detector "
        "(field + both stacks + locksets; zero unwaived is the gate)")

# -- shadow-replica replication audit (observe/replay_check.py) ------------
declare("replay.captures", COUNTER,
        "sync records captured by armed replay taps (full epoch uploads "
        "+ op-log delta suffixes; disarmed production cost is zero)")
declare("replay.syncs", COUNTER,
        "manager sync() calls observed while a replay tap is armed")
declare("replay.offers", COUNTER,
        "compaction offers observed while a replay tap is armed")
declare("replay.divergence", COUNTER,
        "owners whose shadow replica failed array-exact convergence "
        "(zero is the gate; any count means the op-log stream a standby "
        "would receive is incomplete)")
declare("analysis.replay.runs", COUNTER,
        "replication replay audits executed (ci_gate --replay and the "
        "chaos_soak probe)")
declare("analysis.replay.failures", COUNTER,
        "replay audits that diverged or missed the seeded "
        "incomplete-log negative control")
declare("analysis.wirecompat.runs", COUNTER,
        "wire-compatibility audits executed (ci_gate --audit replays "
        "the golden byte corpus through current decoders)")
declare("analysis.wirecompat.failures", COUNTER,
        "wirecompat audits that failed: corpus divergence, live-layout "
        "drift vs the format registry, an uncovered format, or a "
        "missed drift control")
declare("proto.registry.formats", GAUGE,
        "externalized wire/snapshot formats declared in "
        "emqx_tpu/proto/registry.py (each needs a version, a pinned "
        "digest, and golden-corpus coverage)")

# -- causal span tracing (observe/spans.py) --------------------------------
declare("trace.spans.sampled", COUNTER,
        "spans recorded into the ring (head-based sampling accepted)")
declare("trace.spans.dropped", COUNTER,
        "spans lost unfinished (open-registry overflow or a settle that "
        "found no open span)")

# -- semantic routing plane (docs/semantic_routing.md) ---------------------
declare("semantic.filters", GAUGE,
        "live embedding-filter subscriptions in the semantic table")
declare("semantic.hits", COUNTER,
        "qualifying semantic matches on the fused device path "
        "(pre-top-k; the uncapped sem_count sum per batch)")
declare("semantic.topk.truncated", COUNTER,
        "routed rows whose qualifying set exceeded topk (winners "
        "delivered, the tail dropped BY DESIGN)")
declare("semantic.host.batches", COUNTER,
        "batches/messages routed through the host twin (CPU fallback, "
        "per-message paths) instead of the fused kernel")
declare("semantic.host.matches", COUNTER,
        "semantic recipients resolved by the host twin")
declare("semantic.subscribe.rejected", COUNTER,
        "embedding filters ignored at subscribe (no semantic plane "
        "attached, or a $share filter)")
declare("semantic.embed.rejected", COUNTER,
        "per-message embeddings dropped as malformed (bad base64/JSON "
        "or a dimension mismatch)")

# -- rule engine (rules/engine.py; device predicates rules/compile.py) -----
declare("rules.matched", COUNTER,
        "rule evaluations whose FROM clause selected the event")
declare("rules.passed", COUNTER,
        "rule evaluations that passed WHERE and produced output rows")
declare("rules.failed", COUNTER,
        "rule evaluations that raised during SQL evaluation")
declare("rules.dropped", COUNTER,
        "rule evaluations dropped by WHERE (or an empty FOREACH) — on "
        "the device path these rows never built a host context")
declare("rules.device.batches", COUNTER,
        "settled batches whose compiled WHERE masks came from the "
        "serving launch (device rate)")
declare("rules.host.batches", COUNTER,
        "settled batches that fell back to the vectorized numpy WHERE "
        "evaluator (degraded/CPU batches, rule-set churn in flight)")

# -- profiling plane (observe/profiler.py; docs/observability.md
#    "Profiling & provenance") ---------------------------------------------
# the per-launch stage waterfall: prepare -> queue_wait -> launch ->
# device_execute -> readback -> host_dispatch. Observed per BATCH from
# the serving hot path (a handful of perf_counter reads), so the sum of
# stage means tracks the enqueue->settle latency the SLO controller
# steers on — the decomposition says WHERE a regression lives.
declare("profile.stage.prepare.seconds", HISTOGRAM,
        "waterfall: table snapshot + upload before the launch "
        "(Broker.adispatch_begin around dev.prepare)",
        buckets=LATENCY_BUCKETS, unit="seconds")
declare("profile.stage.queue_wait.seconds", HISTOGRAM,
        "waterfall: per-message enqueue -> batch-launch wait "
        "(window accumulation + lane queueing, BatchIngest)",
        buckets=LATENCY_BUCKETS, unit="seconds")
declare("profile.stage.launch.seconds", HISTOGRAM,
        "waterfall: host-side batch encode + kernel enqueue "
        "(DeviceRouter._route_prepared up to the readback boundary)",
        buckets=LATENCY_BUCKETS, unit="seconds")
declare("profile.stage.device_execute.seconds", HISTOGRAM,
        "waterfall: device program completion wait "
        "(block_until_ready at the readback boundary)",
        buckets=LATENCY_BUCKETS, unit="seconds")
declare("profile.stage.readback.seconds", HISTOGRAM,
        "waterfall: the coalesced device_get + host-side decode",
        buckets=LATENCY_BUCKETS, unit="seconds")
declare("profile.stage.host_dispatch.seconds", HISTOGRAM,
        "waterfall: settle-time host fan-out of one device batch "
        "(Broker._dispatch_device_results)",
        buckets=LATENCY_BUCKETS, unit="seconds")
# on-demand jax.profiler capture (REST-armed, bounded duration + file
# budget; disarmed cost is structurally zero — no hot-path hook exists)
declare("profile.captures", COUNTER,
        "completed jax.profiler trace captures (armed via "
        "POST /api/v5/profile)")
declare("profile.cost.kernels", GAUGE,
        "contract kernels covered by the last cost-analysis harvest "
        "(14 = the full registry)")

# -- hardware provenance (observe/provenance.py) ---------------------------
declare("provenance.proxy", GAUGE,
        "1 when the detected backend is NOT a TPU: every number this "
        "process emits is a CPU/GPU proxy, never a number of record")
declare("provenance.device.count", GAUGE,
        "devices visible to the backend this process measured on")

# -- the owner thread's time budget (observe/profiler.py sections) --------
# The section names are a contract (PERF.md section 3 lists each with the
# metric that reads it). Sums and counts only (`Histogram.add` at flush:
# scrape, GET /api/v5/profile, the 1 Hz tick); read as a mean or a share.
OWNER_SECTIONS: Tuple[str, ...] = (
    # the device owner's main thread
    "ingress.decode",       # parser.feed of a read chunk / a pool worker's PUB slab
    "channel.publish_in",   # Channel._in_publish up to the enqueue (checks, authz)
    "ingest.enqueue",       # Broker.apublish_enqueue: message.publish fold + lane append
    "channel.ack_in",       # a chunk's run of PUBACK / PUBREC / PUBCOMP incl. the drain
    "egress.send",          # serialise + append (packets); a flush's socket write; a DLV flush
    "ingest.take",          # BatchIngest._take_batch
    "prepare",              # stage: table snapshot + upload
    "ingest.finish",        # BatchIngest._finish: the per-message fut.set_result loop
    "host_dispatch",        # stage: settle-time fan-out
    "shared.dispatch_picked",  # a message's device picks -> SharedSub.dispatch_picked (entries: picks handed over)
    "broker.share_subscribe",  # the shared half of Broker.subscribe (entries: shared subscriptions)
    "housekeeping",         # the 1 Hz tick
    "cluster.forward.out",  # ClusterNode.forward_batch_remote: replica match, grouping, hand-off (entries: messages forwarded)
    "cluster.forward.in",   # the receiving half of a forward up to its dispatch (entries: messages)
    "cluster.route.apply",  # one applied slice of a peer's route batch
)
SECTIONS: Tuple[str, ...] = OWNER_SECTIONS + (
    # the dispatch executor's threads
    "launch",
    "device_execute",
    "readback",
)
for _name in SECTIONS:
    declare(f"profile.section.{_name}.seconds", HISTOGRAM,
            f"section {_name}: seconds per entry, children included",
            unit="seconds")
    declare(f"profile.section.{_name}.self.seconds", HISTOGRAM,
            f"section {_name}: seconds per entry less its child sections",
            unit="seconds")
declare("owner.loop.select.seconds", HISTOGRAM,
        "the owner loop blocked in select(): idle, waiting for I/O "
        "(one observation per loop iteration)", unit="seconds")
declare("owner.loop.run.seconds", HISTOGRAM,
        "the owner loop between two selects: busy "
        "(one observation per loop iteration)", unit="seconds")
declare("owner.loop.other.seconds", HISTOGRAM,
        "run time of the owner loop that no section names: its run time "
        "less its thread's sections' self time", unit="seconds")
declare("owner.loop.stall.seconds", HISTOGRAM,
        "run phases of the owner loop longer than 0.5 s (one observation "
        "per stall; each is logged with its section and GC share)",
        unit="seconds")
declare("owner.gc.pause.seconds", HISTOGRAM,
        "python GC passes of the owner process, all generations "
        "(SysMon's gc hook)", unit="seconds")
declare("owner.gc.gen2.seconds", HISTOGRAM,
        "full (generation 2) GC passes of the owner process",
        unit="seconds")
declare("owner.gc.freezes", COUNTER,
        "times the collector policy moved what arrived out of the "
        "generations (observe/gc_policy.py: growth of the table or the "
        "session set, seen by the housekeeping tick)")
declare("owner.gc.thaws", COUNTER,
        "thaw passes of the collector policy (unfreeze, full collection, "
        "freeze): releases passed a quarter of the frozen items")
declare("owner.gc.frozen.objects", GAUGE,
        "objects the collector policy holds outside the generations, as "
        "counted when they were frozen (set at every tick)")

# -- the chip's dark time (observe/profiler.py DarkLedger) ------------------
# Dark: no launch lit, from a launch's device_execute end (none left lit)
# to the next kernel enqueue (the end of section `launch`). Each interval's
# causes add up to it: `encode` (the closing batch's ingest.take to its
# enqueue), `select` (the owner loop blocked, nothing launchable), the
# outermost owner-thread section (children included), `other`.
DARK_CAUSES: Tuple[str, ...] = ("select", "encode", "other") + OWNER_SECTIONS
declare("device.dark.seconds", HISTOGRAM,
        "the chip's dark time: one observation per dark interval (its "
        "count is the number of intervals); a flush inside an open "
        "interval adds its elapsed part, which its close does not repeat",
        unit="seconds")
for _name in DARK_CAUSES:
    declare(f"device.dark.{_name}.seconds", HISTOGRAM,
            f"dark time put down to {_name}; the causes add up to "
            "device.dark.seconds", unit="seconds")
