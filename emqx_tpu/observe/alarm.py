"""Alarm lifecycle: activate/deactivate named alarms with history.

Parity with the reference (apps/emqx/src/emqx_alarm.erl): alarms are named,
carry details + message, live in an activated table until deactivated, then
move to a capped history; every transition republishes to
$SYS/brokers/<node>/alarms/activate|deactivate so MQTT clients can watch
them (the reference's emqx_alarm_handler behavior).
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from emqx_tpu.utils.node import node_name


@dataclass
class Alarm:
    name: str
    details: Dict = field(default_factory=dict)
    message: str = ""
    activated_at: float = field(default_factory=time.time)
    deactivated_at: Optional[float] = None

    def to_json(self) -> Dict:
        return {
            "name": self.name,
            "node": node_name(),
            "details": self.details,
            "message": self.message,
            "activated_at": self.activated_at,
            "deactivated_at": self.deactivated_at,
            "duration": (
                (self.deactivated_at or time.time()) - self.activated_at
            ),
        }


class AlarmManager:
    def __init__(
        self,
        publish: Optional[Callable] = None,
        size_limit: int = 1000,
        validity_period: float = 24 * 3600.0,
    ):
        """`publish(topic, payload_bytes)` republishes transitions ($SYS)."""
        self._active: Dict[str, Alarm] = {}
        self._history: List[Alarm] = []
        self._publish = publish
        self.size_limit = size_limit
        self.validity_period = validity_period

    def activate(
        self, name: str, details: Optional[Dict] = None, message: str = ""
    ) -> bool:
        """Returns False when already active (reference: {error, duplicated})."""
        if name in self._active:
            return False
        alarm = Alarm(name=name, details=details or {}, message=message)
        self._active[name] = alarm
        self._republish("activate", alarm)
        return True

    def deactivate(self, name: str) -> bool:
        alarm = self._active.pop(name, None)
        if alarm is None:
            return False
        alarm.deactivated_at = time.time()
        self._history.append(alarm)
        if len(self._history) > self.size_limit:
            del self._history[: len(self._history) - self.size_limit]
        self._republish("deactivate", alarm)
        return True

    def ensure(self, name: str, active: bool, details=None, message="") -> None:
        """Level-triggered helper: (de)activate to match a boolean condition."""
        if active:
            self.activate(name, details, message)
        else:
            self.deactivate(name)

    def is_active(self, name: str) -> bool:
        return name in self._active

    def list(self, activated: Optional[bool] = None) -> List[Dict]:
        if activated is True:
            items = list(self._active.values())
        elif activated is False:
            items = list(self._history)
        else:
            items = list(self._active.values()) + list(self._history)
        return [a.to_json() for a in items]

    def delete_all_deactivated(self) -> int:
        n = len(self._history)
        self._history.clear()
        return n

    def sweep(self, now: Optional[float] = None) -> None:
        """Expire history entries past validity_period (emqx_alarm GC)."""
        now = now or time.time()
        self._history = [
            a
            for a in self._history
            if (a.deactivated_at or now) + self.validity_period > now
        ]

    def _republish(self, kind: str, alarm: Alarm) -> None:
        if self._publish is None:
            return
        topic = f"$SYS/brokers/{node_name()}/alarms/{kind}"
        try:
            self._publish(topic, json.dumps(alarm.to_json()).encode())
        except Exception:
            pass


class FallbackRateWatch:
    """Level-triggered alarm on the TPU-path fallback-row rate.

    Sustained fallback means the device kernel has effectively degraded to
    the CPU trie (frontier/match caps too small for the live workload, or
    topics deeper/longer than the compiled budgets) — the broker still
    answers correctly, but at per-message CPU cost. This watch reads the
    flight-recorder counters of the broker serving path, computes
    the fallback rate over a sliding window, and (de)activates one alarm
    against the configured threshold.

    Windows with fewer than `min_rows` routed rows are ignored in BOTH
    directions: too little traffic neither raises nor clears the alarm
    (an idle broker must not flap an operator page)."""

    ALARM = "tpu_fallback_rate"

    def __init__(
        self,
        alarms: AlarmManager,
        metrics,
        threshold: float = 0.2,
        window: float = 10.0,
        min_rows: int = 64,
    ):
        self.alarms = alarms
        self.metrics = metrics
        self.threshold = threshold
        self.window = window
        self.min_rows = min_rows
        self._last_at: Optional[float] = None
        self._last_fallback = 0
        self._last_total = 0

    def _totals(self) -> tuple:
        m = self.metrics
        fallback = m.get("messages.routed.device_fallback")
        total = m.get("messages.routed.device") + fallback
        return fallback, total

    def check(self, now: Optional[float] = None) -> Optional[float]:
        """Evaluate once per elapsed window; returns the window's fallback
        rate when a window closed (None otherwise). Call from the
        housekeeping tick."""
        now = now if now is not None else time.time()
        if self._last_at is None:
            self._last_at = now
            self._last_fallback, self._last_total = self._totals()
            return None
        if now - self._last_at < self.window:
            return None
        fallback, total = self._totals()
        d_fb = fallback - self._last_fallback
        d_total = total - self._last_total
        self._last_at = now
        self._last_fallback, self._last_total = fallback, total
        if d_total < self.min_rows:
            return None
        rate = d_fb / d_total
        self.alarms.ensure(
            self.ALARM,
            rate > self.threshold,
            details={
                "rate": round(rate, 4),
                "threshold": self.threshold,
                "window_seconds": self.window,
                "fallback_rows": d_fb,
                "routed_rows": d_total,
            },
            message=(
                f"TPU route fallback rate {rate:.1%} over the last "
                f"{self.window:g}s exceeds {self.threshold:.1%}: the "
                "device fast path is degrading to the CPU trie"
            ),
        )
        return rate


class SloViolationWatch:
    """Level-triggered alarm on sustained SLO p99 target misses.

    The adaptive-batching controller (broker/slo.py) closes one
    evaluation window per `slo.eval.interval` and counts a violation
    when the observed enqueue->settle p99 missed the configured target.
    One miss is the controller's job to absorb (widen the window, walk
    the ladder); this watch pages only when the MISSES THEMSELVES are
    sustained — the violation *rate* over its sliding window stays at or
    above `threshold` — meaning the ladder ran out of rungs and the
    broker is serving outside its latency contract.

    Windows with fewer than `min_windows` controller evaluations are
    ignored in BOTH directions (an idle broker, or one with the
    controller off, must not flap an operator page) — the
    FallbackRateWatch min-traffic convention."""

    ALARM = "slo_p99_violation"

    def __init__(
        self,
        alarms: AlarmManager,
        metrics,
        threshold: float = 0.5,
        window: float = 10.0,
        min_windows: int = 4,
    ):
        self.alarms = alarms
        self.metrics = metrics
        self.threshold = threshold
        self.window = window
        self.min_windows = max(1, int(min_windows))
        self._last_at: Optional[float] = None
        self._last_viol = 0
        self._last_evals = 0

    def check(self, now: Optional[float] = None) -> Optional[float]:
        """Evaluate once per elapsed window; returns the window's
        violation rate when a window closed (None otherwise). Call from
        the housekeeping tick."""
        now = now if now is not None else time.time()
        m = self.metrics
        if self._last_at is None:
            self._last_at = now
            self._last_viol = m.get("slo.violations")
            self._last_evals = m.get("slo.eval.windows")
            return None
        if now - self._last_at < self.window:
            return None
        viol = m.get("slo.violations")
        evals = m.get("slo.eval.windows")
        d_viol = viol - self._last_viol
        d_evals = evals - self._last_evals
        self._last_at = now
        self._last_viol, self._last_evals = viol, evals
        if d_evals < self.min_windows:
            return None
        rate = d_viol / d_evals
        self.alarms.ensure(
            self.ALARM,
            rate >= self.threshold,
            details={
                "violation_rate": round(rate, 4),
                "threshold": self.threshold,
                "window_seconds": self.window,
                "violations": d_viol,
                "eval_windows": d_evals,
                "observed_p99_ms": m.gauge("slo.p99.observed_ms"),
                "target_p99_ms": m.gauge("slo.p99.target_ms"),
                "ladder_rung": m.gauge("slo.ladder.rung"),
            },
            message=(
                f"ingest p99 missed the "
                f"{m.gauge('slo.p99.target_ms'):g}ms SLO target in "
                f"{rate:.0%} of controller windows over the last "
                f"{self.window:g}s: the adaptive-batching ladder is "
                "saturated (sustained overload or a degraded fast path)"
            ),
        )
        return rate


class RetraceStormWatch:
    """Level-triggered alarm on steady-state jit compile activity.

    Boot compiles are normal (warmup, first table growth). A compile rate
    that STAYS nonzero after warmup means some batch property keeps
    leaking into a shape or static jit position — every "new" batch
    recompiles the serving program, each compile costing seconds to tens
    of seconds of device stall. The static RT checker predicts the common
    sources; this watch observes the live symptom from the
    `device.compile.count` counter (fed by `DeviceWatch.poll`).

    Semantics: windows ending inside the warmup period only advance the
    cursor. After warmup, `sustain` CONSECUTIVE windows each seeing
    `threshold`+ compiles activate the alarm; any compile-free window
    clears it (level-triggered, like FallbackRateWatch).
    """

    ALARM = "tpu_retrace_storm"

    def __init__(
        self,
        alarms: AlarmManager,
        metrics,
        threshold: int = 1,
        window: float = 10.0,
        warmup: float = 60.0,
        sustain: int = 2,
    ):
        self.alarms = alarms
        self.metrics = metrics
        self.threshold = max(1, int(threshold))
        self.window = window
        self.warmup = warmup
        self.sustain = max(1, int(sustain))
        self.started_at = time.time()
        self._last_at: Optional[float] = None
        self._last_count = 0
        self._hot_windows = 0

    def check(self, now: Optional[float] = None) -> Optional[int]:
        """Evaluate once per elapsed window; returns the closed window's
        compile count (None when no window closed)."""
        now = now if now is not None else time.time()
        if self._last_at is None:
            self._last_at = now
            self._last_count = self.metrics.get("device.compile.count")
            return None
        if now - self._last_at < self.window:
            return None
        count = self.metrics.get("device.compile.count")
        d = count - self._last_count
        self._last_at = now
        self._last_count = count
        if now < self.started_at + self.warmup:
            return d  # boot compiles: observe, never alarm
        self._hot_windows = self._hot_windows + 1 if d >= self.threshold else 0
        self.alarms.ensure(
            self.ALARM,
            self._hot_windows >= self.sustain,
            details={
                "compiles_last_window": d,
                "threshold": self.threshold,
                "window_seconds": self.window,
                "consecutive_hot_windows": self._hot_windows,
                "compile_cache_size": self.metrics.gauge(
                    "device.compile.cache_size"
                ),
            },
            message=(
                f"jit compile rate nonzero for {self._hot_windows} "
                f"consecutive {self.window:g}s windows in steady state: "
                "a batch property is leaking into a jit shape/static "
                "position (retrace storm) — each recompile stalls the "
                "serving path"
            ),
        )
        return d
