"""Device profiling plane: launch waterfalls, per-kernel attribution,
on-demand trace capture, and static cost analysis.

Three instruments, one module (docs/observability.md "Profiling &
provenance"):

1. **Stage waterfall** — every device batch decomposes into six stages
   (`profile.stage.*.seconds` histograms, observed from the hot path):

       prepare        table snapshot + upload (Broker.adispatch_begin)
       queue_wait     enqueue -> launch wait per message (BatchIngest)
       launch         host-side batch encode + kernel enqueue
                      (DeviceRouter._route_prepared up to readback)
       device_execute kernel completion wait (block_until_ready at the
                      readback boundary)
       readback       the coalesced device_get + host decode
       host_dispatch  settle-time fan-out (Broker device results)

   The stages are always-on flight-recorder histograms in the same
   spirit as `router.device.seconds` — a handful of perf_counter reads
   per *batch*, never per message. Per-kernel attribution rides the
   same path: each launch's wall time and readback bytes are observed
   into `device.kernel.<name>.seconds/.bytes`, keyed by the
   `@device_contract` registry names, so all 14 kernels are
   attributable without any kernel-side code.

2. **Trace capture** — an on-demand `jax.profiler` trace, armed via
   `POST /api/v5/profile` with a bounded duration and on-disk file
   budget. Disarmed is the structural zero of faults.py/racetrack: no
   hook exists on the hot path at all; arming only starts the global
   jax trace and housekeeping's 1 Hz tick enforces the deadline/budget.
   `capture is None` IS the disarmed state (asserted racetrack-style in
   tests/test_profiler.py).

3. **Static cost analysis** — `Compiled.cost_analysis()` (FLOPs, bytes
   accessed) harvested per contract kernel per config-matrix row by
   reusing the device-contract audit's harness recipes, rendered as a
   roofline-style estimate (arithmetic intensity vs the detected
   TPU's peak). Off a TPU there are no peaks: rows keep FLOPs, bytes
   and arithmetic intensity (counts), `attainable_flops`/`bound` stay
   None and the block is tagged `proxy: true`.
"""

from __future__ import annotations

import os
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from emqx_tpu.observe import provenance

# the waterfall stage set, in pipeline order (series:
# `profile.stage.<stage>.seconds`, declared in broker/metrics.py)
STAGES: Tuple[str, ...] = (
    "prepare",
    "queue_wait",
    "launch",
    "device_execute",
    "readback",
    "host_dispatch",
)

# roofline peaks by device_kind substring: (peak FLOP/s, peak HBM B/s).
# Public datasheet numbers (dense bf16/fp32-class); the ridge point
# ai = flops/bytes they imply is what the harvest renders against.
DEVICE_PEAKS: Tuple[Tuple[str, Tuple[float, float]], ...] = (
    ("v5p", (459e12, 2765e9)),
    ("v5 lite", (197e12, 819e9)),
    ("v5e", (197e12, 819e9)),
    ("v4", (275e12, 1228e9)),
    ("v3", (123e12, 900e9)),
    ("v2", (45e12, 700e9)),
)


def device_peaks() -> Optional[Dict[str, Any]]:
    """(peak_flops, peak_bytes_per_s) for the detected TPU; None on any
    other platform (a CPU has no roofline to render here). A TPU kind
    missing from DEVICE_PEAKS is an error, not a default."""
    fp = provenance.fingerprint()
    if fp["proxy"]:
        return None
    kind = str(fp["device_kind"]).lower()
    for sub, peaks in DEVICE_PEAKS:
        if sub in kind:
            return {
                "peak_flops": peaks[0],
                "peak_bytes_per_s": peaks[1],
                "device_kind": fp["device_kind"],
            }
    raise LookupError(
        f"no roofline peaks for TPU device_kind {fp['device_kind']!r}: "
        "add its datasheet numbers to DEVICE_PEAKS"
    )


def record_kernel_launch(
    metrics, kernels: Sequence[str], seconds: float, bytes_: int = 0
) -> None:
    """Attribute one launch's wall time + readback bytes to the contract
    kernels that rode it. A fused launch lists every registry name in
    the program (e.g. shape_route_step + compact_fanout_slots +
    semantic_match_step), so per-kernel series answer "what does this
    kernel cost when it is in the program" — launch-level attribution,
    not an intra-program split (cost_harvest gives the static split)."""
    if metrics is None:
        return
    for k in kernels:
        metrics.observe(f"device.kernel.{k}.seconds", seconds)
        if bytes_:
            metrics.observe(f"device.kernel.{k}.bytes", bytes_)


def kernel_summary(metrics) -> Dict[str, Dict]:
    """Per-kernel launch percentiles for every registry kernel a series
    exists for — the REST `profile.kernels` table."""
    from emqx_tpu.ops.contract import REGISTRY

    out: Dict[str, Dict] = {}
    for name in sorted(REGISTRY):
        h = metrics.histogram(f"device.kernel.{name}.seconds")
        if h is None or h.count == 0:
            continue
        hb = metrics.histogram(f"device.kernel.{name}.bytes")
        out[name] = {
            "launches": h.count,
            "mean_ms": (h.sum / h.count) * 1e3,
            "p50_ms": h.p50 * 1e3,
            "p99_ms": h.p99 * 1e3,
            "mean_readback_bytes": (
                hb.sum / hb.count if hb is not None and hb.count else None
            ),
        }
    return out


def waterfall(metrics) -> Dict[str, Optional[Dict]]:
    """The per-stage latency breakdown (seconds): one entry per STAGE
    with count/mean/p50/p95/p99, None where nothing observed yet."""
    out: Dict[str, Optional[Dict]] = {}
    for stage in STAGES:
        h = metrics.histogram(f"profile.stage.{stage}.seconds")
        if h is None or h.count == 0:
            out[stage] = None
            continue
        out[stage] = {
            "count": h.count,
            "mean": h.sum / h.count,
            "p50": h.p50,
            "p95": h.p95,
            "p99": h.p99,
        }
    return out


class Profiler:
    """On-demand jax trace capture + cached cost harvest.

    Disarmed state is `self.capture is None` — the hot path never
    consults this object (stage/kernel series observe straight into the
    metrics registry), so the disarmed overhead is structurally zero:
    there is no check to pay, let alone a branch. Arming starts the
    process-global `jax.profiler` trace into a fresh per-capture
    directory; the housekeeping tick (app.py, 1 Hz) enforces the
    duration bound and the on-disk file budget.
    """

    def __init__(
        self,
        metrics=None,
        trace_dir: str = "profile_traces",
        max_seconds: float = 30.0,
        max_bytes: int = 64 << 20,
        history: int = 16,
    ) -> None:
        self.metrics = metrics
        self.trace_dir = trace_dir
        self.max_seconds = float(max_seconds)
        self.max_bytes = int(max_bytes)
        self.capture: Optional[Dict[str, Any]] = None  # guarded-by: _lock
        self._history: List[Dict[str, Any]] = []  # guarded-by: _lock
        self._history_cap = history
        self._seq = 0
        self._cost: Optional[Dict[str, Any]] = None  # guarded-by: _lock
        self._lock = threading.Lock()

    @property
    def armed(self) -> bool:
        # racy read by design (REST status probe): arm/disarm mutate
        # under _lock; a stale one-word read here is harmless
        return self.capture is not None  # lint: disable=LK001

    # -- trace capture (REST-armed) ---------------------------------------

    def arm(
        self,
        duration_s: Optional[float] = None,
        max_bytes: Optional[int] = None,
    ) -> Dict[str, Any]:
        """Start a bounded jax.profiler trace. Raises RuntimeError when a
        capture is already armed (one at a time: the jax trace is
        process-global) or when the backend refuses to start one."""
        dur = float(duration_s) if duration_s else self.max_seconds
        dur = max(0.1, min(dur, self.max_seconds))
        budget = int(max_bytes) if max_bytes else self.max_bytes
        budget = max(1 << 16, min(budget, self.max_bytes))
        with self._lock:
            if self.capture is not None:
                raise RuntimeError("profile capture already armed")
            self._seq += 1
            cap_dir = os.path.join(
                self.trace_dir, f"capture_{self._seq:04d}"
            )
            os.makedirs(cap_dir, exist_ok=True)
            import jax

            jax.profiler.start_trace(cap_dir)
            self.capture = {
                "dir": cap_dir,
                "started_at": time.time(),
                "deadline": time.time() + dur,
                "duration_s": dur,
                "max_bytes": budget,
            }
            return dict(self.capture)

    def disarm(self, reason: str = "rest") -> Optional[Dict[str, Any]]:
        """Stop the armed capture, settle the file budget, record the
        history entry. No-op (returns None) when disarmed."""
        with self._lock:
            cap = self.capture
            if cap is None:
                return None
            self.capture = None
            import jax

            try:
                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001 — budget still settles
                cap["error"] = str(e)
            entry = self._settle_locked(cap, reason)
            self._history.append(entry)
            del self._history[: -self._history_cap]
        if self.metrics is not None:
            self.metrics.inc("profile.captures")
            self.metrics.observe(
                "profile.capture.seconds", entry["seconds"]
            )
            self.metrics.observe("profile.capture.bytes", entry["bytes"])
        return entry

    def _settle_locked(self, cap, reason) -> Dict[str, Any]:
        bytes_ = _tree_bytes(cap["dir"])
        over = bytes_ > cap["max_bytes"]
        if over:
            # budget enforcement is REAL: an over-budget capture is
            # deleted, not kept with a warning — the bound exists so a
            # long-armed trace can never fill the data disk
            shutil.rmtree(cap["dir"], ignore_errors=True)
        return {
            "dir": cap["dir"],
            "seconds": round(time.time() - cap["started_at"], 3),
            "bytes": bytes_,
            "max_bytes": cap["max_bytes"],
            "over_budget": over,
            "deleted": over,
            "reason": reason,
            "error": cap.get("error"),
        }

    def tick(self, now: Optional[float] = None) -> None:
        """Housekeeping hook (1 Hz): auto-disarm past the deadline, and
        cut a capture short the moment it exceeds its file budget."""
        # racy read by design: the 1 Hz tick may see a capture another
        # thread is disarming; disarm() re-checks under _lock
        cap = self.capture  # lint: disable=LK001
        if cap is None:
            return
        now = time.time() if now is None else now
        if now >= cap["deadline"]:
            self.disarm(reason="deadline")
        elif _tree_bytes(cap["dir"]) > cap["max_bytes"]:
            self.disarm(reason="budget")

    # -- static cost analysis ---------------------------------------------

    def cost_harvest(
        self,
        max_configs_per_kernel: Optional[int] = None,
        refresh: bool = False,
    ) -> Dict[str, Any]:
        """FLOPs / bytes-accessed per contract kernel per config-matrix
        row, via the device-contract audit's own harness recipes (so
        the harvested matrix IS the audited matrix). Compiles every
        kernel — seconds to minutes of work — so the result is cached;
        REST exposes the cached copy and recomputes only on demand."""
        with self._lock:
            if self._cost is not None and not refresh:
                return self._cost
        result = harvest_cost(max_configs_per_kernel)
        with self._lock:
            self._cost = result
        if self.metrics is not None:
            self.metrics.gauge_set(
                "profile.cost.kernels",
                len({r["kernel"] for r in result["rows"]}),
            )
        return result

    def cost_cached(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self._cost

    def snapshot(self) -> Dict[str, Any]:
        """REST-shaped state: armed capture, history, budgets."""
        with self._lock:
            cap = dict(self.capture) if self.capture is not None else None
            hist = list(self._history)
            cost = self._cost
        return {
            "armed": cap is not None,
            "capture": cap,
            "history": hist,
            "max_seconds": self.max_seconds,
            "max_bytes": self.max_bytes,
            "cost_harvested": cost is not None,
        }


def _tree_bytes(path: str) -> int:
    total = 0
    for root, _dirs, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(root, f))
            except OSError:
                pass
    return total


def harvest_cost(
    max_configs_per_kernel: Optional[int] = None,
) -> Dict[str, Any]:
    """Compile every registered contract kernel over (a prefix of) its
    audit config matrix and read `Compiled.cost_analysis()` back.

    Returns `{rows, skipped, peaks, proxy}`: one row per (kernel,
    config) with flops, bytes accessed, arithmetic intensity, and the
    roofline-attainable FLOP/s vs the detected device's peaks. Configs
    the audit itself would skip (e.g. a mesh row on too few devices)
    land in `skipped`, never as silently missing kernels."""
    import jax

    from emqx_tpu.ops.contract import REGISTRY
    # importing the kernel modules populates the registry (the audit's
    # own idiom)
    import emqx_tpu.models.router_model  # noqa: F401
    import emqx_tpu.ops.session_table  # noqa: F401
    import emqx_tpu.parallel.mesh  # noqa: F401

    skipped: List[str] = []

    from tools.analysis.device_contract import (
        _cfg_key,
        _harness,
        _SkipConfig,
    )

    peaks = device_peaks()
    rows: List[Dict[str, Any]] = []
    for name in sorted(REGISTRY):
        recipe = _harness(name)
        if recipe is None:
            skipped.append(f"{name}: no audit harness recipe")
            continue
        configs, build = recipe
        if max_configs_per_kernel:
            configs = configs[:max_configs_per_kernel]
        for cfg in configs:
            key = _cfg_key(cfg)
            try:
                fn, args = build(dict(cfg))
                compiled = jax.jit(fn).lower(*args).compile()
                ca = compiled.cost_analysis()
            except _SkipConfig as e:
                skipped.append(str(e))
                continue
            except Exception as e:  # noqa: BLE001 — backend-specific
                skipped.append(f"{name} {key}: cost analysis failed: {e}")
                continue
            rows.append(_cost_row(name, key, ca, peaks))
    return {
        "rows": rows,
        "skipped": skipped,
        "peaks": peaks,
        "proxy": peaks is None,
    }


def _cost_row(name: str, key: str, ca, peaks) -> Dict[str, Any]:
    """Normalize one cost_analysis() result (dict, or a per-program
    list of dicts on some jax versions) into a roofline row."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        ca = {}
    flops = float(ca.get("flops", 0.0) or 0.0)
    bytes_ = float(ca.get("bytes accessed", 0.0) or 0.0)
    ai = flops / bytes_ if bytes_ > 0 else None
    attainable = bound = None
    if ai is not None and peaks is not None:
        peak_f = peaks["peak_flops"]
        peak_b = peaks["peak_bytes_per_s"]
        attainable = min(peak_f, ai * peak_b)
        bound = "compute" if ai >= peak_f / peak_b else "memory"
    return {
        "kernel": name,
        "config": key,
        "flops": flops,
        "bytes_accessed": bytes_,
        "arithmetic_intensity": ai,
        "attainable_flops": attainable,
        "bound": bound,
    }


def roofline_summary(cost: Optional[Dict[str, Any]]) -> Optional[Dict]:
    """Condense a harvest result to the hotpath headline: per kernel,
    the heaviest config's arithmetic intensity and attainable FLOP/s
    against the detected device peaks. None until a harvest ran."""
    if not cost:
        return None
    best: Dict[str, Dict[str, Any]] = {}
    for r in cost["rows"]:
        cur = best.get(r["kernel"])
        if cur is None or r["flops"] > cur["flops"]:
            best[r["kernel"]] = r
    return {
        "peaks": cost["peaks"],
        "proxy": cost["proxy"],
        "kernels": {
            k: {
                "config": r["config"],
                "arithmetic_intensity": r["arithmetic_intensity"],
                "attainable_flops": r["attainable_flops"],
                "bound": r["bound"],
            }
            for k, r in sorted(best.items())
        },
    }


# the process-wide instance (faults.default_faults idiom): app.py points
# `.metrics` at the broker's registry and REST drives arm/disarm
default_profiler = Profiler()
