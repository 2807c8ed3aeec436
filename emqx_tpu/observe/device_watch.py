"""Device runtime telemetry: compile/retrace watch + HBM & transfer gauges.

The static RT checker (tools/analysis, PR 4) PREDICTS retrace hazards;
this module OBSERVES them on the live broker. Three signals, all polled
from the housekeeping tick (`DeviceWatch.poll`):

- **compiles vs cache hits**: every `@device_contract`-registered jit
  entry point (shape_route_step, the mesh step builders)
  exposes its jit cache size; the summed size is the
  `device.compile.cache_size` gauge and its growth is a compile. A
  process-wide `jax.monitoring` duration listener additionally captures
  every backend compile's wall seconds (`device.compile.seconds`) and —
  where the monitoring API exists — drives the `device.compile.count`
  counter, catching compiles of programs the registry does not know
  about. Steady-state serving should show a FLAT cache size and zero
  compile-count growth. The listener runs on the compiling thread, so a
  compile also counts by the profiler section open there:
  `device.compile.in_launch.count` (route-step programs) and
  `device.compile.in_readback.count` (the per-`B` `dynamic_slice`
  programs of `_readback`). Sustained growth is a retrace storm (a dynamic
  value leaking into a shape/static position — exactly what RT001/RT002
  flag statically) and trips `RetraceStormWatch`
  (emqx_tpu/observe/alarm.py).

- **HBM live bytes** (`device.hbm.bytes` gauge): the accelerator
  allocator's `bytes_in_use` when the backend reports memory stats
  (TPU/GPU), else the summed nbytes of live jax arrays (CPU fallback —
  tracks the same table-growth signal, without allocator overheads).
  `device.hbm.peak.bytes` is the allocator's `peak_bytes_in_use` where
  it has one, else the running maximum of the live bytes.

- **transfer accounting** (`device.transfer.bytes` counter): cumulative
  device->host readback bytes, incremented at the readback site
  (DeviceRouter._readback) next to the per-batch
  `dispatch.readback.bytes` histogram. The counter's RATE is the
  sustained link bandwidth the broker consumes.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Tuple

from emqx_tpu.observe.profiler import current_section

# -- process-global compile-event accumulator -------------------------------
# jax.monitoring listeners cannot be unregistered per-instance, so ONE
# module-level listener feeds monotonic totals; each DeviceWatch keeps its
# own cursor (multiple in-process brokers — cluster tests — each see their
# own deltas).
_mon_lock = threading.Lock()
_mon_compiles = 0  # guarded-by: _mon_lock
_mon_seconds = 0.0  # guarded-by: _mon_lock
# compiles by the profiler section open on the compiling thread, or by
# that thread's name where none is open
_mon_by_section: Dict[str, int] = {}  # guarded-by: _mon_lock
_mon_registered = False

# the once-per-backend-compile event in jax's monitoring stream; the
# jaxpr_trace / mlir_module events fire alongside it and would overcount
_COMPILE_EVENT = "backend_compile"


def _on_event(event: str, duration: float, **_kw) -> None:
    global _mon_compiles, _mon_seconds
    if _COMPILE_EVENT not in event:
        return
    where = current_section() or "thread:" + threading.current_thread().name
    with _mon_lock:
        _mon_compiles += 1
        _mon_seconds += duration
        _mon_by_section[where] = _mon_by_section.get(where, 0) + 1


def _install_listener() -> bool:
    global _mon_registered
    if _mon_registered:
        return True
    try:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_event)
    except Exception:
        return False
    _mon_registered = True
    return True


def _mon_totals() -> tuple:
    with _mon_lock:
        return (
            _mon_compiles, _mon_seconds,
            _mon_by_section.get("launch", 0),
            _mon_by_section.get("readback", 0),
        )


def compiles_by_section() -> Dict[str, int]:
    """Process totals of backend compiles by the section open on the
    compiling thread (`thread:<name>` where none is): where steady-state
    compiles come from (`GET /api/v5/profile`)."""
    with _mon_lock:
        return dict(_mon_by_section)


def hbm_bytes() -> Tuple[int, Optional[int]]:
    """(live, peak) device memory: allocator stats when the backend
    exposes them (TPU/GPU `memory_stats()["bytes_in_use"]` and
    `["peak_bytes_in_use"]`), else summed nbytes of live arrays (CPU —
    same growth signal, no allocator overhead) and no peak."""
    import jax

    total, peak = 0, 0
    saw_stats = saw_peak = False
    try:
        for d in jax.local_devices():
            stats = d.memory_stats()
            if stats and "bytes_in_use" in stats:
                total += int(stats["bytes_in_use"])
                saw_stats = True
                if "peak_bytes_in_use" in stats:
                    peak += int(stats["peak_bytes_in_use"])
                    saw_peak = True
    except Exception:
        saw_stats = False
    if saw_stats:
        return total, (peak if saw_peak else None)
    try:
        return int(sum(a.nbytes for a in jax.live_arrays())), None
    except Exception:
        return 0, None


class DeviceWatch:
    """Polls the device runtime signals into the metrics registry.

    `registry`: name -> DeviceContract (default: the process REGISTRY
    from emqx_tpu.ops.contract). jit-kind entries contribute their
    `_cache_size()`; builder-kind entries are covered by
    `parallel.mesh.jit_cache_size` (the built mesh programs register
    themselves there).
    """

    def __init__(self, metrics, registry: Optional[Dict] = None):
        self.metrics = metrics
        self._registry = registry
        self._monitoring = _install_listener()
        self._last_cache: Optional[int] = None
        self._mon_cursor = _mon_totals()
        self._hbm_max = 0

    def _contracts(self) -> Dict:
        if self._registry is not None:
            return self._registry
        from emqx_tpu.ops.contract import REGISTRY

        return REGISTRY

    def cache_size(self) -> int:
        """Summed jit-cache entries across every registered kernel plus
        the built mesh step programs."""
        n = 0
        for contract in self._contracts().values():
            fn = getattr(contract, "fn", contract)
            cs = getattr(fn, "_cache_size", None)
            if cs is None:
                continue
            try:
                n += int(cs())
            except Exception:
                continue
        try:
            from emqx_tpu.parallel.mesh import jit_cache_size

            n += jit_cache_size()
        except Exception:
            pass
        return n

    def poll(self, now: Optional[float] = None) -> Dict[str, float]:
        """One telemetry tick; call from housekeeping. Returns the sampled
        values (handy for tests and the REST summary)."""
        m = self.metrics
        cs = self.cache_size()
        kernel_compiles = (
            max(0, cs - self._last_cache)
            if self._last_cache is not None
            else 0
        )
        self._last_cache = cs
        m.gauge_set("device.compile.cache_size", cs)
        mon = _mon_totals()
        d_compiles = mon[0] - self._mon_cursor[0]
        d_seconds = mon[1] - self._mon_cursor[1]
        for i, series in (
            (2, "device.compile.in_launch.count"),
            (3, "device.compile.in_readback.count"),
        ):
            if mon[i] != self._mon_cursor[i]:
                m.inc(series, mon[i] - self._mon_cursor[i])
        self._mon_cursor = mon
        if not self._monitoring:
            # no monitoring API on this jax: the registry cache growth is
            # the compile signal (misses non-registered programs)
            d_compiles, d_seconds = kernel_compiles, 0.0
        if d_compiles:
            m.inc("device.compile.count", d_compiles)
            if d_seconds > 0:
                # the listener holds window totals, not per-compile
                # samples: record the window mean per compile
                m.observe_many(
                    "device.compile.seconds",
                    [d_seconds / d_compiles] * d_compiles,
                )
        hbm, peak = hbm_bytes()
        m.gauge_set("device.hbm.bytes", hbm)
        self._hbm_max = max(self._hbm_max, hbm)
        m.gauge_set(
            "device.hbm.peak.bytes",
            peak if peak is not None else self._hbm_max,
        )
        return {
            "compile_cache_size": cs,
            "compiles": d_compiles,
            "compile_seconds": d_seconds,
            "kernel_compiles": kernel_compiles,
            "hbm_bytes": hbm,
            "hbm_peak_bytes": m.gauge("device.hbm.peak.bytes"),
        }

    def summary(self) -> Dict[str, float]:
        """Current totals for the REST surface (no side effects)."""
        m = self.metrics
        return {
            "compile_count": m.get("device.compile.count"),
            "compile_cache_size": m.gauge("device.compile.cache_size"),
            "hbm_bytes": m.gauge("device.hbm.bytes"),
            "transfer_bytes": m.get("device.transfer.bytes"),
        }
