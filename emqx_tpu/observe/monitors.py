"""Runtime health monitors -> alarms.

The reference watches its runtime with three processes (SURVEY.md §5.2/§5.3):
- emqx_sys_mon: BEAM scheduler anomalies (long_gc, long_schedule, large_heap,
  busy_port) -> alarms (apps/emqx/src/emqx_sys_mon.erl:63-76)
- emqx_os_mon: OS cpu/mem watermarks (emqx_os_mon.erl)
- emqx_vm_mon: process-count watermarks (emqx_vm_mon.erl)

The asyncio/CPython equivalents of the runtime anomalies:
- a long run phase of the event loop (a blocked loop is the moral twin
  of long_schedule; measured by the loop's own LoopBudget,
  observe/profiler.py)
- GC pause spikes (gc callbacks time each collection ~ long_gc; the same
  hook feeds the owner.gc.* series)
- task count (asyncio tasks are the process analog) and fd count.

All are polled by `check(now)` from the app's housekeeping tick; no threads.
"""

from __future__ import annotations

import asyncio
import gc
import os
import time
from typing import Optional

from emqx_tpu.observe import profiler as _prof
from emqx_tpu.observe.alarm import AlarmManager


class SysMon:
    """Event-loop lag + GC pause detector (emqx_sys_mon analog).

    Both alarms are transient (level-triggered): they raise when an anomaly
    occurs and clear after `clear_after` seconds without a recurrence.
    The gc callback only RECORDS the pause — it must not run alarm/publish
    code, since gc can fire re-entrantly at any allocation point; `check`
    (the housekeeping tick) surfaces the recorded anomaly safely.
    """

    def __init__(
        self,
        alarms: AlarmManager,
        long_schedule_ms: float = 240.0,
        long_gc_ms: float = 100.0,
        clear_after: float = 60.0,
    ):
        self.alarms = alarms
        self.long_schedule_ms = long_schedule_ms
        self.long_gc_ms = long_gc_ms
        self.clear_after = clear_after
        self._gc_start: Optional[float] = None
        self.max_gc_ms = 0.0
        self._pending_gc_ms: Optional[float] = None
        self._last_long_gc: float = 0.0
        self._last_long_schedule: float = 0.0
        gc.callbacks.append(self._on_gc)

    def close(self) -> None:
        try:
            gc.callbacks.remove(self._on_gc)
        except ValueError:
            pass

    def _on_gc(self, phase: str, info: dict) -> None:
        # record-only: no allocation-heavy work inside the gc hook
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            seconds = time.perf_counter() - self._gc_start
            self._gc_start = None
            _prof.note_gc(seconds, info.get("generation", 0))
            ms = seconds * 1000.0
            if ms > self.max_gc_ms:
                self.max_gc_ms = ms
            if ms > self.long_gc_ms and (
                self._pending_gc_ms is None or ms > self._pending_gc_ms
            ):
                self._pending_gc_ms = ms

    def _raise_transient(self, name: str, details: dict, message: str) -> None:
        # refresh an already-active alarm so repeats update the details
        if self.alarms.is_active(name):
            self.alarms.deactivate(name)
        self.alarms.activate(name, details, message)

    def check(self, now: float, budget=None) -> None:
        """Called each housekeeping tick. `budget` is the loop's
        LoopBudget: its longest run phase since the last tick is how long
        the loop kept everything else waiting (None where the loop does
        not time its select: no long_schedule alarm)."""
        if self._pending_gc_ms is not None:
            ms = self._pending_gc_ms
            self._pending_gc_ms = None
            self._last_long_gc = now
            self._raise_transient(
                "long_gc",
                {"duration_ms": round(ms, 2)},
                f"gc pause {ms:.1f}ms > {self.long_gc_ms}ms",
            )
        if budget is not None:
            lag_ms = budget.take_longest_run() * 1000.0
            if lag_ms > self.long_schedule_ms:
                self._last_long_schedule = now
                self._raise_transient(
                    "long_schedule",
                    {"lag_ms": round(lag_ms, 2)},
                    f"event loop ran {lag_ms:.0f}ms without polling I/O",
                )
        # auto-clear after a quiet period
        if (
            self.alarms.is_active("long_gc")
            and now - self._last_long_gc > self.clear_after
        ):
            self.alarms.deactivate("long_gc")
        if (
            self.alarms.is_active("long_schedule")
            and now - self._last_long_schedule > self.clear_after
        ):
            self.alarms.deactivate("long_schedule")


def _meminfo() -> dict:
    out = {}
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                k, _, rest = line.partition(":")
                out[k.strip()] = int(rest.strip().split()[0]) * 1024
    except OSError:
        pass
    return out


def _cpu_times() -> Optional[tuple]:
    try:
        with open("/proc/stat") as f:
            parts = f.readline().split()
        vals = [int(x) for x in parts[1:]]
        idle = vals[3] + (vals[4] if len(vals) > 4 else 0)
        return sum(vals), idle
    except OSError:
        return None


class OsMon:
    """CPU/memory watermark alarms from /proc (emqx_os_mon analog)."""

    def __init__(
        self,
        alarms: AlarmManager,
        cpu_high_watermark: float = 0.80,
        cpu_low_watermark: float = 0.60,
        mem_high_watermark: float = 0.70,
    ):
        self.alarms = alarms
        self.cpu_high = cpu_high_watermark
        self.cpu_low = cpu_low_watermark
        self.mem_high = mem_high_watermark
        self._prev_cpu = _cpu_times()
        self.cpu_usage = 0.0
        self.mem_usage = 0.0

    def check(self, now: float) -> None:
        cur = _cpu_times()
        if cur and self._prev_cpu:
            dt = cur[0] - self._prev_cpu[0]
            didle = cur[1] - self._prev_cpu[1]
            if dt > 0:
                self.cpu_usage = max(0.0, 1.0 - didle / dt)
                # hysteresis: raise above high, clear below low
                if self.cpu_usage > self.cpu_high:
                    self.alarms.activate(
                        "high_cpu_usage",
                        {"usage": round(self.cpu_usage, 3)},
                        f"cpu usage {self.cpu_usage:.0%} > {self.cpu_high:.0%}",
                    )
                elif self.cpu_usage < self.cpu_low:
                    self.alarms.deactivate("high_cpu_usage")
        self._prev_cpu = cur

        mi = _meminfo()
        total = mi.get("MemTotal")
        avail = mi.get("MemAvailable")
        if total and avail is not None and total > 0:
            self.mem_usage = 1.0 - avail / total
            self.alarms.ensure(
                "high_system_memory_usage",
                self.mem_usage > self.mem_high,
                {"usage": round(self.mem_usage, 3)},
                f"memory usage {self.mem_usage:.0%} > {self.mem_high:.0%}",
            )


class VmMon:
    """Task/fd watermark alarms (emqx_vm_mon's process-count analog)."""

    def __init__(
        self,
        alarms: AlarmManager,
        task_high_watermark: float = 0.80,
        task_low_watermark: float = 0.60,
        max_tasks: int = 1_000_000,
    ):
        self.alarms = alarms
        self.task_high = task_high_watermark
        self.task_low = task_low_watermark
        self.max_tasks = max_tasks
        self.task_count = 0
        self.fd_count = 0

    def check(self, now: float) -> None:
        try:
            self.task_count = len(asyncio.all_tasks())
        except RuntimeError:
            self.task_count = 0
        try:
            self.fd_count = len(os.listdir("/proc/self/fd"))
        except OSError:
            pass
        usage = self.task_count / self.max_tasks if self.max_tasks else 0.0
        if usage > self.task_high:
            self.alarms.activate(
                "too_many_processes",
                {"usage": round(usage, 3), "tasks": self.task_count},
                f"task count {self.task_count} > {self.task_high:.0%} of limit",
            )
        elif usage < self.task_low:
            self.alarms.deactivate("too_many_processes")
