"""Device profiling plane: the owner thread's time budget, launch
waterfalls, on-demand trace capture, and static cost analysis.

Five instruments, one module (docs/observability.md "Profiling &
provenance"):

1. **Sections** — `with section("<name>", **ids):` (or `begin`/`end`)
   around each synchronous stretch of the serving path. One site, three
   outputs: always-on per-thread accumulators (total, self = total less
   children, entries; flushed as `profile.section.<name>.seconds` and
   `.self.seconds` sums/counts), a `jax.profiler.TraceAnnotation`
   `emqx:<name>` on the device trace's clock while a capture is armed,
   and the REST section table. Never around an `await`: on an asyncio
   thread a span that holds one times other tasks.

2. **The loop's budget** — `LoopBudget` times the owner loop's own
   `select()`: idle (`owner.loop.select.seconds`), busy
   (`owner.loop.run.seconds`, one observation per iteration), what no
   section names (`owner.loop.other.seconds`), stalls (run phases over
   0.5 s, `owner.loop.stall.seconds`, the last 32 kept) and GC pauses
   (`owner.gc.pause.seconds`, fed by SysMon's one gc hook). Over any
   interval select + sum of main-thread self + other = the loop's wall.

3. **Stage waterfall** — every device batch decomposes into six stages
   (`profile.stage.*.seconds` histograms, one observe per batch):

       prepare        table snapshot + upload (Broker.adispatch_begin)
       queue_wait     enqueue -> launch wait per message (BatchIngest)
       launch         host-side batch encode + kernel enqueue
                      (DeviceRouter._route_prepared up to readback)
       device_execute kernel completion wait (block_until_ready at the
                      readback boundary)
       readback       the coalesced device_get + host decode
       host_dispatch  settle-time fan-out (Broker device results)

   The five busy stretches are sections too (so they nest and carry an
   annotation); `queue_wait` is a wait and stays a per-message observe.

4. **Trace capture** — an on-demand `jax.profiler` trace, armed via
   `POST /api/v5/profile` with a bounded duration and on-disk file
   budget, python tracer off unless asked for. `capture is None` IS the
   disarmed state: a section then makes no annotation object at all.

5. **Static cost analysis** — `Compiled.cost_analysis()` (FLOPs, bytes
   accessed) harvested per contract kernel per config-matrix row by
   reusing the device-contract audit's harness recipes, rendered as a
   roofline-style estimate (arithmetic intensity vs the detected
   TPU's peak). Off a TPU there are no peaks: rows keep FLOPs, bytes
   and arithmetic intensity (counts), `attainable_flops`/`bound` stay
   None and the block is tagged `proxy: true`.
"""

from __future__ import annotations

import logging
import os
import selectors
import shutil
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from emqx_tpu.broker.metrics import SECTIONS
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


# -- sections -----------------------------------------------------------------
# The names are a contract (PERF.md section 3 lists each with the metric
# that reads it; `broker/metrics.py` keeps them beside their series).
STALL_SECONDS = 0.5  # a run phase of the loop longer than this is a stall
STALLS_KEPT = 32

log = logging.getLogger("emqx_tpu.profiler")
_now = time.perf_counter  # the sections' and the loop budget's one clock


class _ThreadAcc:
    """One thread's open sections and totals. Only its own thread writes;
    a flush only reads, so there is no lock."""

    __slots__ = ("stack", "acc", "flushed", "ids", "budget")

    def __init__(self) -> None:
        # open frames, innermost last: [name, annotation, child_s, t0]
        self.stack: List[list] = []
        self.acc: Dict[str, list] = {}  # name -> [total_s, self_s, entries]
        self.flushed: Dict[str, tuple] = {}  # the flush's cursor, same shape
        self.ids: Dict[str, Any] = {}  # ambient annotation ids (`batch_ids`)
        # the LoopBudget whose loop runs on this thread: it flushes these
        # sections itself, between two iterations, so that every series of
        # the loop covers the same whole iterations
        self.budget: Optional["LoopBudget"] = None


_tls = threading.local()
_accs: List[_ThreadAcc] = []  # guarded-by: _accs_lock
_accs_lock = threading.Lock()
# GC pauses as SysMon's gc hook reports them: [pause_s, passes, gen2_s,
# gen2_passes], and flush's cursor over the same
_gc = [0.0, 0, 0.0, 0]
_gc_flushed = [0.0, 0, 0.0, 0]


def _acc() -> _ThreadAcc:
    try:
        return _tls.acc
    except AttributeError:
        a = _tls.acc = _ThreadAcc()
        with _accs_lock:
            _accs.append(a)
        return a


def _annotate(name: str, ids: Dict[str, Any]):
    """An entered TraceAnnotation `<name>`; only called while a capture
    is armed."""
    import jax

    ann = jax.profiler.TraceAnnotation(name, **ids)
    ann.__enter__()
    return ann


def _close(ann) -> None:
    if ann is not None:
        ann.__exit__(None, None, None)


def begin(name: str, **ids) -> None:
    """Open section `name` on this thread. Pair with `end()` in a
    `finally`, with no `await` between the two."""
    a = _acc()
    ann = None
    if default_profiler.capture is not None:  # lint: disable=LK001
        ann = _annotate("emqx:" + name, {**a.ids, **ids})
    a.stack.append([name, ann, 0.0, _now()])


def end(n: int = 1) -> float:
    """Close this thread's innermost section, counting `n` entries (a
    read chunk is one section and `n` packets). Returns its seconds."""
    now = _now()
    a = _tls.acc
    name, ann, child, t0 = a.stack.pop()
    dur = now - t0
    tot = a.acc.get(name)
    if tot is None:
        a.acc[name] = [dur, dur - child, n]
    else:
        tot[0] += dur
        tot[1] += dur - child
        tot[2] += n
    if a.stack:
        a.stack[-1][2] += dur
    if ann is not None:
        ann.__exit__(None, None, None)
    return dur


class section:
    """`with section("host_dispatch", batch=seq, rows=n) as s:`; `s.n`
    may be set inside (entries counted), `s.seconds` reads after."""

    __slots__ = ("name", "ids", "n", "seconds")

    def __init__(self, name: str, **ids) -> None:
        self.name = name
        self.ids = ids
        self.n = 1
        self.seconds = 0.0

    def __enter__(self) -> "section":
        begin(self.name, **self.ids)
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = end(self.n)


def current_section() -> Optional[str]:
    """The innermost section open on the calling thread."""
    a = getattr(_tls, "acc", None)
    return a.stack[-1][0] if a is not None and a.stack else None


class batch_ids:
    """Ambient annotation ids for every section this thread opens inside
    the block: the executor's launch / device_execute / readback carry
    the batch's seq without threading it through the router's calls."""

    __slots__ = ("ids", "_prev")

    def __init__(self, **ids) -> None:
        self.ids = ids

    def __enter__(self) -> None:
        a = _acc()
        self._prev, a.ids = a.ids, self.ids

    def __exit__(self, *exc) -> None:
        _tls.acc.ids = self._prev


def ambient_ids() -> Dict[str, Any]:
    """This thread's ambient ids, to carry into another thread's
    `batch_ids` or a later section of the same batch."""
    a = getattr(_tls, "acc", None)
    return dict(a.ids) if a is not None else {}


def note_gc(seconds: float, generation: int) -> None:
    """One GC pass (SysMon._on_gc, the process's one gc hook). Plain adds:
    the hook runs at arbitrary allocation points."""
    _gc[0] += seconds
    _gc[1] += 1
    if generation >= 2:
        _gc[2] += seconds
        _gc[3] += 1


class LoopBudget:
    """The select / run split of one event loop's thread.

    `selector()` wraps the loop's selector so that every `select()` is
    timed: the time inside is idle (blocked for I/O), the time between
    two selects is one run phase (callbacks, tasks). A run phase over
    `STALL_SECONDS` is a stall: logged with the section that took most
    of it (or `other`) and the GC pause inside it, kept in `stalls`.

    The loop thread's sections reach the registry from here, between two
    iterations (`flush` only asks for it), so over any two flushes
    select + the sections' self time + other = the loop's wall.

    While a capture is armed the loop's two phases are annotations too,
    for the operator reading the trace (they are no sections and carry no
    `emqx:` prefix: they say the owner was saturated or waiting, not by
    what): a `select()` that may block is `owner:loop.select`, and the
    stretch between two of those, in which the loop always had a callback
    ready and only polled (`select(0)`), is one `owner:loop.busy`.
    """

    def __init__(self) -> None:
        self.select_s = 0.0
        self.run_s = 0.0
        self.iterations = 0
        self.stall_s = 0.0
        self.stall_count = 0
        self.stalls: deque = deque(maxlen=STALLS_KEPT)
        self.longest_run_s = 0.0  # since `take_longest_run`
        self.flush_to = None  # the registry a `flush` asked us to fill
        self._t_in = 0.0
        self._t_out: Optional[float] = None  # when the last select returned
        self._acc: Optional[_ThreadAcc] = None  # the loop thread's sections
        self._mark: Dict[str, float] = {}  # self seconds at `_t_out`
        self._gc_mark = 0.0
        self._flushed = (0.0, 0.0, 0, 0.0, 0, 0.0)
        self._busy_ann = None  # the open owner:loop.busy, while armed
        self._select_ann = None

    def selector(self, inner=None) -> "TimedSelector":
        return TimedSelector(self, inner or selectors.DefaultSelector())

    def enter_select(self, blocking: bool = False) -> None:
        """`blocking`: the loop has no callback ready, so this select may
        wait (asyncio passes a zero timeout otherwise)."""
        if blocking:
            _close(self._busy_ann)
            self._busy_ann = None
            self._select_ann = self._annotate("owner:loop.select")
        self._t_in = t_in = _now()
        if self._t_out is None:
            return
        run = t_in - self._t_out
        self.run_s += run
        self.iterations += 1
        if run > self.longest_run_s:
            self.longest_run_s = run
        if run > STALL_SECONDS:
            self._stall(run)
        if self.flush_to is not None:
            metrics, self.flush_to = self.flush_to, None
            self.flush(metrics)

    def exit_select(self) -> None:
        t_out = _now()
        _close(self._select_ann)
        self._select_ann = None
        if self._busy_ann is None:
            self._busy_ann = self._annotate("owner:loop.busy")
        if self._t_out is None:
            # the first select: bind the loop's thread, and leave what its
            # sections did before the loop ran out of the budget
            a = self._acc = _acc()
            a.budget = self
            self._flushed = self._totals()
        else:
            self.select_s += t_out - self._t_in
        self._t_out = t_out
        self._mark = {k: v[1] for k, v in self._acc.acc.items()}
        self._gc_mark = _gc[0]

    def closed(self) -> None:
        """The loop is gone: `flush` reads its thread's sections again."""
        if self._acc is not None:
            self._acc.budget = None

    @staticmethod
    def _annotate(name: str):
        if default_profiler.capture is None:  # lint: disable=LK001
            return None
        return _annotate(name, {})

    def _stall(self, run: float) -> None:
        self.stall_s += run
        self.stall_count += 1
        mark = self._mark
        by = {k: v[1] - mark.get(k, 0.0) for k, v in self._acc.acc.items()}
        by["other"] = run - sum(by.values())
        top = max(by, key=by.get)
        entry = {
            "at": time.time(),
            "seconds": round(run, 4),
            "section": top,
            "section_seconds": round(by[top], 4),
            "gc_seconds": round(_gc[0] - self._gc_mark, 4),
        }
        self.stalls.append(entry)
        log.warning(
            "owner loop stalled %.3fs: %.3fs in %s, %.3fs of GC",
            run, by[top], top, entry["gc_seconds"],
        )

    # Profiler listener: `arm` and `disarm` run on the loop's thread (the
    # REST handlers, the housekeeping tick), inside a run phase
    def capture_started(self) -> None:
        if self._acc is getattr(_tls, "acc", None) and self._busy_ann is None:
            self._busy_ann = self._annotate("owner:loop.busy")

    def capture_stopping(self) -> None:
        if self._acc is getattr(_tls, "acc", None):
            _close(self._busy_ann)
            self._busy_ann = None

    def take_longest_run(self) -> float:
        """The longest run phase since the last call (SysMon's
        `long_schedule` alarm reads this, once a housekeeping tick)."""
        v, self.longest_run_s = self.longest_run_s, 0.0
        return v

    def _totals(self) -> tuple:
        a = self._acc
        return (
            self.select_s, self.run_s, self.iterations, self.stall_s,
            self.stall_count,
            sum(v[1] for v in a.acc.values()) if a is not None else 0.0,
        )

    def flush(self, metrics) -> None:
        """The loop thread's sections and the owner.loop.* series, as of
        the last whole iteration: called between two iterations (by
        `enter_select`, after a `flush` asked), or once the loop is gone."""
        cur, was = self._totals(), self._flushed
        n = cur[2] - was[2]
        if not n:
            return
        self._flushed = cur
        _flush_acc(metrics, self._acc)
        run = cur[1] - was[1]
        metrics.add("owner.loop.select.seconds", cur[0] - was[0], n)
        metrics.add("owner.loop.run.seconds", run, n)
        # what no section names: the run time less the sections' self time
        metrics.add("owner.loop.other.seconds", run - (cur[5] - was[5]), n)
        if cur[4] != was[4]:
            metrics.add(
                "owner.loop.stall.seconds", cur[3] - was[3], cur[4] - was[4]
            )


class TimedSelector(selectors.BaseSelector):
    """A selector that reports its own `select()` to a LoopBudget; passed
    as `asyncio.SelectorEventLoop(selector=...)`."""

    def __init__(self, budget: LoopBudget, inner) -> None:
        self._budget = budget
        self._inner = inner

    def select(self, timeout=None):
        b = self._budget
        b.enter_select(blocking=timeout != 0)
        try:
            return self._inner.select(timeout)
        finally:
            b.exit_select()

    def register(self, fileobj, events, data=None):
        return self._inner.register(fileobj, events, data)

    def unregister(self, fileobj):
        return self._inner.unregister(fileobj)

    def modify(self, fileobj, events, data=None):
        return self._inner.modify(fileobj, events, data)

    def get_key(self, fileobj):
        return self._inner.get_key(fileobj)

    def get_map(self):
        return self._inner.get_map()

    def close(self) -> None:
        self._inner.close()
        self._budget.closed()


def loop_factory():
    """`asyncio.run(main(), loop_factory=loop_factory)`: an event loop
    whose selector feeds `default_profiler.budget`."""
    import asyncio

    prof = default_profiler
    prof.listeners = [
        x for x in prof.listeners if not isinstance(x, LoopBudget)
    ]
    budget = prof.budget = LoopBudget()
    prof.listeners.append(budget)
    return asyncio.SelectorEventLoop(budget.selector())


_flush_lock = threading.Lock()


def _flush_acc(metrics, a: _ThreadAcc) -> None:
    for name, (tot, slf, n) in list(a.acc.items()):
        f = a.flushed.get(name, (0.0, 0.0, 0))
        if n == f[2]:
            continue
        a.flushed[name] = (tot, slf, n)
        metrics.add(f"profile.section.{name}.seconds", tot - f[0], n - f[2])
        metrics.add(
            f"profile.section.{name}.self.seconds", slf - f[1], n - f[2]
        )


def flush(metrics) -> None:
    """Move what accumulated since the last flush into the registry, as
    sums and counts (`Histogram.add`). Called at scrape, by
    `GET /api/v5/profile` and by the 1 Hz housekeeping tick. A thread
    whose loop has a LoopBudget is only asked: its sections and the loop's
    series follow when the running iteration ends."""
    with _flush_lock, _accs_lock:
        for a in _accs:
            if a.budget is not None:
                a.budget.flush_to = metrics
            else:
                _flush_acc(metrics, a)
        g = list(_gc)
        if g[1] != _gc_flushed[1]:
            metrics.add(
                "owner.gc.pause.seconds",
                g[0] - _gc_flushed[0], g[1] - _gc_flushed[1],
            )
            if g[3] != _gc_flushed[3]:
                metrics.add(
                    "owner.gc.gen2.seconds",
                    g[2] - _gc_flushed[2], g[3] - _gc_flushed[3],
                )
            _gc_flushed[:] = g


def section_table(
    metrics, budget: Optional[LoopBudget] = None
) -> Dict[str, Any]:
    """The REST section table, as of the last flush (and asks for the
    next): per section its entries, total and self seconds and its self
    time's share of the loop's busy time; the loop's select / run / other
    / stall seconds; the last stalls."""
    flush(metrics)

    def hsum(name: str) -> Tuple[float, int]:
        h = metrics.histogram(name)
        return (h.sum, h.count) if h is not None else (0.0, 0)

    run_s, iterations = hsum("owner.loop.run.seconds")
    rows: Dict[str, Dict] = {}
    with _accs_lock:
        names = sorted({n for a in _accs for n in a.acc} | set(SECTIONS))
    for name in names:
        tot, n = hsum(f"profile.section.{name}.seconds")
        if not n:
            continue
        slf, _ = hsum(f"profile.section.{name}.self.seconds")
        rows[name] = {
            "entries": n,
            "total_s": tot,
            "self_s": slf,
            "busy_share": slf / run_s if run_s > 0 else None,
        }
    return {
        "sections": rows,
        "loop": {
            "iterations": iterations,
            "select_s": hsum("owner.loop.select.seconds")[0],
            "run_s": run_s,
            "other_s": hsum("owner.loop.other.seconds")[0],
            "stall_s": hsum("owner.loop.stall.seconds")[0],
            "gc_pause_s": hsum("owner.gc.pause.seconds")[0],
            "gc_gen2_s": hsum("owner.gc.gen2.seconds")[0],
        },
        "stalls": list(budget.stalls) if budget is not None else [],
    }


class Profiler:
    """On-demand jax trace capture + cached cost harvest.

    Disarmed state is `self.capture is None`: a section reads that one
    attribute and makes no annotation object. Arming starts the
    process-global `jax.profiler` trace (python tracer off unless asked
    for) into a fresh per-capture directory; the housekeeping tick
    (app.py, 1 Hz) enforces the duration bound and the on-disk file
    budget. `listeners` hear `capture_started()` right after a capture
    starts and `capture_stopping()` right before it stops, on the arming
    and disarming thread: the LoopBudget opens and closes its
    `owner:loop.busy` there, so that the trace's first and last stretches
    carry it too.
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
        # the owner loop's select/run split (`loop_factory` sets it; None
        # where the loop was made some other way, e.g. in tests)
        self.budget: Optional[LoopBudget] = None
        self.listeners: List[Any] = []

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
        python_tracer: bool = False,
    ) -> Dict[str, Any]:
        """Start a bounded jax.profiler trace. Raises RuntimeError when a
        capture is already armed (one at a time: the jax trace is
        process-global) or when the backend refuses to start one. The
        python tracer (a frame event per call: a traced stretch at about
        half rate, 57-206 MB per capture in PR 23's runs) is off unless
        `python_tracer`; the `emqx:<section>` annotations name the host's
        time either way."""
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

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 1 if python_tracer else 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(cap_dir, profiler_options=opts)
            self.capture = {
                "dir": cap_dir,
                "started_at": time.time(),
                "deadline": time.time() + dur,
                "duration_s": dur,
                "max_bytes": budget,
                "python_tracer": bool(python_tracer),
            }
            info = dict(self.capture)
        for listener in self.listeners:
            listener.capture_started()
        return info

    def disarm(self, reason: str = "rest") -> Optional[Dict[str, Any]]:
        """Stop the armed capture, settle the file budget, record the
        history entry. No-op (returns None) when disarmed."""
        if self.capture is not None:  # lint: disable=LK001
            for listener in self.listeners:
                listener.capture_stopping()
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
