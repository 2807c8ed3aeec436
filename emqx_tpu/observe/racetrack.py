"""Runtime race harness: an Eraser-style lockset detector with
happens-before edges, over *registered* shared objects.

The CX checker (tools/analysis) proves cross-context discipline
statically; this module catches what static analysis cannot see —
container mutations, discipline that holds the wrong lock, annotations
that lie at runtime. It is the dynamic half of the PR 8 concurrency rig,
armed in the `race`-marked test suite, never in production steady
state.

Model (Eraser refined with vector clocks, FastTrack-lite):

- each thread carries a vector clock and a lockset (the tracked locks it
  currently holds);
- every probed access is labeled (thread, clock snapshot, lockset, trimmed
  stack);
- two accesses to the same field RACE when they come from different
  threads, at least one is a write, no happens-before edge orders them,
  and their locksets are disjoint. Both conditions must fail: a pure
  lockset detector false-positives on handoff patterns (loop builds, pool
  consumes), a pure HB detector misses races the schedule didn't happen
  to interleave — together they catch the discipline violation whenever
  either side witnesses it;
- happens-before edges come from the three sync idioms the broker uses:
  **executor submit -> task run** and **task completion -> Future.result**
  (both patched into `ThreadPoolExecutor.submit`/`Future.result` while
  armed — `loop.run_in_executor` rides the same pair, its result crossing
  back via `Future.result` on the loop thread), and **lock release ->
  acquire** (tracked locks publish the releaser's clock to the next
  acquirer).

Instrumentation is registration-based, the `faults.py` shape: production
classes carry no probes. `watch(obj)` registers a shared object (the
Metrics registry, DeviceRouter's prepare cache, DegradeController
breakers, RetainedStormFeed, route_sync tables); `arm()` swaps each
watched instance onto a generated subclass whose `__setattr__`/
`__getattribute__` probe the tracked fields, and wraps the instance's
locks so locksets and release->acquire edges are observed. `disarm()`
restores the original classes and locks — a disarmed tracker costs the
production pipeline literally nothing, and the explicit `probe()` hook
(for state the attribute probes cannot see, e.g. a dict entry) costs one
attribute check, exactly like a disarmed fault site.

Every candidate race is a `RaceReport` carrying the field, BOTH stack
traces, and both locksets; reports count into the `race.reports` series
and every probed access into `racetrack.events`. Known-benign fields are
waived by `waive("Class.field")` glob patterns.
"""

from __future__ import annotations

import fnmatch
import itertools
import sys
import threading
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

_LOCK_TYPES = (type(threading.Lock()), type(threading.RLock()))

_STACK_DEPTH = 6


def _stack() -> Tuple[str, ...]:
    """Trimmed caller stack, racetrack's own frames dropped.

    Hand-walked with sys._getframe instead of traceback.extract_stack:
    the latter pulls source lines through linecache, which is orders of
    magnitude too slow for a probe that fires on every watched access
    of a hot object (a chaos soak probes the Metrics registry millions
    of times)."""
    out = []
    f = sys._getframe(1)
    hops = 0
    while f is not None and hops < 40 and len(out) < _STACK_DEPTH:
        code = f.f_code
        if not code.co_filename.endswith("racetrack.py"):
            out.append(
                f"{code.co_filename}:{f.f_lineno} in {code.co_name}"
            )
        f = f.f_back
        hops += 1
    out.reverse()  # outermost first, the access site last
    return tuple(out)


def _iter_attrs(obj):
    """(name, value) pairs across __dict__ AND __slots__ instances."""
    seen = set()
    d = getattr(obj, "__dict__", None)
    if d:
        for k, v in list(d.items()):
            seen.add(k)
            yield k, v
    for klass in type(obj).__mro__:
        for s in getattr(klass, "__slots__", ()) or ():
            if s.startswith("__") or s in seen:
                continue
            seen.add(s)
            try:
                yield s, getattr(obj, s)
            except AttributeError:
                continue


@dataclass(frozen=True)
class Access:
    label: str  # "Class.field"
    thread: str
    tid: int
    write: bool
    locks: Tuple[str, ...]
    clock: Tuple[Tuple[int, int], ...]  # frozen vector-clock snapshot
    stack: Tuple[str, ...]

    def clock_of(self, tid: int) -> int:
        for t, e in self.clock:
            if t == tid:
                return e
        return 0


@dataclass(frozen=True)
class RaceReport:
    field: str
    prior: Access
    current: Access

    def render(self) -> str:
        def side(tag: str, a: Access) -> str:
            op = "WRITE" if a.write else "READ"
            locks = ", ".join(a.locks) or "<none>"
            stack = "\n      ".join(a.stack) or "<no stack>"
            return (
                f"  {tag}: {op} on thread {a.thread!r} "
                f"holding [{locks}]\n      {stack}"
            )

        return (
            f"race on {self.field}:\n"
            f"{side('prior', self.prior)}\n{side('current', self.current)}"
        )


class _FieldState:
    __slots__ = ("last_write", "reads")

    def __init__(self):
        self.last_write: Optional[Access] = None
        self.reads: Dict[int, Access] = {}


# logical thread ids, never reused: threading.get_ident() recycles the
# ids of dead threads, which would alias a fresh thread's clock onto a
# dead one's accesses and silently order unrelated work
_next_tid = itertools.count(1)


class _ThreadState:
    __slots__ = ("tid", "vc", "held", "busy")

    def __init__(self):
        self.tid = next(_next_tid)
        self.vc: Dict[int, int] = {self.tid: 1}
        self.held: List[str] = []
        self.busy = False  # re-entrancy guard (metrics calls inside probes)


class TrackedLock:
    """Wraps a real lock: lockset bookkeeping + release->acquire HB."""

    def __init__(self, inner, label: str, tracker: "RaceTracker"):
        self._inner = inner
        self._label = label
        self._tracker = tracker
        self._clock: Dict[int, int] = {}

    def acquire(self, *args, **kwargs) -> bool:
        got = self._inner.acquire(*args, **kwargs)
        if got:
            self._tracker._lock_acquired(self)
        return got

    def release(self) -> None:
        self._tracker._lock_released(self)
        self._inner.release()

    def locked(self) -> bool:
        return self._inner.locked()

    def __enter__(self) -> "TrackedLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()


class RaceTracker:
    def __init__(self, metrics=None):
        self.metrics = metrics
        self._armed = False
        self._ilock = threading.Lock()
        self._tls = threading.local()
        self._fields: Dict[Tuple[int, str], _FieldState] = {}
        # id(obj) -> (obj, display name, fields, orig class or None,
        #             {attr: original lock})
        self._watched: Dict[int, list] = {}
        self._class_cache: Dict[Tuple[type, frozenset], type] = {}
        self._waived: List[str] = []
        self._report_keys: Set[Tuple] = set()
        self.reports: List[RaceReport] = []
        # metric deltas accumulate HERE and flush at disarm: the probe
        # often fires while the watched object's own lock is held (a
        # Metrics instance inside `inc`), so calling metrics.inc inline
        # would re-acquire that very lock and self-deadlock
        self._events = 0
        self._flushed_events = 0
        self._flushed_reports = 0
        self._orig_submit = None
        self._orig_result = None

    # -- public surface -----------------------------------------------------
    @property
    def armed(self) -> bool:
        return self._armed

    def waive(self, pattern: str) -> None:
        """Suppress reports for fields matching the glob (e.g.
        ``"Metrics.started_at"``, ``"*._rand_seq"``)."""
        self._waived.append(pattern)

    def waived(self, label: str) -> bool:
        return any(fnmatch.fnmatch(label, p) for p in self._waived)

    def watch(
        self,
        obj,
        fields: Optional[Sequence[str]] = None,
        name: Optional[str] = None,
        locks: bool = True,
    ):
        """Register a shared object. Instrumentation happens at arm():
        a watched-but-disarmed object is untouched. `fields` defaults to
        every data attribute in the instance dict; `locks` wraps the
        instance's Lock/RLock attributes for lockset + HB tracking."""
        if id(obj) in self._watched:
            return obj  # already registered (possibly instrumented)
        name = name or type(obj).__name__
        if fields is None:
            fields = [
                k
                for k, v in _iter_attrs(obj)
                if not k.startswith("__")
                and not callable(v)
                and not isinstance(v, (_LOCK_TYPES + (TrackedLock,)))
            ]
        entry = [obj, name, tuple(fields), None, {}, locks]
        self._watched[id(obj)] = entry
        if self._armed:
            self._instrument(entry)
        return obj

    def arm(self, metrics=None) -> None:
        """Instrument every watched object and patch the executor seams.
        Re-arming is a no-op."""
        if self._armed:
            return
        if metrics is not None:
            self.metrics = metrics
        self._armed = True
        for entry in self._watched.values():
            self._instrument(entry)
        self._patch_executors()

    def disarm(self) -> None:
        """Restore classes, locks, and the executor seams. Reports and
        waivers survive so a soak can disarm before reading them."""
        if not self._armed:
            return
        self._armed = False
        for entry in self._watched.values():
            self._deinstrument(entry)
        self._unpatch_executors()
        self.flush_metrics()

    def flush_metrics(self) -> None:
        """Push accumulated racetrack.events / race.reports deltas into
        the metric registry. Runs at disarm (no probes can be in flight
        holding a watched lock) or whenever a soak wants a live read."""
        if self.metrics is None:
            return
        with self._ilock:
            ev = self._events - self._flushed_events
            rp = len(self.reports) - self._flushed_reports
            self._flushed_events += ev
            self._flushed_reports += rp
        if ev:
            self.metrics.inc("racetrack.events", ev)
        if rp:
            self.metrics.inc("race.reports", rp)

    def reset(self) -> None:
        """Drop accumulated state (watched set stays registered)."""
        with self._ilock:
            self._fields.clear()
            self._report_keys.clear()
            self.reports = []

    def unwaived_reports(self) -> List[RaceReport]:
        return [r for r in self.reports if not self.waived(r.field)]

    # -- manual probe (the faults.hit analog) -------------------------------
    def probe(self, owner, field: str, write: bool = True,
              name: Optional[str] = None) -> None:
        """Hand-instrumented access for state the attribute probes cannot
        see (a dict entry, a list slot). One attribute check when
        disarmed."""
        if not self._armed:
            return
        label = f"{name or type(owner).__name__}.{field}"
        self._on_access(id(owner), label, write)

    # -- instrumentation ----------------------------------------------------
    def _instrument(self, entry) -> None:
        obj, name, fields, orig_cls, orig_locks, wrap_locks = entry
        if orig_cls is not None:
            return  # already instrumented
        if wrap_locks:
            for attr, val in _iter_attrs(obj):
                if isinstance(val, _LOCK_TYPES):
                    proxy = TrackedLock(val, f"{name}.{attr}", self)
                    object.__setattr__(obj, attr, proxy)
                    orig_locks[attr] = val
        cls = type(obj)
        entry[3] = cls
        obj.__class__ = self._tracked_class(cls, frozenset(fields), name)

    def _deinstrument(self, entry) -> None:
        obj, _name, _fields, orig_cls, orig_locks, _wrap = entry
        if orig_cls is None:
            return
        obj.__class__ = orig_cls
        entry[3] = None
        for attr, real in orig_locks.items():
            object.__setattr__(obj, attr, real)
        orig_locks.clear()

    def _tracked_class(self, cls: type, fields: frozenset,
                       name: str) -> type:
        key = (cls, fields)
        got = self._class_cache.get(key)
        if got is not None:
            return got
        tracker = self
        orig_setattr = cls.__setattr__
        orig_getattribute = cls.__getattribute__

        def __setattr__(self, attr, value):
            if attr in fields and tracker._armed:
                tracker._on_access(id(self), f"{name}.{attr}", True)
            orig_setattr(self, attr, value)

        def __getattribute__(self, attr):
            if attr in fields and tracker._armed:
                tracker._on_access(id(self), f"{name}.{attr}", False)
            return orig_getattribute(self, attr)

        sub = type(
            f"Racetracked{cls.__name__}",
            (cls,),
            {
                "__slots__": (),
                "__setattr__": __setattr__,
                "__getattribute__": __getattribute__,
            },
        )
        self._class_cache[key] = sub
        return sub

    # -- executor seams (HB edges) ------------------------------------------
    def _patch_executors(self) -> None:
        tracker = self
        self._orig_submit = orig_submit = ThreadPoolExecutor.submit
        self._orig_result = orig_result = Future.result

        def submit(pool, fn, *args, **kwargs):
            if not tracker._armed:
                return orig_submit(pool, fn, *args, **kwargs)
            snap = tracker._publish()  # submit -> run edge
            cell = {}

            def run(*a, **kw):
                tracker._merge(snap)
                try:
                    return fn(*a, **kw)
                finally:
                    cell["clock"] = tracker._publish()  # done -> result

            fut = orig_submit(pool, run, *args, **kwargs)
            try:
                fut._racetrack_cell = cell
            except Exception:  # noqa: BLE001 — slotted Future subclass
                pass
            return fut

        def result(fut, timeout=None):
            value = orig_result(fut, timeout)
            if tracker._armed:
                cell = getattr(fut, "_racetrack_cell", None)
                if cell is not None:
                    clk = cell.get("clock")
                    if clk:
                        tracker._merge(clk)
            return value

        ThreadPoolExecutor.submit = submit
        Future.result = result

    def _unpatch_executors(self) -> None:
        if self._orig_submit is not None:
            ThreadPoolExecutor.submit = self._orig_submit
            self._orig_submit = None
        if self._orig_result is not None:
            Future.result = self._orig_result
            self._orig_result = None

    # -- vector-clock plumbing ----------------------------------------------
    def _state(self) -> _ThreadState:
        st = getattr(self._tls, "st", None)
        if st is None:
            st = _ThreadState()
            self._tls.st = st
        return st

    def _publish(self) -> Dict[int, int]:
        """Snapshot this thread's clock, then tick it: later accesses by
        this thread are NOT covered by the snapshot."""
        st = self._state()
        snap = dict(st.vc)
        st.vc[st.tid] = st.vc.get(st.tid, 0) + 1
        return snap

    def _merge(self, clock: Dict[int, int]) -> None:
        st = self._state()
        for t, e in clock.items():
            if st.vc.get(t, 0) < e:
                st.vc[t] = e

    def _lock_acquired(self, lock: TrackedLock) -> None:
        st = self._state()
        st.held.append(lock._label)
        self._merge(lock._clock)

    def _lock_released(self, lock: TrackedLock) -> None:
        st = self._state()
        lock._clock = self._publish()
        try:
            st.held.remove(lock._label)
        except ValueError:
            pass

    # -- the detector -------------------------------------------------------
    @staticmethod
    def _ordered(prior: Access, vc: Dict[int, int]) -> bool:
        """Did the current thread observe the prior access (HB)?"""
        return vc.get(prior.tid, 0) >= prior.clock_of(prior.tid)

    def _on_access(self, obj_id: int, label: str, write: bool) -> None:
        st = self._state()
        if st.busy:
            return  # re-entrant probe (metrics call inside the tracker)
        st.busy = True
        try:
            acc = Access(
                label=label,
                thread=threading.current_thread().name,
                tid=st.tid,
                write=write,
                locks=tuple(st.held),
                clock=tuple(sorted(st.vc.items())),
                stack=_stack(),
            )
            with self._ilock:
                self._events += 1
                fs = self._fields.setdefault(
                    (obj_id, label), _FieldState()
                )
                if write:
                    if fs.last_write is not None:
                        self._check(fs.last_write, acc, st.vc)
                    for r in fs.reads.values():
                        self._check(r, acc, st.vc)
                    fs.last_write = acc
                    fs.reads = {}
                else:
                    if fs.last_write is not None:
                        self._check(fs.last_write, acc, st.vc)
                    fs.reads[st.tid] = acc
        finally:
            st.busy = False

    def _check(self, prior: Access, acc: Access,
               vc: Dict[int, int]) -> int:  # holds-lock: _ilock
        if prior.tid == acc.tid:
            return 0
        if not (prior.write or acc.write):
            return 0
        if self._ordered(prior, vc):
            return 0
        if set(prior.locks) & set(acc.locks):
            return 0  # a common lock serializes them
        key = (
            acc.label,
            prior.write,
            acc.write,
            prior.stack[-1] if prior.stack else "",
            acc.stack[-1] if acc.stack else "",
        )
        if key in self._report_keys:
            return 0
        self._report_keys.add(key)
        self.reports.append(
            RaceReport(field=acc.label, prior=prior, current=acc)
        )
        return 1


# the process-wide tracker the race suite and chaos_soak arm; production
# code never touches it (registration-based instrumentation only)
default_tracker = RaceTracker()


def probe(owner, field: str, write: bool = True) -> None:
    """Module-level shorthand mirroring `faults.hit`: one attribute
    check when the default tracker is disarmed."""
    default_tracker.probe(owner, field, write)
