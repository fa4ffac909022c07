"""The unified trace/metrics substrate (spans, counters, events).

Every timing and diagnostic signal in this repo used to be ad-hoc:
bench.py and a dozen probe/profile scripts each reinvented timers, log formats and checksum provenance. This module is
the ONE substrate they all report through:

- **spans** — ``with obs.span("weave.sort", width=n):`` — record wall
  time (epoch-anchored, perf_counter-measured), pid/tid, nesting
  (parent id + depth) and the reporting process's platform tag;
- a **counter/gauge registry** — program-cache hits/misses, lane-cache
  hits, wave fallbacks, checksum-gate outcomes, certification
  revocations — aggregated in-process and snapshotted into the event
  stream by ``flush()`` (and automatically at exit);
- a bounded in-process **event ring buffer** (newest events win) with
  JSONL export, plus a streaming **sink**: when an output path is
  configured every event is appended to it the moment it is recorded,
  one JSON line per event, via a single O_APPEND write. That makes the
  sink safe for several processes appending to one sidecar file:
  concurrent appends interleave at line granularity;
- a **Chrome-trace/Perfetto exporter** (``cause_tpu.obs.perfetto``,
  ``python -m cause_tpu.obs``) so any bench or soak run opens in a
  trace viewer.

Dependency-light on purpose (stdlib only): bench.py's parent process
and the watcher must be able to import it without jax.

Off by default: with ``CAUSE_TPU_OBS`` unset (or ``0``), ``span()``
returns a shared no-op context manager, ``counter()``/``gauge()``
return a shared no-op instrument, nothing is recorded and no file is
opened. Enable with ``CAUSE_TPU_OBS=1``; stream with
``CAUSE_TPU_OBS_OUT=<path>``; bound the ring with
``CAUSE_TPU_OBS_RING`` (default 65536 events).

Spans have a second sink, independent of that switch: whenever a JAX
profiler session is recording (``jax.profiler.start_trace``), every
``span()`` also opens a profiler ``TraceMe`` of the same name, with its
integer and string attributes as the event's stats. The spans then sit
on the device trace's own clock, obs on or off. With obs off and no
profiler recording, a span costs one ``TraceMe.is_enabled()`` check on
top of the no-op; the profiler is looked up only once JAX has loaded
it, so this module still imports no JAX.
"""

from __future__ import annotations

import atexit
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Dict, Optional

__all__ = [
    "configure",
    "enabled",
    "span",
    "event",
    "counter",
    "gauge",
    "counters_snapshot",
    "events",
    "flush",
    "export_jsonl",
    "set_platform",
    "reset",
    "subscribe",
    "unsubscribe",
]

_TRUTHY = ("1", "true", "yes")
_DEFAULT_RING = 65536


class _NullSpan:
    """The disabled-mode span: one shared instance, every method a
    no-op. Deliberately tiny — the disabled ``span()`` call is on trace
    -time and wave hot paths and must stay sub-microsecond."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


class _NullInstrument:
    """Disabled-mode counter/gauge: shared, inert."""

    __slots__ = ()

    def inc(self, n=1):
        return self

    def set(self, value):
        return self

    @property
    def value(self):
        return 0


_NULL_SPAN = _NullSpan()
_NULL_INSTRUMENT = _NullInstrument()

# the profiler's TraceMe class, found once JAX has loaded it
_TRACEME = None


def _profiler_traceme():
    """The profiler's ``TraceMe`` class while a profiler session
    records, else None. Never imports JAX: before JAX is loaded no
    profiler can be recording."""
    global _TRACEME
    tm = _TRACEME
    if tm is None:
        mod = sys.modules.get("jaxlib._profiler")
        if mod is None:
            return None
        tm = _TRACEME = mod.TraceMe
    return tm if tm.is_enabled() else None


def _trace_stats(attrs: dict) -> dict:
    """The attributes a TraceMe carries as stats: integers and strings.
    The profiler encodes them into the event name as ``#k=v,...#``, so
    a string loses the characters that would end its value."""
    out = {}
    for k, v in attrs.items():
        if isinstance(v, int):
            out[k] = int(v)
        elif isinstance(v, str):
            out[k] = v.replace("#", "_").replace(",", "_").replace("=", "_")
    return out


class _TraceSpan:
    """A span while obs is off and a profiler session records: the
    profiler's TraceMe alone."""

    __slots__ = ("tm",)

    def __init__(self, traceme, name: str, attrs: dict):
        self.tm = traceme(name, **_trace_stats(attrs))

    def __enter__(self):
        self.tm.__enter__()
        return self

    def __exit__(self, *exc):
        self.tm.__exit__(*exc)
        return False

    def set(self, **attrs):
        self.tm.set_metadata(**_trace_stats(attrs))
        return self


# default bound of one in-process subscriber queue (obs.live's
# monitor drains on its own cadence; a stalled reader must cost the
# writer one deque append and nothing else)
_SUBSCRIBER_QUEUE = 8192


class Subscription:
    """One bounded in-process subscriber on the sink: every record the
    substrate emits is appended to this queue the moment it lands in
    the ring (newest win when the reader falls behind — ``dropped``
    counts the loss honestly). Readers drain with :meth:`drain` on
    their own cadence; the writer never blocks and never runs reader
    code (no callback re-entrancy under the state lock). Created via
    :func:`subscribe`, torn down via :func:`unsubscribe`."""

    __slots__ = ("queue", "dropped", "lock", "closed")

    def __init__(self, maxlen: int):
        self.queue = deque(maxlen=max(1, int(maxlen)))
        self.dropped = 0
        self.lock = threading.Lock()
        self.closed = False

    def push(self, obj: dict) -> None:
        with self.lock:
            if self.closed:
                return
            if len(self.queue) == self.queue.maxlen:
                self.dropped += 1
            self.queue.append(obj)

    def drain(self) -> list:
        """All queued records, oldest first (and empties the queue)."""
        with self.lock:
            out = list(self.queue)
            self.queue.clear()
        return out


class _State:
    """One process-wide obs state (enabled flag, registry, ring,
    sink). Re-created by configure(reset=True) for tests."""

    __slots__ = (
        "enabled", "out", "ring", "counters", "gauges", "lock",
        "tls", "fd", "platform", "ids", "atexit_armed", "subscribers",
    )

    def __init__(self, enabled_: bool, out: str, ring_size: int):
        self.enabled = enabled_
        self.out = out
        self.ring = deque(maxlen=max(1, int(ring_size)))
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.lock = threading.Lock()
        self.tls = threading.local()
        self.fd = None            # lazily opened O_APPEND sink
        self.platform = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
        self.ids = itertools.count(1)
        self.atexit_armed = False
        # in-process live subscribers (obs.live monitors); empty
        # tuple in the common case so record() pays one attribute
        # read, nothing else
        self.subscribers: tuple = ()

    # ---------------------------------------------------------- sink
    def write_line(self, obj: dict) -> None:
        """Append one JSON line to the sink (if any). A single
        os.write of the whole line on an O_APPEND fd: parent and
        abandoned-child writers interleave at line granularity, and an
        IO failure never takes the instrumented program down."""
        if not self.out:
            return
        try:
            if self.fd is None:
                d = os.path.dirname(os.path.abspath(self.out))
                if d:
                    os.makedirs(d, exist_ok=True)
                self.fd = os.open(
                    self.out, os.O_APPEND | os.O_CREAT | os.O_WRONLY,
                    0o644,
                )
            os.write(self.fd,
                     (json.dumps(obj, default=str) + "\n").encode())
        except OSError:
            self.out = ""  # sink is best-effort; stop retrying

    def record(self, obj: dict) -> None:
        self.ring.append(obj)
        self.write_line(obj)
        for sub in self.subscribers:
            sub.push(obj)


_STATE: Optional[_State] = None
_STATE_LOCK = threading.Lock()


def _resolve_state() -> _State:
    global _STATE
    st = _STATE
    if st is None:
        with _STATE_LOCK:
            st = _STATE
            if st is None:
                on = os.environ.get("CAUSE_TPU_OBS", "").strip().lower()
                out = os.environ.get("CAUSE_TPU_OBS_OUT", "").strip()
                try:
                    ring = int(os.environ.get("CAUSE_TPU_OBS_RING",
                                              "") or _DEFAULT_RING)
                except ValueError:
                    ring = _DEFAULT_RING
                st = _State(on in _TRUTHY, out, ring)
                _STATE = st
                if st.enabled:
                    _arm_atexit(st)
    return st


def _arm_atexit(st: _State) -> None:
    if not st.atexit_armed:
        st.atexit_armed = True
        atexit.register(_atexit_flush)


def _atexit_flush() -> None:
    # the final counter snapshot: an abandoned bench child exits
    # naturally (SystemExit between phases), so its counters land in
    # the sidecar even though nobody waits for it
    st = _STATE
    if st is not None and st.enabled and (st.counters or st.gauges):
        flush()


def configure(enabled: Optional[bool] = None,
              out: Optional[str] = None,
              ring_size: Optional[int] = None,
              reset: bool = False) -> None:
    """Reconfigure obs at runtime (tests, --obs-out script flags).
    ``reset=True`` drops recorded events/counters and re-reads the
    environment for anything not explicitly given."""
    global _STATE
    with _STATE_LOCK:
        cur = _STATE
        if reset or cur is None:
            if cur is not None:
                if cur.fd is not None:
                    try:
                        os.close(cur.fd)
                    except OSError:
                        pass
                # a reset drops ALL obs state, subscribers included —
                # mark them closed so a live attachment polling a
                # dead queue can SEE it died (sub.closed) instead of
                # silently draining nothing forever
                with cur.lock:
                    for s in cur.subscribers:
                        s.closed = True
                    cur.subscribers = ()
            _STATE = None
    if reset:
        # one reset reaches every tracer layered on this core: the
        # lag registries and the xtrace span/op registries would
        # otherwise leak state (and trace bindings) across test cases
        # and bench fleets. Late imports — both modules import core
        # at module level, so the top of this file cannot import them
        from . import lag as _lag
        from . import xtrace as _xtrace

        _lag.reset()
        _xtrace.reset()
    with _STATE_LOCK:
        if reset and enabled is None and out is None \
                and ring_size is None:
            return
    st = _resolve_state()
    with st.lock:
        if enabled is not None:
            st.enabled = bool(enabled)
        if out is not None:
            if st.fd is not None and out != st.out:
                try:
                    os.close(st.fd)
                except OSError:
                    pass
                st.fd = None
            st.out = out
        if ring_size is not None and ring_size != st.ring.maxlen:
            st.ring = deque(st.ring, maxlen=max(1, int(ring_size)))
        if st.enabled:
            _arm_atexit(st)


def reset() -> None:
    """Drop all obs state and re-read the environment on next use."""
    configure(reset=True)


def enabled() -> bool:
    return _resolve_state().enabled


def set_platform(platform: str) -> None:
    """Tag subsequent events with the confirmed backend platform
    (callers that initialized jax know it; obs itself never imports
    jax, so it cannot ask)."""
    st = _resolve_state()
    st.platform = str(platform)


def subscribe(maxlen: int = _SUBSCRIBER_QUEUE) -> Optional[Subscription]:
    """Attach a bounded in-process subscriber to the sink: every
    subsequently recorded event/span/gauge/counter snapshot is queued
    for the subscriber to :meth:`Subscription.drain` on its own
    cadence (the ``obs.live`` in-process feed). Returns None when obs
    is disabled — the obs-off contract is zero state, so a disabled
    process keeps no subscriber registry at all. An ``obs.reset()`` /
    ``configure(reset=True)`` detaches every subscriber and marks it
    ``closed`` — the holder must re-subscribe against the new state
    (``live.LiveAttachment.closed`` surfaces this)."""
    st = _resolve_state()
    if not st.enabled:
        return None
    sub = Subscription(maxlen)
    with st.lock:
        st.subscribers = st.subscribers + (sub,)
    return sub


def unsubscribe(sub: Optional[Subscription]) -> None:
    """Detach a subscriber (idempotent; None is a no-op so callers can
    pass the obs-off :func:`subscribe` result straight back)."""
    if sub is None:
        return
    sub.closed = True
    st = _STATE
    if st is None:
        return
    with st.lock:
        st.subscribers = tuple(s for s in st.subscribers if s is not sub)


class _Span:
    """An enabled span: context manager recording one "span" event on
    close. ``set(**attrs)`` adds attributes mid-flight."""

    __slots__ = ("st", "name", "attrs", "sid", "parent", "depth",
                 "t0", "ts_us", "tm")

    def __init__(self, st: _State, name: str, attrs: dict, traceme=None):
        self.st = st
        self.name = name
        self.attrs = attrs
        self.sid = next(st.ids)
        self.parent = 0
        self.depth = 0
        self.t0 = 0.0
        self.ts_us = 0
        self.tm = (traceme(name, **_trace_stats(attrs))
                   if traceme is not None else None)

    def set(self, **attrs):
        self.attrs.update(attrs)
        if self.tm is not None:
            self.tm.set_metadata(**_trace_stats(attrs))
        return self

    def __enter__(self):
        st = self.st
        stack = getattr(st.tls, "stack", None)
        if stack is None:
            stack = st.tls.stack = []
        if stack:
            self.parent = stack[-1]
        self.depth = len(stack)
        stack.append(self.sid)
        if self.tm is not None:
            self.tm.__enter__()
        self.ts_us = time.time_ns() // 1000
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur_us = int((time.perf_counter() - self.t0) * 1e6)
        if self.tm is not None:
            self.tm.__exit__(exc_type, exc, tb)
        st = self.st
        stack = getattr(st.tls, "stack", None)
        if stack and stack[-1] == self.sid:
            stack.pop()
        rec = {
            "ev": "span",
            "name": self.name,
            "ts_us": self.ts_us,
            "dur_us": dur_us,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "id": self.sid,
            "parent": self.parent,
            "depth": self.depth,
            "platform": st.platform,
        }
        if self.attrs:
            rec["attrs"] = dict(self.attrs)
        if exc_type is not None:
            rec["error"] = exc_type.__name__
        with st.lock:
            st.record(rec)
        return False


class _Counter:
    __slots__ = ("st", "name")

    def __init__(self, st: _State, name: str):
        self.st = st
        self.name = name

    def inc(self, n=1):
        st = self.st
        with st.lock:
            st.counters[self.name] = st.counters.get(self.name, 0) + n
        return self

    @property
    def value(self):
        return self.st.counters.get(self.name, 0)


class _Gauge:
    __slots__ = ("st", "name")

    def __init__(self, st: _State, name: str):
        self.st = st
        self.name = name

    def set(self, value):
        """Record the new value in the registry AND as a timestamped
        ``gauge`` event — a gauge is a sampled time series (devprof
        memory curves), so each set must land in the stream, not just
        in the flush-time snapshot (which only has flush resolution)."""
        st = self.st
        rec = {
            "ev": "gauge",
            "name": self.name,
            "ts_us": time.time_ns() // 1000,
            "pid": os.getpid(),
            "platform": st.platform,
            "value": value,
        }
        with st.lock:
            st.gauges[self.name] = value
            st.record(rec)
        return self

    @property
    def value(self):
        return self.st.gauges.get(self.name, 0)


def span(name: str, **attrs):
    """A wall-time span: recorded to the ring and sink when obs is on,
    and to the profiler's trace whenever a profiler session records.
    With neither, the shared no-op."""
    st = _resolve_state()
    traceme = _profiler_traceme()
    if not st.enabled:
        if traceme is None:
            return _NULL_SPAN
        return _TraceSpan(traceme, name, attrs)
    return _Span(st, name, attrs, traceme)


def event(name: str, **fields) -> None:
    """An instant event (harvest ladder decisions, checksum-gate
    outcomes, overflow retries). ``fields`` must be JSON-serializable
    (non-serializable values are stringified)."""
    st = _resolve_state()
    if not st.enabled:
        return
    stack = getattr(st.tls, "stack", None)
    rec = {
        "ev": "event",
        "name": name,
        "ts_us": time.time_ns() // 1000,
        "pid": os.getpid(),
        "tid": threading.get_ident(),
        "parent": stack[-1] if stack else 0,
        "platform": st.platform,
    }
    if fields:
        rec["fields"] = fields
    with st.lock:
        st.record(rec)


def counter(name: str):
    """The named monotonic counter (disabled mode: shared no-op)."""
    st = _resolve_state()
    if not st.enabled:
        return _NULL_INSTRUMENT
    return _Counter(st, name)


def gauge(name: str):
    """The named last-value gauge (disabled mode: shared no-op)."""
    st = _resolve_state()
    if not st.enabled:
        return _NULL_INSTRUMENT
    return _Gauge(st, name)


def counters_snapshot() -> dict:
    """{"counters": {...}, "gauges": {...}} — current aggregate
    values (empty dicts when disabled)."""
    st = _resolve_state()
    with st.lock:
        return {"counters": dict(st.counters),
                "gauges": dict(st.gauges)}


def flush() -> None:
    """Snapshot the counter/gauge registry into the event stream (and
    the sink). Call at phase boundaries; also runs at exit."""
    st = _resolve_state()
    if not st.enabled:
        return
    with st.lock:
        rec = {
            "ev": "counters",
            "ts_us": time.time_ns() // 1000,
            "pid": os.getpid(),
            "platform": st.platform,
            "counters": dict(st.counters),
            "gauges": dict(st.gauges),
        }
        st.record(rec)


def events() -> list:
    """A snapshot list of the ring buffer's events (oldest first)."""
    st = _resolve_state()
    with st.lock:
        return list(st.ring)


def export_jsonl(path: str) -> int:
    """Write the ring buffer (plus a final counter snapshot) to
    ``path`` as JSON lines; returns the number of lines written."""
    st = _resolve_state()
    with st.lock:
        evs = list(st.ring)
        snap = {
            "ev": "counters",
            "ts_us": time.time_ns() // 1000,
            "pid": os.getpid(),
            "platform": st.platform,
            "counters": dict(st.counters),
            "gauges": dict(st.gauges),
        }
    evs.append(snap)
    d = os.path.dirname(os.path.abspath(path))
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        for e in evs:
            f.write(json.dumps(e, default=str) + "\n")
    return len(evs)
