"""The program's one span API, and the host-side ring behind it.

``span(name, **args)`` is the only way the program writes a span.  It
has two sinks:

- a ``jax.profiler.TraceAnnotation``: recorded only while a jax profile
  is being captured (``paddle_tpu.profiler.profiler()``, xprof, the
  benchmark's ``--trace 1`` run), in the profile's host plane, on the
  same clock as the device ops — so a device-idle gap can be named
  after what the host was doing in it.  With no profile running it
  costs one object construction.
- the ``GLOBAL_EVENTS`` ring, **off by default**: while enabled
  (``recording()``, ``paddle stats --trace``, ``GET /trace``) every
  span is kept as a Chrome-trace complete event with an ``id``, its
  ``parent`` (the innermost span open on the same thread, 0 for none)
  and its args; spans of one request share ``rid``.  The ring runs on
  ``time.perf_counter`` (CLOCK_MONOTONIC, the clock a load generator
  stamps its sends on) and its export carries that clock's value at the
  ring's epoch, so a ring span lines up with a client-side record.

A profile's timestamps count from the profile's start, so ring and
profile are aligned by content, not by clock: with both sinks on the
same span is in both.

``phase(name, **args)`` is the same statement for a span whose seconds
the program also keeps by itself, in every run: it reads
``time.perf_counter`` once at each edge, gives the ring those two
readings and adds their difference to the ``PhaseAccount`` that is open
on the thread (``PhaseAccount.open``; none open: a span and no more).
The decode tick's phases are written so (``decode/session.py``), and
flushed from the account into the registry once a tick.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List

from jax.profiler import TraceAnnotation


class EventRecorder:
    """Thread-safe bounded ring of Chrome-trace events.

    Timestamps are microseconds since the recorder's epoch
    (``perf_counter`` based, monotonic), which is what the trace viewer
    expects; the epoch is anchored once in metadata, on the wall clock
    and on ``perf_counter`` itself.  ``enabled`` gates only what
    ``span()`` writes into ``GLOBAL_EVENTS``: ``complete``/``instant``
    called directly always record (a private recorder in a test or an
    artifact).
    """

    def __init__(self, max_events: int = 100_000):
        self._t0 = time.perf_counter()
        self._epoch_unix = time.time()
        self._events: collections.deque = collections.deque(maxlen=max_events)
        self._lock = threading.Lock()
        self.enabled = False

    def enable(self):
        """Start a recording: drop what an earlier one left, then let
        ``span()`` write here."""
        self.clear()
        self.enabled = True

    def disable(self):
        self.enabled = False

    def now(self) -> float:
        """Seconds since the recorder epoch."""
        return time.perf_counter() - self._t0

    def complete(self, name: str, start: float, dur: float,
                 cat: str = "paddle", **args):
        """Record a complete ("X") event; ``start``/``dur`` in seconds
        on the ``now()`` clock."""
        self._complete(name, start, dur, cat, args)

    def _complete(self, name, start, dur, cat, args):
        ev: Dict[str, Any] = {
            "name": name, "cat": cat, "ph": "X",
            "ts": start * 1e6, "dur": max(dur, 0.0) * 1e6,
            "pid": os.getpid(), "tid": threading.get_ident(),
        }
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def instant(self, name: str, cat: str = "paddle", **args):
        ev: Dict[str, Any] = {
            "name": name, "cat": cat, "ph": "i", "s": "t",
            "ts": self.now() * 1e6,
            "pid": os.getpid(), "tid": threading.get_ident(),
        }
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def clear(self):
        """Drop recorded events.  The epoch is deliberately NOT rebased:
        a span in flight on another thread (serving handlers) captured
        its start against the current epoch, and rebasing would give it
        a garbage/negative timestamp when it completes."""
        with self._lock:
            self._events.clear()

    def to_chrome_trace(self) -> dict:
        return {
            "traceEvents": self.events(),
            "displayTimeUnit": "ms",
            "otherData": {
                "recorder": "paddle_tpu.observability",
                "epoch_unix_sec": self._epoch_unix,
                "epoch_perf_counter_sec": self._t0,
            },
        }

    def export(self, path: str) -> str:
        """Write ``chrome://tracing``-loadable JSON; returns ``path``."""
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


GLOBAL_EVENTS = EventRecorder()

_SPAN_IDS = itertools.count(1)       # next() is atomic under the GIL
_OPEN = threading.local()            # .stack: ids of this thread's open spans
#                                      .account: the PhaseAccount open on it


class span:
    """``with span("decode.tick", active=3): ...`` — see the module's
    docstring.  Safe from any thread and re-entrant; an argument known
    only inside the span is added with ``set`` (it reaches the profile
    and the ring alike)."""

    __slots__ = ("name", "args", "_ann", "_open")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args

    def set(self, **args):
        self.args.update(args)
        self._ann.set_metadata(**args)

    def __enter__(self):
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self._open = (self._ring_open(time.perf_counter())
                      if GLOBAL_EVENTS.enabled else None)
        return self

    def __exit__(self, *exc):
        if self._open is not None:
            self._ring_close(time.perf_counter())
        self._ann.__exit__(*exc)
        return False

    def _ring_open(self, t0: float) -> tuple:
        """The span is open in the ring since ``t0`` (``perf_counter``)."""
        try:
            stack = _OPEN.stack
        except AttributeError:
            stack = _OPEN.stack = []
        sid = next(_SPAN_IDS)
        stack.append(sid)
        return sid, stack[-2] if len(stack) > 1 else 0, t0, stack

    def _ring_close(self, t1: float) -> None:
        sid, parent, t0, stack = self._open
        if stack is not None:
            stack.remove(sid)
        GLOBAL_EVENTS._complete(
            self.name, t0 - GLOBAL_EVENTS._t0, t1 - t0, "paddle",
            dict(self.args, id=sid, parent=parent))


class PhaseAccount:
    """Seconds by span name, summed over the ``phase`` statements that
    ran on the thread while the account was open there: the counter
    twin of those spans, kept whether or not anything records them.
    ``names`` are the spans it keeps (a ``phase`` of another name is a
    span and no more); the owner reads ``seconds`` and zeroes it with
    ``take``."""

    __slots__ = ("index", "seconds")

    def __init__(self, names):
        self.index = {name: i for i, name in enumerate(names)}
        self.seconds = [0.0] * len(self.index)

    def open(self) -> None:
        """Phases on this thread charge this account from now on."""
        _OPEN.account = self

    @staticmethod
    def close() -> None:
        _OPEN.account = None

    def take(self) -> List[float]:
        """The seconds by name, in ``names``' order, since the last
        ``take``."""
        taken, self.seconds = self.seconds, [0.0] * len(self.seconds)
        return taken


class phase(span):
    """``span`` whose seconds also go to the thread's open
    ``PhaseAccount``: one ``perf_counter`` reading at each edge serves
    the ring and the account, so a phase's counter and its ring span
    agree to the last bit, and the profile's annotation lies round
    both.  ``seconds`` is the duration once the block has ended."""

    __slots__ = ("t0", "seconds")

    def __enter__(self):
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self.t0 = t0 = time.perf_counter()
        self._open = self._ring_open(t0) if GLOBAL_EVENTS.enabled else None
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.seconds = t1 - self.t0
        account = getattr(_OPEN, "account", None)
        if account is not None:
            i = account.index.get(self.name)
            if i is not None:
                account.seconds[i] += self.seconds
        if self._open is not None:
            self._ring_close(t1)
        self._ann.__exit__(*exc)
        return False


class straddling_phase(phase):
    """A ``phase`` that one call opens and a later call closes (the
    wait between two decode ticks), so it is not nested in anything:
    the ring keeps it as a root and it is never on the thread's stack
    of open spans, where one that is never closed (a session dropped
    while not idle) would pass for the parent of every later span."""

    __slots__ = ()

    def _ring_open(self, t0: float) -> tuple:
        return next(_SPAN_IDS), 0, t0, None


@contextlib.contextmanager
def recording():
    """Record every ``span()`` of the enclosed block into
    ``GLOBAL_EVENTS``; yields the ring."""
    GLOBAL_EVENTS.enable()
    try:
        yield GLOBAL_EVENTS
    finally:
        GLOBAL_EVENTS.disable()
