"""Chrome-trace-format span timeline for supersteps, WAL and service events.

Emits the JSON "trace event format" consumed by ``chrome://tracing`` and
https://ui.perfetto.dev — complete events (``ph: "X"``) for timed spans and
instant events (``ph: "i"``) for replayed per-pass markers.  Timestamps are
microseconds relative to ``start_trace()``, stamped with
``time.perf_counter()``.

Design constraints, in order:

1. **Never perturb the computation.**  Spans only read values the host already
   has (frontier sizes, pinned per-chunk masks, planner charges); nothing is
   forced off-device for tracing.  The trace-parity test in
   ``tests/test_obs.py`` asserts instrumented runs are bit-identical.
2. **Zero cost when off.**  Tracing is opt-in: ``span()`` returns a shared
   no-op singleton unless a collector was started (``start_trace()`` or the
   ``REPRO_TRACE`` env var) *and* ``REPRO_OBS`` is not ``0``.  The fast path
   is one attribute read and one env check.
3. **One clock with the device.**  While the collector is active, every span
   also opens a ``jax.profiler.TraceAnnotation`` of the same name — when JAX
   is already imported; this module never imports it.  Under a running JAX
   profiler the span is then a host event of the profiler's own trace, on its
   clock, beside the device operations (Perfetto, TensorBoard).

Span catalogue (DESIGN.md §14 lists each with its args):

* engine — ``superstep`` (per-pass loop), ``resident.chunk`` (one chunk
  call of the resident fixpoint, its download and replay),
  ``resident.replay`` (the host replay of one chunk's planner charges),
  ``resident.bind`` (structure build and upload on a cache miss),
  ``engine.merge_buffered`` (splice of buffered edge updates);
* maintenance — ``maintenance.parallel_settle`` / ``.batch_settle`` /
  ``.apply_batch`` (one micro-batch), ``maint.apply_ops`` (the
  batch applied to the buffered graph), ``maint.plan`` (one round's grouped
  planning), ``cnt_prologue`` (the exact-cnt scan);
* stream — ``service.ingest``, ``wal.append``, ``wal.rotate``,
  ``snapshot.save``, ``replica.bootstrap``, ``replica.sync``.

``REPRO_TRACE`` values: unset/``0`` — off; ``1`` — collect (caller saves);
any other string — collect and atexit-save to that path.
"""
from __future__ import annotations

import atexit
import json
import os
import sys
import time
from typing import List, Optional

from .metrics import obs_enabled

__all__ = [
    "TRACE_ENV_VAR",
    "Span",
    "TraceCollector",
    "get_collector",
    "start_trace",
    "stop_trace",
    "save_trace",
    "clear_trace",
    "tracing_active",
    "span",
    "instant",
]

TRACE_ENV_VAR = "REPRO_TRACE"


class _NullSpan:
    """Shared do-nothing span handed out when tracing is off."""

    __slots__ = ()
    active = False

    def set(self, **args) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


_NULL_SPAN = _NullSpan()


class Span:
    """A timed complete event; use as a context manager.

    ``set(**args)`` attaches extra args visible in the Perfetto side panel
    (frontier sizes, block activity, probe counts, …).  With JAX imported,
    the span also opens a profiler ``TraceAnnotation`` of its name.
    """

    __slots__ = ("_collector", "name", "cat", "args", "_t0", "_annotation")
    active = True

    def __init__(self, collector: "TraceCollector", name: str, cat: str, args: dict):
        self._collector = collector
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = 0.0
        self._annotation = None

    def set(self, **args) -> "Span":
        self.args.update(args)
        return self

    def __enter__(self) -> "Span":
        jax = sys.modules.get("jax")
        if jax is not None:
            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self._collector._emit_complete(self)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None


class TraceCollector:
    """Accumulates trace events; one per process is plenty."""

    def __init__(self) -> None:
        self.events: List[dict] = []
        self.active = False
        self._epoch = 0.0
        self._pid = os.getpid()

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        if not self.active:
            self.active = True
            self._epoch = time.perf_counter()

    def stop(self) -> None:
        self.active = False

    def clear(self) -> None:
        self.events = []
        self._epoch = time.perf_counter()

    def _enabled(self) -> bool:
        return self.active and obs_enabled()

    def _us(self, t: float) -> float:
        return (t - self._epoch) * 1e6

    # -- event emission -----------------------------------------------------
    def span(self, name: str, cat: str = "repro", **args):
        if not self._enabled():
            return _NULL_SPAN
        return Span(self, name, cat, args)

    def _emit_complete(self, sp: Span) -> None:
        if not self._enabled():
            return
        now = time.perf_counter()
        self.events.append({
            "name": sp.name,
            "cat": sp.cat,
            "ph": "X",
            "ts": self._us(sp._t0),
            "dur": (now - sp._t0) * 1e6,
            "pid": self._pid,
            "tid": 0,
            "args": sp.args,
        })

    def instant(self, name: str, cat: str = "repro", **args) -> None:
        if not self._enabled():
            return
        self.events.append({
            "name": name,
            "cat": cat,
            "ph": "i",
            "ts": self._us(time.perf_counter()),
            "s": "t",
            "pid": self._pid,
            "tid": 0,
            "args": args,
        })

    # -- output -------------------------------------------------------------
    def to_chrome(self) -> dict:
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.to_chrome(), f)
        return path


_collector = TraceCollector()


def get_collector() -> TraceCollector:
    return _collector


def start_trace() -> None:
    """Begin collecting trace events (idempotent)."""
    _collector.start()


def stop_trace() -> None:
    _collector.stop()


def clear_trace() -> None:
    _collector.clear()


def save_trace(path: str) -> str:
    """Write the collected timeline as Chrome-trace JSON and return the path."""
    return _collector.save(path)


def tracing_active() -> bool:
    return _collector._enabled()


def span(name: str, cat: str = "repro", **args):
    """Open a span against the process collector (no-op singleton when off)."""
    return _collector.span(name, cat, **args)


def instant(name: str, cat: str = "repro", **args) -> None:
    _collector.instant(name, cat, **args)


def _init_from_env() -> None:
    val = os.environ.get(TRACE_ENV_VAR, "")
    if not val or val == "0":
        return
    start_trace()
    if val != "1":
        atexit.register(save_trace, val)


_init_from_env()
