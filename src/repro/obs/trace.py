"""Host-side span tracer with Chrome-trace/Perfetto export (DESIGN.md §15).

One tracer serves the whole process: the Trainer's chunk phases
(decide/put/execute/fetch/record), the Prefetcher's produce/wait pair (on
its worker thread), and the serving schedulers' tick phases all record
into it. Events live in host memory as plain tuples until ``export``
writes the Chrome trace-event JSON (load the file at
https://ui.perfetto.dev or chrome://tracing). An enabled tracer's spans
also enter a ``jax.profiler.TraceAnnotation`` of the same name and args,
so inside a ``jax.profiler.trace`` window the profiler writes them on
the trace's own clock, on the recording thread's line, beside the
device's ops.

Design constraints:

  * ONE wall-clock source. ``monotonic()`` (= ``time.perf_counter``) is
    the repo's only measurement clock — mixing ``time.time()`` into a
    perf_counter-based timeline made one-shot serve latencies and
    scheduler timestamps incomparable. Everything that stamps a duration
    or an arrival goes through this helper.
  * Near-zero overhead when disabled: ``span()`` on a disabled tracer
    returns a shared no-op context manager after a single attribute
    check — no object allocation, no clock read, no event
    (``tests/test_obs.py::test_disabled_tracer_costs_nothing``).
  * Zero device interaction. Recording touches only the clock, a list
    append and the profiler's host annotation, so instrumented code
    stays green under the ``analysis.hostsync`` guard; span ``args``
    must already be host scalars (never jax arrays — stringifying one
    would sync).
  * Thread safety by construction: ``list.append`` is atomic under the
    GIL and each event carries its recording thread's id; export maps
    the ids to dense Perfetto track numbers with ``thread_name``
    metadata.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "get_tracer", "monotonic", "set_tracer"]


def monotonic() -> float:
    """THE wall-clock of the repo: monotonic seconds (perf_counter).

    Not comparable across processes or to ``time.time()`` — durations
    and same-process orderings only, which is all the trainer, the
    schedulers, and the benchmarks ever need."""
    return time.perf_counter()


class _NullCtx:
    """Shared no-op context manager: the disabled-tracer fast path."""
    __slots__ = ()

    def __enter__(self) -> "_NullCtx":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL = _NullCtx()


class _Span:
    """One open span; records a complete ('X') event on exit. Its
    profiler annotation is what a ``jax.profiler`` trace records."""
    __slots__ = ("_tr", "_name", "_args", "_t0", "_ann")

    def __init__(self, tr: "Tracer", name: str, args: Dict[str, Any]):
        self._tr, self._name, self._args = tr, name, args
        self._ann = TraceAnnotation(name, **args)

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self._t0 = monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = monotonic()
        self._ann.__exit__(*exc)
        self._tr._record("X", self._name, self._t0, t1 - self._t0,
                         self._args)
        return False


def _jsonable(v: Any) -> Any:
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    return str(v)


class Tracer:
    """Nested spans + instant events on the monotonic clock.

    ``span(name, **args)`` is a context manager (nesting = call-stack
    containment, rendered as stacked slices per thread); ``instant``
    marks a point ('i' event, e.g. a jit retrace or a prefix-cache
    hit). ``export(path)`` writes
    ``{"traceEvents": [...]}`` with timestamps in µs since the tracer's
    epoch."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._epoch = monotonic()
        self._events: List[Tuple[str, str, float, float, int,
                                 Dict[str, Any]]] = []
        self._tid_names: Dict[int, str] = {}
        self._lock = threading.Lock()

    # -- recording ----------------------------------------------------------

    def _record(self, ph: str, name: str, ts: float, dur: float,
                args: Dict[str, Any]) -> None:
        tid = threading.get_ident()
        if tid not in self._tid_names:
            self._tid_names[tid] = threading.current_thread().name
        self._events.append((ph, name, ts, dur, tid, args))

    def span(self, name: str, **args):
        """Context manager timing the enclosed block. Disabled tracers
        return a shared no-op after one attribute check."""
        if not self.enabled:
            return _NULL
        return _Span(self, name, args)

    def instant(self, name: str, **args) -> None:
        if not self.enabled:
            return
        self._record("i", name, monotonic(), 0.0, args)

    # -- device profile ----------------------------------------------------

    def profile_window(self, logdir: Optional[str]):
        """``jax.profiler.trace`` window writing a TensorBoard-loadable
        device profile under ``logdir`` (no-op when ``logdir`` is empty).
        Independent of ``enabled``: a device profile needs no host spans."""
        if not logdir:
            return _NULL
        import jax.profiler
        return jax.profiler.trace(logdir)

    # -- inspection / export ------------------------------------------------

    def __len__(self) -> int:
        return len(self._events)

    @property
    def events(self) -> List[Tuple[str, str, float, float, int,
                                   Dict[str, Any]]]:
        """Raw (ph, name, t_start, dur, tid, args) tuples, in record
        order (seconds on the monotonic clock)."""
        return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events = []
            self._tid_names = {}
            self._epoch = monotonic()

    def export(self, path: Optional[str] = None) -> Dict[str, Any]:
        """Chrome trace-event JSON object; written to ``path`` if given.

        Spans become complete ('X') events with ``ts``/``dur`` in µs;
        instants carry thread scope (``"s": "t"``); each thread gets a
        ``thread_name`` metadata event so Perfetto labels its track."""
        with self._lock:
            evs = list(self._events)
            names = dict(self._tid_names)
        dense: Dict[int, int] = {}
        for e in evs:
            dense.setdefault(e[4], len(dense))
        pid = os.getpid()
        out: List[Dict[str, Any]] = [
            {"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
             "args": {"name": "repro"}}]
        for tid, dt in dense.items():
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": dt,
                        "args": {"name": names.get(tid, f"thread-{dt}")}})
        for ph, name, ts, dur, tid, args in evs:
            ev: Dict[str, Any] = {
                "ph": ph, "name": name, "pid": pid, "tid": dense[tid],
                "ts": (ts - self._epoch) * 1e6,
                "args": {k: _jsonable(v) for k, v in args.items()}}
            if ph == "X":
                ev["dur"] = dur * 1e6
            else:
                ev["s"] = "t"
            out.append(ev)
        doc = {"traceEvents": out, "displayTimeUnit": "ms"}
        if path:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc


# ---------------------------------------------------------------------------
# process-global tracer (disabled by default)
# ---------------------------------------------------------------------------
# Instrumented code paths (Trainer, Prefetcher, schedulers, dryrun) pick
# this up when no tracer is passed explicitly, so `--trace-out` in a
# launcher turns on every layer at once.

_GLOBAL = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _GLOBAL


def set_tracer(tracer: Tracer) -> Tracer:
    global _GLOBAL
    _GLOBAL = tracer
    return tracer
