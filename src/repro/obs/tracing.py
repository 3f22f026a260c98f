"""Spans and the flight recorder (DESIGN.md §13).

A :class:`Span` is a nestable context manager timed on
``time.perf_counter``.  Entering pushes it onto a thread-local stack, so a
child records its parent's name and shares its call id: every span of one
top-level call carries the same ``call``.  It also enters a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler session
shows the span on its host timeline, on the clock of the device events.
Exiting records the duration into the registry histogram of the same name
(armed only) and appends ``{name, start_s, end_s, parent, call, thread,
error[, attrs]}`` to the registry's ring.  Exit is exception-safe: a
raising body still closes the span (``error=True``) and re-raises.

This module does not load JAX: a process that never did (a WAL tool, the
chaos soak's parent) has no profiler to annotate, and its armed spans
record without the annotation.

``dump_incident`` is the flight recorder's readout: on a fault event
(write-path poison, shard strike-out, degraded read) the engine calls
``registry.incident(reason, **ctx)`` which snapshots the newest spans plus
the scalar deltas since the previous incident and writes one JSON file —
tmp + ``os.replace`` so a crash mid-dump never leaves a torn incident —
making a chaos-soak kill diagnosable post-mortem.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import tempfile
import threading
import time
from typing import Optional

#: spans an incident dump writes, newest last
INCIDENT_SPANS = 64

#: call ids of top-level spans (``next`` on a count is atomic under the GIL)
_CALL_IDS = itertools.count()


#: ``jax.profiler.TraceAnnotation`` once the process has loaded JAX
_ANNOTATION = None


def _annotation():
    """The annotation class, or None while the process has not loaded JAX
    (then no profiler can be recording)."""
    global _ANNOTATION
    if _ANNOTATION is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


def profiling() -> bool:
    """True while a profiler session records host events."""
    annotation = _ANNOTATION or _annotation()
    return annotation is not None and annotation.is_enabled()


def _span_stack(registry) -> list:
    stack = getattr(registry._local, "span_stack", None)
    if stack is None:
        stack = []
        registry._local.span_stack = stack
    return stack


class Span:
    """One timed region; made by ``Registry.span`` while armed or traced."""

    __slots__ = ("_registry", "name", "attrs", "parent", "call", "_t0",
                 "_trace")

    def __init__(self, registry, name: str, attrs: Optional[dict] = None):
        self._registry = registry
        self.name = name
        self.attrs = dict(attrs) if attrs else None
        self.parent = None
        self.call = None
        self._t0 = 0.0
        annotation = _ANNOTATION or _annotation()
        self._trace = annotation(name) if annotation is not None else None

    def __enter__(self):
        stack = _span_stack(self._registry)
        if stack:
            self.parent = stack[-1].name
            self.call = stack[-1].call
        else:
            self.call = next(_CALL_IDS)
        stack.append(self)
        if self._trace is not None:
            self._trace.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        if self._trace is not None:
            self._trace.__exit__(exc_type, exc, tb)
        stack = _span_stack(self._registry)
        if stack and stack[-1] is self:
            stack.pop()
        self._registry.hist_record(self.name, t1 - self._t0)
        rec = {"name": self.name, "start_s": self._t0, "end_s": t1,
               "parent": self.parent, "call": self.call,
               "thread": threading.current_thread().name,
               "error": exc_type is not None}
        if self.attrs:
            rec["attrs"] = self.attrs
        self._registry._spans.append(rec)
        return False   # never swallow the exception


def dump_incident(registry, reason: str, ctx: dict) -> Optional[str]:
    """Write one incident file; returns its path (None when no incident
    dir is configured or the per-process cap is exhausted — the counter
    still bumps so the scrape shows suppressed incidents)."""
    registry.counter_add("incidents")
    seq = registry._next_incident()
    directory = registry.incident_dir
    if directory is None or seq > registry.max_incidents:
        return None
    spans = registry.spans()[-INCIDENT_SPANS:]
    scalars = registry.scalars()
    deltas = registry.incident_delta(scalars)
    payload = {
        "schema": "mcq-incident-v1",
        "reason": reason,
        "ctx": {k: repr(v) if not isinstance(
            v, (int, float, str, bool, type(None))) else v
            for k, v in ctx.items()},
        "seq": seq,
        "pid": os.getpid(),
        "unix_time": time.time(),
        "spans": spans,
        "scalars": scalars,
        "deltas": deltas,
    }
    os.makedirs(directory, exist_ok=True)
    final = os.path.join(directory, f"incident_{seq:04d}_{os.getpid()}.json")
    fd, tmp = tempfile.mkstemp(dir=directory, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=2, default=repr)
        os.replace(tmp, final)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
    return final
