"""Host-side tracing: monotonic-clock spans + structured events.

The sampler's device work is one opaque scan dispatch; everything the
HOST does around it — the engine's per-call layout and staging, streamed
windows, snapshot I/O, draw-bank refreshes, serving prefill/decode — is
what this module makes visible. One module-level tracer (disabled by
default: every call is a no-op on a shared null object, so instrumented
code paths cost nothing when nobody is watching), configured once per
process by the entry points::

    from repro.obs import trace
    trace.configure(path="run/trace.jsonl", echo=True)
    with trace.span("engine.segment", r0=0, rounds=8):
        ...
    trace.event("engine.progress", round=8, steps_per_s=1.2e5)

Three sinks, any combination:

* ``path``: span and event records kept in memory and written as JSONL
  at ``close()`` (``configure()`` closes the tracer it replaces). Span
  records carry the WALL-clock start (``ts``, epoch seconds — for
  cross-process alignment) and a MONOTONIC duration (``dur_s`` — immune
  to clock steps), plus the nesting ``depth`` and ``parent`` span name
  from a thread-local stack, so a reader can rebuild the span tree from
  the flat JSONL.
* ``echo``: one compact human line per record, printed at once — the
  structured replacement for bare ``print`` progress messages.
* ``profiler``: every span enters a ``jax.profiler.TraceAnnotation``
  carrying its attributes, so host spans land in a profiler trace
  (``jax.profiler.start_trace``) on the device's clock, attributes as
  event stats. Alone it builds no record.

``span.set(k=v)`` adds attributes known only inside the span (counters);
they reach the record and the annotation alike.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Optional


class _NullSpan:
    """Shared no-op context manager: the disabled-tracer fast path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("tracer", "name", "attrs", "t0", "ts", "depth", "parent",
                 "_prof")

    def __init__(self, tracer, name, attrs):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self._prof = None

    def __enter__(self):
        tr = self.tracer
        if tr.recording:
            stack = getattr(tr._tls, "stack", None)
            if stack is None:
                stack = tr._tls.stack = []
            self.depth = len(stack)
            self.parent = stack[-1] if stack else None
            stack.append(self.name)
            self.ts = time.time()
            self.t0 = time.monotonic()
        if tr._annotation is not None:
            self._prof = tr._annotation(self.name, **self.attrs)
            self._prof.__enter__()
        return self

    def set(self, **attrs):
        """Add attributes to the open span (counters known only now)."""
        self.attrs.update(attrs)
        if self._prof is not None:
            self._prof.set_metadata(**attrs)

    def __exit__(self, *exc):
        if self._prof is not None:
            self._prof.__exit__(*exc)
        tr = self.tracer
        if tr.recording:
            dur = time.monotonic() - self.t0
            tr._tls.stack.pop()
            rec = {"type": "span", "name": self.name, "ts": self.ts,
                   "dur_s": dur, "depth": self.depth, "parent": self.parent}
            rec.update(self.attrs)
            tr._emit(rec)
        return False


class Tracer:
    """A span/event sink. With no ``path``, no ``echo`` and no
    ``profiler`` it is disabled entirely (``span`` returns a shared no-op
    context manager)."""

    def __init__(self, path: Optional[str] = None, *, echo: bool = False,
                 profiler: bool = False):
        self.path = path
        self.echo = echo
        self.profiler = profiler
        self._annotation = None
        if profiler:
            import jax
            self._annotation = jax.profiler.TraceAnnotation
        self._lines = []
        self._lock = threading.Lock()
        self._tls = threading.local()

    @property
    def recording(self) -> bool:
        """Whether spans and events build records (JSONL or echo)."""
        return self.path is not None or self.echo

    @property
    def enabled(self) -> bool:
        return self.recording or self.profiler

    def span(self, name: str, **attrs):
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, attrs)

    def event(self, name: str, **attrs):
        if not self.recording:
            return
        stack = getattr(self._tls, "stack", [])
        rec = {"type": "event", "name": name, "ts": time.time(),
               "depth": len(stack),
               "parent": stack[-1] if stack else None}
        rec.update(attrs)
        self._emit(rec)

    def _emit(self, rec: dict):
        with self._lock:
            if self.path is not None:
                self._lines.append(json.dumps(rec, default=str))
            if self.echo:
                ts = time.strftime("%H:%M:%S", time.localtime(rec["ts"]))
                kv = " ".join(
                    f"{k}={rec[k]}" for k in rec
                    if k not in ("type", "name", "ts", "depth", "parent"))
                print(f"[{ts}] {rec['name']} {kv}".rstrip(), flush=True)

    def close(self):
        """Append the records kept so far to ``path``."""
        with self._lock:
            if self.path is not None and self._lines:
                with open(self.path, "a") as f:
                    f.write("\n".join(self._lines) + "\n")
            self._lines = []


_TRACER = Tracer()


def configure(path: Optional[str] = None, *, echo: bool = False,
              profiler: bool = False) -> Tracer:
    """Install the process-wide tracer (and return it), closing the one
    it replaces. Call with no arguments to disable tracing again."""
    global _TRACER
    _TRACER.close()
    _TRACER = Tracer(path, echo=echo, profiler=profiler)
    return _TRACER


def enabled() -> bool:
    return _TRACER.enabled


def span(name: str, **attrs):
    """Context manager timing one named host-side segment."""
    return _TRACER.span(name, **attrs)


def event(name: str, **attrs):
    """One timestamped structured log line (no duration)."""
    _TRACER.event(name, **attrs)


def read_jsonl(path: str) -> list:
    """Parse a trace JSONL file back into a list of record dicts."""
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]
