"""Host spans of a served process, on the clock a device trace is anchored
to.

A span is a named stretch of host time. :class:`Spans` records each as
``(start_ns, end_ns)`` on ``time.time_ns``, the real-time clock to which
the JAX profiler anchors a trace (its ``profile_start_time``), in one
record ``{name: [(start_ns, end_ns), ...]}``. A device trace reduction
(``chipbench/trace_reduce.py``) then places the spans beside the device's
ops and says which host step the device waits on.

The server runs on one thread, so spans nest: a span's parent is the
innermost span that encloses it in time, and no request identifier is
needed. The spans of :class:`~repro.launch.serve_cnn.CnnServer`:

- ``install``: fold, mask derivation and fingerprint of new weights;
- ``bind``: one ``cnn.bind_execution`` call, holding one
  ``bind.layer.<kernel name>`` span per bound conv (plan, index table,
  weight packing);
- ``warmup.bucket``: one bucket's first call in ``warmup`` (trace,
  compile or persistent-cache load, and run);
- per chunk of an ``infer`` call, in turn: ``infer.prepare`` (frames to
  the device and checked, the chunk sliced and padded to its bucket, the
  bucket's program looked up; the first chunk's also holds the request's
  own checks), ``infer.dispatch`` (the jitted forward's enqueue) and
  ``infer.collect`` (the logits to the host, which waits for the device,
  the finite check and the output slice; the last chunk's also the
  concatenation);
- ``compile``: one XLA compile, ending when JAX reports it, while
  :meth:`Spans.watch_compiles` is on.

:data:`NO_SPANS`, the default everywhere, records nothing: a span is then
one call that returns a shared ``nullcontext`` or ``0``, with no clock
read, no allocation and no device sync. Nothing here syncs the device in
either case; the spans time the host work that is there.

To record a served process, hand the server a :class:`Spans`, start the
profiler with its host tracer off (``chipbench/run.profile_options``:
the host tracer halves the host's rate), serve, stop the profiler, take
the record with :meth:`Spans.drain`, and reduce it with
``chipbench/trace_reduce.reduce``.
"""
from __future__ import annotations

import contextlib
import time
import types
from typing import Dict, List, Tuple

Record = Dict[str, List[Tuple[int, int]]]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_hits"

_NULL = contextlib.nullcontext()
_watchers: List["Spans"] = []      # recorders taking JAX's compile events
_listening = False


class _Span:
    __slots__ = ("record", "name", "start")

    def __init__(self, record: Record, name: str):
        self.record, self.name = record, name

    def __enter__(self):
        self.start = time.time_ns()

    def __exit__(self, *exc):
        self.record.setdefault(self.name, []).append(
            (self.start, time.time_ns()))


class NoSpans:
    """The recorder that records nothing (:data:`NO_SPANS`)."""

    spans = types.MappingProxyType({})

    def __call__(self, name: str):
        return _NULL

    def now(self) -> int:
        return 0

    def mark(self, name: str, start: int) -> int:
        return 0


NO_SPANS = NoSpans()


class Spans:
    """Records host spans by name in ``time.time_ns``, and counts JAX's
    compiles and persistent-cache loads while :meth:`watch_compiles` is
    on.

    ``with spans(name): ...`` records the block. For steps that follow
    one another, ``t = spans.now()`` opens the first and
    ``t = spans.mark(name, t)`` closes each (records ``[t, now)``) and
    returns the start of the next."""

    def __init__(self):
        self.spans: Record = {}
        self.compiles = 0
        self.cache_loads = 0

    def __call__(self, name: str) -> _Span:
        return _Span(self.spans, name)

    def now(self) -> int:
        return time.time_ns()

    def mark(self, name: str, start: int) -> int:
        end = time.time_ns()
        self.spans.setdefault(name, []).append((start, end))
        return end

    def drain(self) -> Record:
        """The record so far; the recorder starts a new one."""
        out, self.spans = self.spans, {}
        return out

    @contextlib.contextmanager
    def watch_compiles(self):
        """While inside: count compiles and cache loads, and record each
        compile as a ``compile`` span that ends at JAX's event and lasts
        the compile's reported duration, so a device idle during a
        recompile is put down to it."""
        _listen()
        _watchers.append(self)
        try:
            yield self
        finally:
            _watchers.remove(self)

    def _compiled(self, seconds: float) -> None:
        end = time.time_ns()
        self.compiles += 1
        self.spans.setdefault("compile", []).append(
            (end - int(seconds * 1e9), end))


def _listen() -> None:
    """Register the ``jax.monitoring`` listeners, once per process (JAX
    has no way to remove one); they feed the recorders watching."""
    global _listening
    if _listening:
        return
    import jax

    def duration(event, seconds, *args, **kwargs):
        if event == COMPILE_EVENT:
            for r in _watchers:
                r._compiled(seconds)

    def count(event, *args, **kwargs):
        if event == CACHE_LOAD_EVENT:
            for r in _watchers:
                r.cache_loads += 1

    jax.monitoring.register_event_duration_secs_listener(duration)
    jax.monitoring.register_event_listener(count)
    _listening = True
