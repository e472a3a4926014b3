"""Named host spans of the program, recorded only while a profiler session
runs.

``span(name, **attrs)`` marks a stretch of host work. With no profiler
session recording (``jax.profiler.TraceAnnotation.is_enabled()`` false)
it runs its body and does nothing else: there is no flag, no environment
variable and no exporter. Inside a session each span

  * enters ``TraceAnnotation(f"surf.{name}", **attrs)``, so it lands on the
    host plane of the same profile as the device operations, and
  * appends a ``SpanRecord`` to a bounded, process-wide buffer: name,
    parent span (per thread), thread id, start and end on
    ``time.perf_counter``, the thread's CPU seconds inside the span
    (``time.thread_time``), and its attributes.

The body may add attributes it learns late with ``.set(**attrs)`` on the
object the ``with`` statement binds (a no-op when off).

An operator captures the spans by tracing the process::

    with jax.profiler.trace("surf-trace"):
        ...serve traffic or train...
    recs = repro.utils.spans.records()

and reads either the profile (TensorBoard / Perfetto: host events named
``surf.*``) or ``records()``: e.g. the mean of ``r.t1 - r.t0`` over the
records named ``serve.tick.stack``, or a request's queue wait as the
start of the ``serve.tick`` whose ``reqs`` holds its id less the end of
the ``serve.submit`` whose ``req`` is that id.

Span names (``serve/queue.py``): ``serve.submit`` (``req``) holding
``serve.submit.featurize`` and ``serve.submit.pad``; ``serve.tick``
(``reqs``, ``bucket``) holding ``serve.tick.admit``, ``serve.tick.stack``,
``serve.tick.call`` (``bytes_in``: host bytes passed, θ excluded;
``devices``: the size of the server's mesh; ``theta_bytes``: the bytes of
θ each device streams in the tick's executable; ``gather_bytes``: the
bytes each device receives from its all-gathers of W, 0 unless θ is
split), ``serve.tick.wait`` and ``serve.tick.unpack`` (``bytes_out``: the
bytes brought to the host, the admitted slots' W and the small leaves).

Device operations carry ``jax.named_scope`` names instead, in their HLO
metadata (backward operations as ``transpose(jvp(surf/mix))``):
``surf/featurize``, ``surf/mix``, ``surf/perceptron``, ``surf/loss``,
``surf/constraints``, ``surf/clip``, ``surf/adam`` and ``surf/dual``, and,
where the server splits θ by columns, ``surf/gather`` (the all-gather of
W that feeds each layer's perceptron).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import NamedTuple

from jax.profiler import TraceAnnotation

PREFIX = "surf."
MAX_RECORDS = 1 << 16


class SpanRecord(NamedTuple):
    name: str
    parent: str | None
    thread: int
    t0: float                # time.perf_counter at entry
    t1: float                # ... at exit
    cpu_s: float             # this thread's CPU seconds inside the span
    attrs: dict


_RECORDS: deque = deque(maxlen=MAX_RECORDS)
_LOCAL = threading.local()


class _Off:
    """The span when no profiler session records: enters, exits, ignores
    attributes."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


_OFF = _Off()


class _Span:
    __slots__ = ("name", "attrs", "parent", "_ann", "_t0", "_c0")

    def __init__(self, name, attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        stack = getattr(_LOCAL, "stack", None)
        if stack is None:
            stack = _LOCAL.stack = []
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        self._ann = TraceAnnotation(PREFIX + self.name, **self.attrs)
        self._c0 = time.thread_time()
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs):
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        cpu = time.thread_time() - self._c0
        _LOCAL.stack.pop()
        _RECORDS.append(SpanRecord(self.name, self.parent,
                                   threading.get_ident(), self._t0, t1, cpu,
                                   self.attrs))
        return False


def span(name, **attrs):
    """A context manager over one named stretch of host work; see the
    module docstring. Costs one ``is_enabled()`` check when off."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return _Span(name, attrs)


def records() -> list:
    """The recorded spans, oldest first (at most ``MAX_RECORDS``)."""
    return list(_RECORDS)
