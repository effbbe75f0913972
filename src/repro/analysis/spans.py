"""Program spans and counters: where a FedSDD round's host time goes.

A round's device work is a handful of programs; the host work around
them (planning, bucket stacking, eager per-leaf reassembly, the bank
push) leaves the device idle, and only the host can say what it was
doing.  This module is the one place that says it::

    with collect_round(round=t) as col, span("fedsdd.round"):
        with span("fedsdd.local.prep"):
            ...                          # host work, timed
        count("local_steps", steps)      # a counter of the round
    col.seconds, col.parents, col.counts

``span(name, **attrs)`` opens a ``jax.profiler.TraceAnnotation`` (so the
span lands on the profiler's host plane, on the same clock as the
device's ``XLA Ops``), takes ``perf_counter`` at both ends, and adds the
duration to the round collector under ``name`` and under its parent, the
enclosing span on the same thread (a thread-local stack, so a span on
the async KD worker never nests into the main thread's).  The
collector's attributes (``round=t``) are stamped onto every span it
collects.  Outside ``collect_round`` a span only annotates the trace.

Spans are always on; with no profiler active one costs two
``perf_counter`` calls, a dict add and an inactive TraceMe.  They belong
at phase boundaries: never per leaf, client or step, never inside a
jitted function, and never around a ``block_until_ready`` that was not
already there.

``named_program(name, fn)`` is the device half: ``jax.jit`` under a
stable name, so the profiler's ``XLA Modules`` line shows
``jit_<name>(…)``, with ``jax.named_scope(name)`` inside so the ops keep
the name in their metadata where the program is traced into another.
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Callable, Iterator

import jax

__all__ = ["RoundCollector", "collect_round", "count", "named_program",
           "self_seconds", "span"]

_TLS = threading.local()                 # per-thread stack of open spans
_LOCK = threading.Lock()
_CURRENT: list["RoundCollector | None"] = [None]   # the open round


class RoundCollector:
    """One round's summed span seconds, parent links and counters.

    ``seconds[name]``: host seconds summed over every opening of
    ``name``; ``parents[name][parent]``: the part of them spent under
    ``parent`` (``""`` for a span with no enclosing span on its thread);
    ``counts[name]``: the round's counters."""

    def __init__(self, **attrs) -> None:
        self.attrs = attrs
        self.seconds: dict[str, float] = {}
        self.parents: dict[str, dict[str, float]] = {}
        self.counts: dict[str, int] = {}

    def add(self, name: str, parent: str, seconds: float) -> None:
        with _LOCK:
            self.seconds[name] = self.seconds.get(name, 0.0) + seconds
            links = self.parents.setdefault(name, {})
            links[parent] = links.get(parent, 0.0) + seconds

    def count(self, name: str, n: int) -> None:
        with _LOCK:
            self.counts[name] = self.counts.get(name, 0) + int(n)


def self_seconds(seconds: dict, parents: dict) -> dict[str, float]:
    """Each span's seconds less those of the spans opened directly
    inside it: the host time no finer span names."""
    inner: dict[str, float] = {}
    for links in parents.values():
        for parent, s in links.items():
            inner[parent] = inner.get(parent, 0.0) + s
    return {name: s - inner.get(name, 0.0) for name, s in seconds.items()}


def _stack() -> list[str]:
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    return stack


@contextlib.contextmanager
def collect_round(**attrs) -> Iterator[RoundCollector]:
    """Open a collector that every span and counter of the scope, on any
    thread, adds to; the one it replaces is restored on exit."""
    col = RoundCollector(**attrs)
    prev, _CURRENT[0] = _CURRENT[0], col
    try:
        yield col
    finally:
        _CURRENT[0] = prev


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[None]:
    """Time the scope as ``name`` into the open round and the trace."""
    col = _CURRENT[0]
    if col is not None and col.attrs:
        attrs = {**col.attrs, **attrs}
    stack = _stack()
    parent = stack[-1] if stack else ""
    stack.append(name)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name, **attrs):
            yield
    finally:
        seconds = time.perf_counter() - t0
        stack.pop()
        if col is not None:
            col.add(name, parent, seconds)


def count(name: str, n: int) -> None:
    """Add ``n`` to the open round's counter ``name`` (no-op outside)."""
    col = _CURRENT[0]
    if col is not None:
        col.count(name, n)


def named_program(name: str, fn: Callable, **jit_kwargs) -> Callable:
    """``jax.jit(fn, **jit_kwargs)`` named ``name``, its body under
    ``jax.named_scope(name)``."""
    @functools.wraps(fn)
    def program(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program, **jit_kwargs)
