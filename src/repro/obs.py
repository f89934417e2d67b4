"""Spans and compile counts inside the program, on the profiler's clock.

Off by default. While off, :func:`span` returns one shared no-op context
manager: no clock read, no JAX call, nothing kept. While on, each span

- keeps ``Span(name, start_ns, end_ns, parent, ids)`` in memory, on
  ``time.perf_counter_ns()``, where ``parent`` is the name of the span
  open around it (None at the top) and ``ids`` the keywords it was opened
  with (a request id, a step number);
- enters ``jax.profiler.TraceAnnotation(name)``, so that a run under
  ``jax.profiler`` has the span on the profiler's host line, on the same
  clock as the device's events.

Spans stay in memory until the caller reads them (:func:`spans`) and
clears them (:func:`reset`); nothing is written out. Tracing never waits
for the device and never reads a device array.

The first :func:`enable` also registers one ``jax.monitoring`` listener
that counts XLA compilations, less loads from the persistent compile
cache, under the innermost span open when each happened (``none`` where
none was): :func:`compiles`.

The state is process-wide, as the profiler it writes into is. Spans nest
on one thread: the engine's.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, NamedTuple, Optional

import jax

NONE = "none"          # where a compile happened under no span
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]
    ids: Dict[str, int]


class _State:
    def __init__(self):
        self.on = False
        self.listening = False
        self.done: List[Span] = []
        self.open: List[str] = []      # the spans open, outermost first
        self.compiles: Dict[str, int] = {}


_state = _State()
_OFF = contextlib.nullcontext()


class _Open:
    """One span while it is open."""
    __slots__ = ("name", "ids", "parent", "note", "t0")

    def __init__(self, name: str, ids: Dict[str, int]):
        self.name, self.ids = name, ids

    def __enter__(self):
        stack = _state.open
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.note = jax.profiler.TraceAnnotation(self.name)
        self.note.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.note.__exit__(*exc)
        _state.open.pop()
        _state.done.append(Span(self.name, self.t0, t1, self.parent,
                                self.ids))
        return False


def span(name: str, **ids: int):
    """A context manager around one piece of work: recorded while tracing
    is on, the shared no-op while it is off."""
    if not _state.on:
        return _OFF
    return _Open(name, ids)


def enable(flag: bool) -> None:
    """Turn span recording on or off; spans already kept stay."""
    if not _state.listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _state.listening = True
    _state.on = bool(flag)


def spans() -> List[Span]:
    """The spans closed since the last :func:`reset`, in the order they
    closed (a child before its parent)."""
    return list(_state.done)


def compiles() -> Dict[str, int]:
    """Compilations since the last :func:`reset`, by the innermost span
    open when each happened."""
    return dict(_state.compiles)


def reset() -> None:
    """Forget the spans and compile counts kept so far; spans open now are
    kept when they close."""
    _state.done = []
    _state.compiles = {}


def _count_compile(n: int) -> None:
    where = _state.open[-1] if _state.open else NONE
    _state.compiles[where] = _state.compiles.get(where, 0) + n


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE:
        _count_compile(1)


def _on_event(event: str, **_) -> None:
    # a load from the persistent cache is timed as a compile too
    if event == _CACHE_HIT:
        _count_compile(-1)
