"""Host spans and compile attribution for the kNN query path.

``span(name)`` opens a ``jax.profiler.TraceAnnotation``: recorded only
while a profiler session runs, on the same host plane and clock as the
device ops, so a trace names each idle stretch of the device by the
innermost span open over it.  Outside a profiler session a span costs a
microsecond or two; there is no other switch.

Each thread also keeps a stack of its open span names.  One
process-wide ``jax.monitoring`` listener charges every program JAX
compiles, or loads from its persistent cache, to the innermost open
``knn.*`` span of the compiling thread (``OUTSIDE`` when none is open).
``snapshot()`` before a call and ``since(before)`` after it give the
call's programs, their seconds, and which span asked for them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Tuple

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
PREFIX = "knn."
OUTSIDE = "(outside)"

_local = threading.local()
_lock = threading.Lock()
_tally: Dict[str, Tuple[int, float]] = {}   # span -> (programs, seconds)
_listening = False


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


@contextlib.contextmanager
def span(name: str):
    """A named host span over the ``with`` body."""
    stack = _stack()
    stack.append(name)
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        stack.pop()


def _on_duration(event: str, duration_secs: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    key = next((n for n in reversed(_stack()) if n.startswith(PREFIX)),
               OUTSIDE)
    with _lock:
        n, s = _tally.get(key, (0, 0.0))
        _tally[key] = (n + 1, s + duration_secs)


@dataclasses.dataclass
class Compiles:
    """Programs compiled or loaded in an interval, and their seconds."""

    programs: int
    seconds: float
    by_span: Dict[str, int]


def snapshot() -> Dict[str, Tuple[int, float]]:
    """The running tally; registers the listener on first use."""
    global _listening
    with _lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True
        return dict(_tally)


def since(before: Dict[str, Tuple[int, float]]) -> Compiles:
    """What was compiled or loaded since ``before = snapshot()``, on any
    thread of the process."""
    with _lock:
        now = dict(_tally)
    by_span, seconds = {}, 0.0
    for key, (n, s) in now.items():
        n0, s0 = before.get(key, (0, 0.0))
        if n > n0:
            by_span[key] = n - n0
            seconds += s - s0
    return Compiles(sum(by_span.values()), seconds, by_span)
