"""Named host spans and counters of the sweep's stages.

While the JAX profiler is on, ``with span(name, **counts):`` opens a
``jax.profiler.TraceAnnotation`` (the calling thread's host line, on the
device trace's clock) and adds the span's duration, self time (duration
less its child spans) and counts to an in-memory table of totals;
``count(name, n)`` and every backend compile (``compiles``,
``compile_s``) add to the innermost open span.  ``snapshot()`` is the
table of the latest profiler session.  With the profiler off a span is
one check and records nothing.  Nothing here imports JAX.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_OFF = contextlib.nullcontext()


class _Local(threading.local):
    def __init__(self):
        self.stack: list[_Span] = []        # this thread's open spans


class _State:
    lock = threading.Lock()
    table: dict[str, dict] = {}
    session: object = object()    # the profiler session the table is of
    listening = False


_local = _Local()


def enabled() -> bool:
    """True while the JAX profiler records."""
    jax = sys.modules.get("jax")
    return jax is not None and jax.profiler.TraceAnnotation.is_enabled()


def _on_compile(event: str, duration: float, **kw) -> None:
    if event == _COMPILE_EVENT and enabled():
        count("compiles", 1)
        count("compile_s", duration)


class _Span:
    __slots__ = ("name", "counts", "child_s", "t0", "note")

    def __init__(self, name: str, counts: dict):
        self.name, self.counts, self.child_s = name, counts, 0.0

    def __enter__(self):
        import jax
        from jax._src import profiler

        with _State.lock:
            if not _State.listening:
                jax.monitoring.register_event_duration_secs_listener(
                    _on_compile)
                _State.listening = True
            # one session object per start_trace; None for a remote capture
            session = profiler._profile_state.profile_session
            if not _local.stack and session is not _State.session:
                _State.table, _State.session = {}, session
        self.note = jax.profiler.TraceAnnotation(self.name)
        self.note.__enter__()
        _local.stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        stack = _local.stack
        stack.pop()
        self.note.__exit__(*exc)
        if stack:
            stack[-1].child_s += dur
        with _State.lock:
            row = _State.table.setdefault(
                self.name, {"n": 0, "total_s": 0.0, "self_s": 0.0})
            row["n"] += 1
            row["total_s"] += dur
            row["self_s"] += dur - self.child_s
            for k, v in self.counts.items():
                row[k] = row.get(k, 0) + v


def span(name: str, **counts):
    """Context manager: a named host span carrying ``counts``."""
    return _Span(name, counts) if enabled() else _OFF


def count(name: str, n) -> None:
    """Add ``n`` to counter ``name`` of the innermost open span."""
    if _local.stack:
        top = _local.stack[-1].counts
        top[name] = top.get(name, 0) + n


def snapshot() -> dict[str, dict]:
    """``{span: {"n", "total_s", "self_s", <counts>}}`` of the most
    recent profiler session."""
    with _State.lock:
        return {k: dict(v) for k, v in _State.table.items()}
