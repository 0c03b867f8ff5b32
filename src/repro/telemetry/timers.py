"""Region timers and the telemetry session.

A :class:`Session` owns a sink and a monotonic clock origin; it is
installed module-wide by the :func:`session` context manager (or
``Session.start()``).  With no session installed, :func:`metric` costs
one falsy check and :func:`region` one profiler annotation besides — the
hot solve path is untouched (``tests/test_telemetry.py`` pins identical
lowered HLO).

Regions are nestable and **synced**: JAX dispatch is asynchronous, so a
bare ``perf_counter`` pair around a jitted call times the dispatch, not
the work.  ``region(name, sync=...)`` calls ``jax.block_until_ready`` on
the value (or the result of the callable) before closing the span.
Ranks: under the single-controller runtimes used here the host is rank
``jax.process_index()``; spans carry it so multi-process traces merge
into one Perfetto timeline with a row per rank.

Every region is also a ``jax.profiler.TraceAnnotation``, session or
not: under ``jax.profiler.trace`` it lands on the profiler's host plane,
on one timeline with the device's ops.  :func:`trace_span` is that
annotation alone, for per-call spans on a hot path (no session event).
"""

from __future__ import annotations

import contextlib
import time

from jax.profiler import TraceAnnotation as _TraceAnnotation

from .sink import MemorySink, NullSink


def host_rank() -> int:
    """This host's process index, read only from a backend that is
    already up (0 before then).  ``jax.process_index()`` would start a
    backend itself, and on a chip host that claims the chip for this
    process, so a parent that only builds a session must never call it.
    """
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return 0
    import jax

    return jax.process_index()


class Session:
    """An active telemetry session: clock origin + sink + span stack."""

    def __init__(self, sink=None, meta: dict | None = None):
        self.sink = MemorySink() if sink is None else sink
        self.meta = dict(meta or {})
        self.t0 = time.perf_counter()
        self._depth = 0

    @property
    def rank(self) -> int:
        return host_rank()

    # -- event emission ------------------------------------------------
    def now(self) -> float:
        return time.perf_counter() - self.t0

    def emit(self, event: dict):
        self.sink.emit(event)
        # mirror into the flight recorder's per-rank ring buffer (a single
        # None check when no recorder is installed)
        from .flight import current as _flight_current
        rec = _flight_current()
        if rec is not None:
            rec.record(event)

    def span(self, name: str, ts: float, dur: float, **attrs):
        self.emit({"type": "span", "name": name, "ts": ts, "dur": dur,
                   "depth": self._depth, "rank": self.rank, **attrs})

    def metric(self, name: str, value, **attrs):
        self.emit({"type": "metric", "name": name, "value": value,
                   "ts": self.now(), "rank": self.rank, **attrs})

    # -- lifecycle -----------------------------------------------------
    def start(self) -> "Session":
        global _CURRENT
        if _CURRENT is not None:
            raise RuntimeError("a telemetry session is already active")
        _CURRENT = self
        return self

    def stop(self):
        global _CURRENT
        if _CURRENT is self:
            _CURRENT = None


_CURRENT: Session | None = None


def current_session() -> Session | None:
    return _CURRENT


def enabled() -> bool:
    return _CURRENT is not None


@contextlib.contextmanager
def session(sink=None, meta: dict | None = None):
    """Install a telemetry session for the duration of the block.

    Reentrant: if a session is already active, the block joins it (the
    inner ``sink``/``meta`` are ignored) — a benchmark harness can open
    its own session and still compose under ``benchmarks/run.py``'s
    outer one.  Use ``Session(...).start()`` to insist on exclusivity.
    """
    if _CURRENT is not None:
        yield _CURRENT
        return
    s = Session(sink=sink, meta=meta).start()
    try:
        yield s
    finally:
        s.stop()


def _sync(value):
    import jax

    jax.block_until_ready(value() if callable(value) else value)


def region(name: str, *, sync=None, **attrs):
    """Time a region; emits a span event to the active session.

    ``sync`` — an array/pytree (or a zero-arg callable returning one)
    blocked on before the span closes, so asynchronously dispatched
    device work is charged to the region that launched it.  Session or
    not, the region is a ``TraceAnnotation`` of its name on the
    profiler's timeline (a no-op while no profiler trace is taken); with
    no session active that annotation is all it is: no event, no sync.
    """
    s = _CURRENT
    if s is None:
        return _TraceAnnotation(name)
    return _SessionRegion(s, name, sync, attrs)


class _SessionRegion:
    """A region under an active session: the annotation, plus a span
    event when it closes."""

    __slots__ = ("s", "name", "sync", "attrs", "ann", "t0")

    def __init__(self, s: Session, name: str, sync, attrs: dict):
        self.s, self.name, self.sync, self.attrs = s, name, sync, attrs

    def __enter__(self):
        self.ann = _TraceAnnotation(self.name)
        self.ann.__enter__()
        self.s._depth += 1
        self.t0 = self.s.now()

    def __exit__(self, exc_type, exc, tb):
        s = self.s
        try:
            if exc_type is None and self.sync is not None:
                _sync(self.sync)
        finally:
            self.ann.__exit__(exc_type, exc, tb)
            s._depth -= 1
            s.span(self.name, self.t0, s.now() - self.t0, **self.attrs)


# A span on the profiler's timeline only, with no session event, for
# per-call spans on a hot path: under a microsecond a use while no trace
# is taken.
trace_span = _TraceAnnotation


def metric(name: str, value, **attrs):
    """Emit a metric event to the active session (no-op when disabled)."""
    if _CURRENT is not None:
        _CURRENT.metric(name, value, **attrs)


__all__ = ["Session", "current_session", "enabled", "metric", "region",
           "session", "trace_span", "MemorySink", "NullSink"]
