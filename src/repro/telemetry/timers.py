"""Region timers and the telemetry session.

A :class:`Session` owns a sink and a monotonic clock origin; it is
installed module-wide by the :func:`session` context manager (or
``Session.start()``).  With no session installed, :func:`region` and
:func:`metric` cost one falsy check — the hot solve path is untouched
(``tests/test_telemetry.py`` pins identical lowered HLO).

Regions are nestable and **synced**: JAX dispatch is asynchronous, so a
bare ``perf_counter`` pair around a jitted call times the dispatch, not
the work.  ``region(name, sync=...)`` calls ``jax.block_until_ready`` on
the value (or the result of the callable) before closing the span.
Ranks: under the single-controller runtimes used here the host is rank
``jax.process_index()``; spans carry it so multi-process traces merge
into one Perfetto timeline with a row per rank.
"""

from __future__ import annotations

import contextlib
import time

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

    def counter(self, name: str, snapshot: dict, **attrs):
        self.emit({"type": "counter", "name": name, "rank": self.rank,
                   **snapshot, **attrs})

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


@contextlib.contextmanager
def region(name: str, *, sync=None, **attrs):
    """Time a region; emits a span event to the active session.

    ``sync`` — an array/pytree (or a zero-arg callable returning one)
    blocked on before the span closes, so asynchronously dispatched
    device work is charged to the region that launched it.  No-op (single
    falsy check, no sync) when no session is active.
    """
    s = _CURRENT
    if s is None:
        yield
        return
    s._depth += 1
    t0 = s.now()
    try:
        yield
        if sync is not None:
            _sync(sync)
    finally:
        s._depth -= 1
        t1 = s.now()
        s.span(name, t0, t1 - t0, **attrs)


def metric(name: str, value, **attrs):
    """Emit a metric event to the active session (no-op when disabled)."""
    if _CURRENT is not None:
        _CURRENT.metric(name, value, **attrs)


__all__ = ["Session", "current_session", "enabled", "metric", "region",
           "session", "MemorySink", "NullSink"]
