"""Solver telemetry — the observability layer of the reproduction.

The paper's headline evidence is a *measured* number: the effective
memory throughput ``T_eff = A_eff / t_it`` and the fraction of halo
communication hidden behind compute are what back the near-ideal
weak-scaling claims.  This package makes those numbers first-class:

* :mod:`timers`   — nestable region timers (``block_until_ready``-synced,
  per-rank) emitting span events;
* :mod:`counters` — communication counters with **zero device cost**:
  :func:`repro.core.halo.update_halo` and the all-reduces of
  :mod:`repro.solvers.reductions` report into a trace-time collector, so
  counting a compiled solve is one abstract re-trace
  (:func:`count_comm`) — no instruction is added to the hot path and the
  lowered HLO is bit-identical with telemetry on or off (pinned by
  ``tests/test_telemetry.py``);
* :mod:`metrics`  — the paper's ``A_eff``/``T_eff`` convention;
* :mod:`scopes`   — which program scope (``hide.*``, ``halo.update``)
  each instruction of a compiled module belongs to, to read a profiler
  trace phase by phase (:func:`op_scopes`);
* :mod:`sink`     — structured sinks: a no-op default, an in-memory
  recorder, JSONL metric events, and a Chrome-trace/Perfetto span export
  (load the file at ``ui.perfetto.dev`` or ``chrome://tracing``).

Everything is **off by default**: with no active session the hooks are a
single falsy check.  A benchmark enables it as::

    from repro import telemetry as tele

    with tele.session(meta={"bench": "solvers"}) as s:
        with tele.region("solve", sync=lambda: u):
            u, info = app.solve("mgcg")
        s.metric("t_eff_gbs", tele.t_eff(a_eff_bytes, info.s_per_iter()))
    s.sink.dump_jsonl("metrics.jsonl")
    s.sink.dump_chrome_trace("trace.json")

Per-solve communication totals ride on the solvers themselves: every
``SolveInfo`` carries a device-recorded residual history, the solve wall
time, and — when :func:`counting` is active — a :class:`CommStats` whose
per-iteration halo bytes and all-reduce counts are exact (validated
against the analytic halo-volume formula ``2 * halo * prod(face) *
itemsize`` per dim).
"""

import contextlib as _contextlib

from .counters import (
    CommStats, CounterSnapshot, counting, counting_enabled, count_comm,
    halo_slab_bytes, record_all_reduce, record_halo, tag,
)
from .flight import FlightRecorder, flight
from .health import HealthConfig, SolveStatus, watch, watching
from .metrics import a_eff, t_eff
from .scopes import PROGRAM_SCOPES, op_scopes
from .sink import ChromeTraceSink, JsonlSink, MemorySink, NullSink
from .timers import (
    Session, current_session, enabled, metric, region, session, trace_span,
)


@_contextlib.contextmanager
def observe(*, heartbeat: int = 0, flight_dir: str | None = None,
            flight_capacity: int = 256, meta: dict | None = None, **watch_kw):
    """One-stop runtime observability: flight recorder + health watch.

    ``heartbeat > 0`` installs solve-health watchdogs (:func:`watch`)
    with a rank-0 heartbeat every that many iterations; ``flight_dir``
    installs a per-rank flight recorder dumping there.  Both are
    reentrant, so app-level observe blocks compose under an outer
    session/watch.  With neither requested this is a no-op block.
    """
    with _contextlib.ExitStack() as stack:
        if flight_dir:
            stack.enter_context(flight(flight_dir, capacity=flight_capacity,
                                       meta=meta))
        if heartbeat or watch_kw:
            stack.enter_context(watch(heartbeat_every=heartbeat, **watch_kw))
        yield


__all__ = [
    "CommStats", "CounterSnapshot", "counting", "counting_enabled",
    "count_comm", "halo_slab_bytes", "record_all_reduce", "record_halo",
    "tag",
    "FlightRecorder", "flight",
    "HealthConfig", "SolveStatus", "watch", "watching",
    "a_eff", "t_eff",
    "ChromeTraceSink", "JsonlSink", "MemorySink", "NullSink",
    "PROGRAM_SCOPES", "op_scopes",
    "Session", "current_session", "enabled", "metric", "region", "session",
    "trace_span", "observe",
]
