"""Telemetry subsystem: trace-time comm counters validated against the
analytic halo-volume formula and CG's known all-reduce structure,
device-recorded residual histories, zero-cost-when-disabled (identical
lowered HLO), and sink serialization."""

import json
import os
import sys

import numpy as np

from _mp import run

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))


# ---------------------------------------------------------------------------
# pure-Python units (no devices needed)
# ---------------------------------------------------------------------------

def test_halo_slab_bytes_formula():
    """halo_slab_bytes is the analytic 2 * h * prod(face) * itemsize."""
    from repro.telemetry import halo_slab_bytes

    shape = (10, 14, 18)
    for dim in range(3):
        face = np.prod([n for d, n in enumerate(shape) if d != dim])
        for width, itemsize in ((1, 8), (2, 4)):
            assert halo_slab_bytes(shape, dim, width, itemsize) \
                == 2 * width * face * itemsize


def test_counter_snapshot_arithmetic():
    from repro.telemetry import CommStats
    from repro.telemetry.counters import CounterSnapshot

    setup = CounterSnapshot()
    setup.add_halo(0, 100)
    setup.add_all_reduce(3)
    per_it = CounterSnapshot()
    per_it.add_halo(0, 100)
    per_it.add_halo(1, 40)
    per_it.add_all_reduce(1)
    per_it.add_all_reduce(1)

    tot = CommStats(setup, per_it).totals(10)
    assert tot.halo_exchanges == 1 + 10 * 2
    assert tot.halo_bytes == 100 + 10 * 140
    assert tot.all_reduces == 1 + 10 * 2
    assert tot.all_reduce_scalars == 3 + 10 * 2
    assert tot.halo_per_dim[0] == {"exchanges": 11, "bytes": 1100}
    assert tot.halo_per_dim[1] == {"exchanges": 10, "bytes": 400}
    # round-trips through as_dict (json-serializable)
    json.dumps(CommStats(setup, per_it).as_dict(iterations=10))


def test_tag_innermost_collector_only():
    """A nested collector absorbs counts; the outer one stays clean."""
    from repro.telemetry.counters import counting, record_all_reduce, tag

    with counting() as outer:
        record_all_reduce(1)
        with counting() as inner:
            with tag("iteration"):
                record_all_reduce(1)
        record_all_reduce(1)
    assert outer.stats().setup.all_reduces == 2
    assert outer.stats().per_iteration.all_reduces == 0
    assert inner.stats().per_iteration.all_reduces == 1


def test_tag_nested_same_name_unwinds_by_position():
    """Exiting an inner same-name tag must pop ITS stack entry, not the
    first occurrence of the name (list.remove semantics), so counts
    recorded after the inner exit still land in the outer tag."""
    from repro.telemetry.counters import counting, record_all_reduce, tag

    with counting() as col:
        with tag("iteration"):
            with tag("solve"):
                with tag("iteration"):   # same name, nested deeper
                    record_all_reduce(1)
                # inner "iteration" exited: the OUTER one must survive
                assert col.tags == ["iteration", "solve"]
                record_all_reduce(1)
            record_all_reduce(1)
        assert col.tags == []
        record_all_reduce(1)
    assert col.buckets["iteration"].all_reduces == 2
    assert col.buckets["solve"].all_reduces == 1
    assert col.buckets["setup"].all_reduces == 1


def test_a_eff_t_eff():
    from repro.telemetry import a_eff, t_eff

    # heat: T unknown, Ci known, f32 -> 3 bytes/cell/step
    assert a_eff(100, 1, 1, 4) == 3 * 100 * 4
    assert t_eff(2e9, 1.0) == 2.0
    assert np.isnan(t_eff(1.0, 0.0))


def test_sinks_serialize():
    from repro.telemetry import MemorySink, NullSink, session, region, metric

    NullSink().emit({"type": "span"})  # never raises, never stores

    sink = MemorySink()
    with session(sink=sink):
        with region("outer", label="x"):
            with region("inner"):
                pass
            metric("t_eff_gbs", 12.5)
    kinds = [e["type"] for e in sink.events]
    assert kinds == ["span", "metric", "span"]  # inner closes first
    ct = sink.chrome_trace_events()
    assert [e["ph"] for e in ct] == ["X", "i", "X"]
    for e in ct:
        json.dumps(e)
    spans = [e for e in ct if e["ph"] == "X"]
    assert all(e["dur"] >= 0 for e in spans)
    inner, = (e for e in spans if e["name"] == "inner")
    outer, = (e for e in spans if e["name"] == "outer")
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3


def test_chrome_trace_sink_perfetto_loadable(tmp_path):
    """ChromeTraceSink output is Perfetto-loadable: valid JSON, complete
    events with non-negative monotone timestamps, consistent pid/tid."""
    from repro.telemetry import ChromeTraceSink, metric, region, session

    path = tmp_path / "trace.json"
    sink = ChromeTraceSink(str(path))
    with session(sink=sink):
        with region("a"):
            with region("b"):
                pass
            metric("m", 1.0)
        with region("c"):
            pass
    sink.close()
    trace = json.loads(path.read_text())   # must parse as one JSON doc
    assert trace["displayTimeUnit"] == "ms"
    evs = trace["traceEvents"]
    assert all(e["ph"] in ("X", "i") for e in evs)
    for e in evs:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["ts"] >= 0
    assert len({(e["pid"], e["tid"]) for e in evs}) == 1  # one rank here
    spans = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(spans) == {"a", "b", "c"}
    assert all(e["dur"] >= 0 for e in spans.values())
    # spans nest/order consistently on the session clock
    assert spans["a"]["ts"] <= spans["b"]["ts"]
    assert spans["b"]["ts"] + spans["b"]["dur"] \
        <= spans["a"]["ts"] + spans["a"]["dur"] + 1.0   # µs slack
    assert spans["c"]["ts"] >= spans["a"]["ts"] + spans["a"]["dur"] - 1.0
    # the metric instant falls inside its enclosing span
    (inst,) = [e for e in evs if e["ph"] == "i"]
    assert spans["a"]["ts"] <= inst["ts"] \
        <= spans["a"]["ts"] + spans["a"]["dur"] + 1.0


def test_jsonl_sink_empty_session_and_close_twice(tmp_path):
    from repro.telemetry import JsonlSink, session

    empty = tmp_path / "empty.jsonl"
    sink = JsonlSink(str(empty))
    with session(sink=sink):
        pass
    sink.close()
    sink.close()                      # idempotent, must not raise
    assert empty.read_text() == ""    # empty session -> empty file

    full = tmp_path / "one.jsonl"
    sink2 = JsonlSink(str(full))
    with session(sink=sink2) as s:
        s.metric("x", 1.5)
    sink2.close()
    sink2.close()
    lines = full.read_text().splitlines()
    assert len(lines) == 1
    ev = json.loads(lines[0])
    assert ev["type"] == "metric" and ev["name"] == "x" and ev["value"] == 1.5


def test_flight_recorder_composes_with_sessions(tmp_path):
    """flight() is reentrant, mirrors (not steals) session events into
    the per-rank ring buffer, and respects the ring capacity."""
    from repro.telemetry import MemorySink, current_session, flight, \
        region, session
    from repro.telemetry.flight import current as flight_current

    sink = MemorySink()
    with session(sink=sink) as s:
        with flight(str(tmp_path), capacity=4) as rec:
            with flight(str(tmp_path / "ignored")) as rec2:
                assert rec2 is rec               # inner joins the outer
            assert flight_current() is rec       # inner exit: no teardown
            with region("r1"):
                with region("r2"):
                    pass
            assert current_session() is s        # session still the outer one
            for i in range(10):
                rec.record({"type": "tick", "i": i})
        assert flight_current() is None
    # session sink saw the spans untouched (mirroring, not rerouting)
    assert [e["name"] for e in sink.events if e["type"] == "span"] \
        == ["r2", "r1"]
    # ring buffer bounded at capacity, keeping the newest events
    evs = rec.events(rec.host_rank)
    assert len(evs) == 4
    assert [e["i"] for e in evs] == [6, 7, 8, 9]
    # clean exit, no failure -> nothing dumped
    assert rec.dump_count == 0 and not list(tmp_path.glob("flight-*.jsonl"))


def test_flight_recorder_dumps_on_exception(tmp_path):
    from repro.telemetry import flight

    try:
        with flight(str(tmp_path), meta={"app": "t"}) as rec:
            rec.record({"type": "tick", "i": 0})
            raise RuntimeError("boom")
    except RuntimeError:
        pass
    (path,) = sorted(tmp_path.glob("flight-rank*.jsonl"))
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    header, events = lines[0], lines[1:]
    assert header["type"] == "flight_header"
    assert header["reason"] == "exception:RuntimeError"
    assert header["meta"] == {"app": "t"}
    assert "host_peak_rss_kb" in header["memory"]
    kinds = [e["type"] for e in events]
    assert kinds == ["tick", "exception"]
    assert "boom" in events[-1]["error"]


def test_observe_composes_flight_and_watch(tmp_path):
    """tele.observe() = flight + watch, each reentrant; a no-op with
    neither requested."""
    from repro import telemetry as tele
    from repro.telemetry.flight import current as flight_current

    with tele.observe():                          # no-op block
        assert flight_current() is None and not tele.watching()
    with tele.observe(heartbeat=5, flight_dir=str(tmp_path),
                      stagnation_window=7):
        assert tele.watching()
        from repro.telemetry import health
        cfg = health.current()
        assert cfg.heartbeat_every == 5 and cfg.stagnation_window == 7
        rec = flight_current()
        assert rec is not None
        with tele.observe(heartbeat=50, flight_dir=str(tmp_path / "x")):
            assert health.current() is cfg        # inner observe joins
            assert flight_current() is rec
    assert flight_current() is None and not tele.watching()


def test_constructors_start_no_backend():
    """Building a session or a flight recorder must not start a JAX
    backend: on a chip host that would claim the chip for a parent that
    only wants to launch workers.  The rank is read once one is up."""
    run("""
from jax._src import xla_bridge
from repro import telemetry as tele

s = tele.Session()
rec = tele.FlightRecorder("unused")
assert s.rank == 0 and rec.host_rank == 0
assert not xla_bridge.backends_are_initialized()
jax.devices()
assert s.rank == jax.process_index() and rec.host_rank == s.rank
print("OK")
""", ndev=1)


def test_region_noop_without_session():
    from repro.telemetry import current_session, enabled, region

    assert not enabled() and current_session() is None
    with region("nothing"):
        pass  # must not raise, must not require a session


def test_region_events_with_and_without_a_session():
    """With no session a region emits nothing and syncs nothing; under a
    session it emits one span per region, innermost first, with its depth,
    rank and attrs, syncing only on a clean exit."""
    from repro.telemetry import MemorySink, region, session

    synced = []

    def sync():
        synced.append(1)
        return np.zeros(1)

    with region("off", sync=sync):
        pass
    assert synced == []

    sink = MemorySink()
    with session(sink=sink):
        with region("outer", label="x", sync=sync):
            with region("inner"):
                pass
        try:
            with region("raises", sync=sync):
                raise KeyError("x")
        except KeyError:
            pass
    assert synced == [1]                      # not on the raising region
    assert [{k: v for k, v in e.items() if k not in ("ts", "dur")}
            for e in sink.events] == [
        {"type": "span", "name": "inner", "depth": 1, "rank": 0},
        {"type": "span", "name": "outer", "depth": 0, "rank": 0, "label": "x"},
        {"type": "span", "name": "raises", "depth": 0, "rank": 0},
    ]
    inner, outer, _ = sink.events
    assert outer["ts"] <= inner["ts"] and inner["dur"] <= outer["dur"]


def test_regions_and_parallel_calls_reach_the_profiler_trace(tmp_path):
    """A region, session or not, and each call of a ``grid.parallel``
    function are host events of the profiler's trace; the wrapper's
    ``lower`` lowers the program its calls run."""
    run("""
import glob
from jax.profiler import ProfileData
from repro import telemetry as tele
from repro.core import init_global_grid

g = init_global_grid(6, 6, 6, dims=(1, 1, 1))

@g.parallel
def twice(A):
    return 2 * A

A = g.full(1.0)
twice(A).block_until_ready()
assert "multiply" in twice.lower(A).compile().as_text()
with jax.profiler.trace(%r):
    with tele.region("tele.off"):
        twice(A).block_until_ready()
    with tele.session():
        with tele.region("tele.on"):
            twice(A).block_until_ready()
path, = glob.glob(%r + "/**/*.xplane.pb", recursive=True)
host = next(p for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU")
names = [e.name for line in host.lines for e in line.events]
assert names.count("tele.off") == 1 and names.count("tele.on") == 1, names
assert names.count("grid.parallel.twice") == 2, names
print("OK")
""" % (str(tmp_path), str(tmp_path)), ndev=1)


def test_op_scopes_name_each_hide_phase():
    """In the compiled module of a hidden heat step on two ranks, each
    instruction the program made maps to the innermost of its phases:
    the shell's and the interior's slices, step arithmetic and writes, and
    the exchange's permutes under ``halo.update``; what the compiler made
    itself (copies, hoisted constants) maps to nothing."""
    out = run(r"""
import re
from repro import telemetry as tele
from repro.apps.heat3d import Heat3D

app = Heat3D(nx=40, ny=12, nz=12, dims=(2, 1, 1), use_kernel="ref")
T, Ci = app.init_fields()
text = app._step.lower(T, Ci).compile().as_text()
scopes = tele.op_scopes(text)
prims = {s: set() for s in tele.PROGRAM_SCOPES}
unmapped = set()
for line in text.splitlines():
    m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \S+ ([\w\-]+)\(", line)
    if m is None:
        continue
    name, opcode = m.groups()
    op = re.search(r'op_name="([^"]*)"', line)
    parts = op.group(1).split("/") if op else []
    # a JAX primitive as the last part: the program made it
    made = len(parts) > 2 and not re.fullmatch(r"[\w\-]+\.\d+", parts[-1])
    assert (name in scopes) == made, line
    if not made:
        unmapped.add(opcode)
        continue
    inner = [p for p in parts if p in tele.PROGRAM_SCOPES]
    assert scopes[name] == inner[-1], line
    if "hide.exchange" in parts:
        assert scopes[name] == "halo.update", line
    prims[scopes[name]].add(parts[-1])
for phase in ("hide.shell", "hide.interior"):
    assert {"slice", "scatter", "add", "mul"} <= prims[phase], prims[phase]
assert "ppermute" in prims["halo.update"], prims["halo.update"]
assert prims["hide.exchange"] == set()
assert "copy" in unmapped, unmapped
print("ok", sorted(unmapped))
""", ndev=2)
    assert "ok" in out


def test_session_is_reentrant():
    """An inner ``session()`` joins the active one (benchmark harnesses
    open their own session yet compose under ``benchmarks/run.py``'s)."""
    from repro.telemetry import MemorySink, current_session, session

    outer_sink = MemorySink()
    with session(sink=outer_sink) as outer:
        with session(sink=MemorySink()) as inner:  # inner sink ignored
            assert inner is outer
            inner.metric("nested", 1.0)
        assert current_session() is outer  # inner exit must not tear down
    assert current_session() is None
    assert [e["name"] for e in outer_sink.events] == ["nested"]


# ---------------------------------------------------------------------------
# distributed (subprocess, 8 fake devices)
# ---------------------------------------------------------------------------

def test_halo_bytes_match_analytic_formula():
    """Counted bytes of one update_halo == analytic formula per dim, for
    center and face locations, widths 1 and 2."""
    out = run("""
        jax.config.update("jax_enable_x64", True)
        from jax.sharding import PartitionSpec as P
        from repro.core import init_global_grid
        from repro.telemetry import counting, halo_slab_bytes

        g = init_global_grid(10, 12, 14, dims=(2, 2, 2))

        def one(A):
            return g.update_halo(A)

        sm = jax.shard_map(one, mesh=g.mesh, in_specs=(g.spec,),
                           out_specs=g.spec, check_vma=False)
        A = g.zeros()
        with counting() as col:
            jax.eval_shape(sm, A)
        snap = col.stats().setup
        local = g.local_shape
        item = jnp.dtype(g.dtype).itemsize
        assert snap.halo_exchanges == 3, snap.halo_exchanges
        for d in range(3):
            want = halo_slab_bytes(local, d, g.halo, item)
            got = snap.halo_per_dim[d]["bytes"]
            assert got == want, (d, got, want)
        assert snap.halo_bytes == sum(
            halo_slab_bytes(local, d, g.halo, item) for d in range(3))

        # a face-located field counts identically (shape-uniform staggering)
        from repro import fields
        F = fields.zeros(g, "xface")
        def onef(F):
            return fields.update_halo(g, F)
        smf = jax.shard_map(onef, mesh=g.mesh, in_specs=(g.spec,),
                            out_specs=g.spec, check_vma=False)
        with counting() as colf:
            jax.eval_shape(smf, F)
        assert colf.stats().setup.halo_bytes == snap.halo_bytes

        # width-2 exchange scales bytes by 2
        g2 = init_global_grid(10, 12, 14, dims=(2, 2, 2), overlap=4)
        def two(A):
            return g2.update_halo(A)
        sm2 = jax.shard_map(two, mesh=g2.mesh, in_specs=(g2.spec,),
                            out_specs=g2.spec, check_vma=False)
        with counting() as col2:
            jax.eval_shape(sm2, g2.zeros())
        snap2 = col2.stats().setup
        for d in range(3):
            assert snap2.halo_per_dim[d]["bytes"] == \
                halo_slab_bytes(g2.local_shape, d, 2, item)
        print("ok")
    """)
    assert "ok" in out


def test_cg_all_reduce_and_residual_history():
    """Plain CG: exactly 2 all-reduces and 1 halo exchange per dim per
    iteration; residuals device-recorded, last == relres, monotone-ish."""
    out = run("""
        jax.config.update("jax_enable_x64", True)
        from repro import telemetry as tele
        from repro.apps.poisson import Poisson3D

        app = Poisson3D(nx=10, ny=10, nz=10, dims=(2, 2, 2))
        with tele.session():
            x, info = app.solve(method="cg", tol=1e-8)
        c = info.comm
        assert c is not None
        # CG's known structure: alpha denominator + rz_new (res reuses
        # rz_new for the unpreconditioned method)
        assert c.per_iteration.all_reduces == 2, c.per_iteration.all_reduces
        # one operator application -> one halo update -> 3 dims
        assert c.per_iteration.halo_exchanges == 3
        # setup: bnorm + rz + res0, initial apply_A + final halo refresh
        assert c.setup.all_reduces == 3, c.setup.all_reduces
        assert c.setup.halo_exchanges == 6

        r = info.residuals
        assert len(r) == info.iterations
        assert np.isclose(r[-1], info.relres)
        assert np.all(r > 0)
        # monotone-ish: CG residuals may wiggle, but never explode
        assert np.all(np.diff(np.log(r)) < 2.0)
        assert r[-1] < r[0]

        # preconditioned CG fuses <r, z> and <r, r> into ONE batched
        # all-reduce (tree_dot_many), so it matches plain CG's count
        with tele.session():
            x, info2 = app.solve(method="mgcg", tol=1e-8)
        assert info2.comm.per_iteration.all_reduces == 2
        # ...but that fused reduce carries 2 scalars (+1 for alpha)
        assert info2.comm.per_iteration.all_reduce_scalars == 3
        assert np.isclose(info2.residuals[-1], info2.relres)

        # wall clock recorded and sane
        assert info.wall_s is not None and info.wall_s > 0
        assert info.s_per_iter() > 0
        print("ok")
    """)
    assert "ok" in out


def test_pipecg_single_all_reduce_per_iteration():
    """Pipelined CG: the headline claim, COUNTED not asserted — exactly
    ONE all-reduce per iteration (carrying 3 fused scalars), plus a
    separate per-replacement bucket for the residual-replacement
    recomputations."""
    out = run("""
        jax.config.update("jax_enable_x64", True)
        from repro import telemetry as tele
        from repro.apps.poisson import Poisson3D
        from repro.solvers.cg import replacement_count

        app = Poisson3D(nx=10, ny=10, nz=10, dims=(2, 2, 2))
        with tele.session():
            x, info = app.solve(method="pipecg", tol=1e-8)
        c = info.comm
        assert c is not None
        # THE claim of the variant: one fused reduction per iteration...
        assert c.per_iteration.all_reduces == 1, c.per_iteration.all_reduces
        # ...carrying gamma=<r,u>, delta=<w,u> and ||r||^2 together
        assert c.per_iteration.all_reduce_scalars == 3
        # one operator apply per iteration (m = M w is free here: no M)
        assert c.per_iteration.halo_exchanges == 3
        # setup: bnorm + the initial fused reduction
        assert c.setup.all_reduces == 2, c.setup.all_reduces
        # a replacement segment recomputes r, w, s, z (4 operator
        # applies -> 12 dim-exchanges) but performs NO reductions
        assert c.per_replacement.all_reduces == 0
        assert c.per_replacement.halo_exchanges == 12
        assert info.replacements == replacement_count(info.iterations, 50)
        tot = c.totals(info.iterations, info.replacements)
        assert tot.all_reduces == 2 + info.iterations
        assert np.isclose(info.residuals[-1], info.relres)

        # preconditioned pipelined CG keeps the single fused reduction
        with tele.session():
            x2, info2 = app.solve(method="pipemgcg", tol=1e-8)
        assert info2.comm.per_iteration.all_reduces == 1
        assert info2.comm.per_iteration.all_reduce_scalars == 3
        print("ok")
    """)
    assert "ok" in out


def test_comm_totals_and_repeat_solves_cached():
    """totals() = setup + k * per_iteration; the comm re-trace is cached
    so a repeat solve reuses the same CommStats object."""
    out = run("""
        jax.config.update("jax_enable_x64", True)
        from repro import telemetry as tele
        from repro.apps.poisson import Poisson3D

        app = Poisson3D(nx=10, ny=10, nz=10, dims=(2, 2, 2))
        with tele.session():
            _, a = app.solve(method="cg", tol=1e-8)
            _, b = app.solve(method="cg", tol=1e-8)
        assert a.comm is b.comm  # cached in grid._jit_cache
        tot = a.comm.totals(a.iterations)
        assert tot.all_reduces == 3 + 2 * a.iterations
        assert tot.halo_exchanges == 6 + 3 * a.iterations
        print("ok")
    """)
    assert "ok" in out


def test_zero_cost_when_disabled():
    """The lowered HLO of a solve is bit-identical with telemetry on or
    off, and an active session adds no jit traces on the hot solve path."""
    out = run("""
        jax.config.update("jax_enable_x64", True)
        from jax.sharding import PartitionSpec as P
        from repro import telemetry as tele
        from repro.core import init_global_grid
        from repro.solvers import reductions as red

        g = init_global_grid(10, 10, 10, dims=(2, 2, 2))

        def work(A):
            A = g.update_halo(A)
            return red.psum(g.topo, jnp.sum(A))

        def lower():
            sm = jax.shard_map(work, mesh=g.mesh, in_specs=(g.spec,),
                               out_specs=P(), check_vma=False)
            return jax.jit(sm).lower(g.zeros()).as_text()

        plain = lower()
        with tele.session():
            with tele.counting():
                instrumented = lower()
        assert plain == instrumented, "telemetry changed the lowered HLO"

        # no extra traces on repeat instrumented solves: the same compiled
        # executable and the cached CommStats are reused
        from repro.apps.poisson import Poisson3D
        app = Poisson3D(nx=10, ny=10, nz=10, dims=(2, 2, 2))
        app.solve(method="cg", tol=1e-8)          # warm up (compile)
        n0 = len(app.grid._jit_cache)
        with tele.session():
            app.solve(method="cg", tol=1e-8)      # adds ONE comm entry
            n1 = len(app.grid._jit_cache)
            app.solve(method="cg", tol=1e-8)      # adds nothing
            n2 = len(app.grid._jit_cache)
        assert n1 == n0 + 1 and n2 == n1, (n0, n1, n2)
        print("ok")
    """)
    assert "ok" in out


def test_zero_cost_health_probes_when_unwatched():
    """Solver HLO with a session (no watch) is byte-identical to the
    plain lowering; a watch() compiles a separate program under its own
    cache key without invalidating the plain one."""
    out = run("""
        jax.config.update("jax_enable_x64", True)
        from repro import telemetry as tele
        from repro.apps.poisson import Poisson3D

        app = Poisson3D(nx=10, ny=10, nz=10, dims=(2, 2, 2))
        app.solve(method="cg", tol=1e-8)
        key, = [k for k in app.grid._jit_cache if k[0] == "solvers.cg"]
        assert key[-1] is None          # no HealthConfig in the plain key
        jf = app.grid._jit_cache[key]
        x0 = jnp.zeros_like(app.b)
        plain = jf.lower(app.b, x0, app.c).as_text()

        # re-lowering under an active session + counting must not change
        # one instruction — the health probes are compiled out entirely
        with tele.session(), tele.counting():
            instrumented = jf.lower(app.b, x0, app.c).as_text()
        assert plain == instrumented, "health probes leaked into plain HLO"

        # a watch retraces under a config-extended key; the plain entry
        # survives untouched and the watched program differs (the carry
        # gains the probe state)
        n0 = len(app.grid._jit_cache)
        with tele.watch(heartbeat_every=10):
            _, info = app.solve(method="cg", tol=1e-8)
        assert info.status == tele.SolveStatus.CONVERGED
        wkeys = [k for k in app.grid._jit_cache
                 if k[0] == "solvers.cg" and k[-1] is not None]
        assert len(wkeys) == 1 and len(app.grid._jit_cache) == n0 + 1
        watched = app.grid._jit_cache[wkeys[0]].lower(
            app.b, x0, app.c).as_text()
        assert watched != plain
        assert jf.lower(app.b, x0, app.c).as_text() == plain
        print("ok")
    """)
    assert "ok" in out


def test_health_statuses_and_heartbeats():
    """Device-side probes: CONVERGED with rank-0 heartbeats + one final
    health event per rank, MAX_ITERATIONS, and STAGNATED early exit."""
    out = run("""
        jax.config.update("jax_enable_x64", True)
        from repro import telemetry as tele
        from repro.apps.poisson import Poisson3D

        app = Poisson3D(nx=10, ny=10, nz=10, dims=(2, 2, 2))

        # host classification is always on, even unwatched
        _, plain = app.solve(method="cg", tol=1e-8)
        assert plain.status == tele.SolveStatus.CONVERGED

        sink = tele.MemorySink()
        with tele.session(sink=sink), tele.watch(heartbeat_every=10):
            _, w = app.solve(method="cg", tol=1e-8)
        jax.effects_barrier()
        assert w.status == tele.SolveStatus.CONVERGED
        assert w.iterations == plain.iterations   # probes don't change math
        assert np.isclose(w.relres, plain.relres)

        hb = [e for e in sink.events if e.get("type") == "heartbeat"]
        assert hb, "no heartbeat events"
        assert all(e["rank"] == 0 for e in hb)          # rank-0 throttled
        assert all(e["iteration"] % 10 == 0 for e in hb)
        assert len(hb) == w.iterations // 10
        assert all(np.isfinite(e["relres"]) for e in hb)

        finals = [e for e in sink.events if e.get("type") == "health"]
        assert {e["rank"] for e in finals} == set(range(8))  # every rank
        assert all(e["status"] == "CONVERGED" for e in finals)
        assert all(len(e["residual_tail"]) == 8 for e in finals)
        assert np.isclose(finals[0]["residual_tail"][-1], w.relres)

        # benign maxiter exit
        with tele.watch():
            _, m = app.solve(method="cg", tol=1e-14, maxiter=3)
        assert m.status == tele.SolveStatus.MAX_ITERATIONS
        assert m.iterations == 3

        # stagnation: demand 10x improvement every 5 iterations — CG
        # can't, so the watchdog exits the loop early
        with tele.watch(stagnation_window=5, stagnation_rtol=0.9):
            _, s = app.solve(method="cg", tol=1e-30, maxiter=500)
        assert s.status == tele.SolveStatus.STAGNATED
        assert s.iterations < 20, s.iterations

        # the probes ride along in mg and pt too
        with tele.watch(heartbeat_every=50):
            _, img = app.solve(method="mg", tol=1e-8)
            _, ipt = app.solve(method="pt", tol=1e-8)
        assert img.status == tele.SolveStatus.CONVERGED
        assert ipt.status == tele.SolveStatus.CONVERGED
        print("ok")
    """)
    assert "ok" in out


def test_multigrid_and_pt_histories():
    """mg and pt records: history length == iterations; mg's last entry
    is the relative residual; pt keeps its absolute-norm convention."""
    out = run("""
        jax.config.update("jax_enable_x64", True)
        from repro import telemetry as tele
        from repro.apps.poisson import Poisson3D

        app = Poisson3D(nx=10, ny=10, nz=10, dims=(2, 2, 2))
        with tele.session():
            _, mg = app.solve(method="mg", tol=1e-8)
            _, pt = app.solve(method="pt", tol=1e-8)
        assert len(mg.residuals) == mg.iterations
        assert np.isclose(mg.residuals[-1], mg.relres)
        assert mg.comm.per_iteration.all_reduces >= 1
        assert mg.comm.per_iteration.halo_exchanges > 3  # V-cycle levels

        assert len(pt.residuals) == pt.iterations
        assert pt.residuals[-1] < pt.residuals[0]   # absolute norms
        assert pt.comm.per_iteration.all_reduces == 1
        assert pt.comm.per_iteration.halo_exchanges == 3
        print("ok")
    """)
    assert "ok" in out
