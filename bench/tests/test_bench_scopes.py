"""The program's scopes and spans, read from a trace recorded on a TPU v5e
chip with scoped names (``data/heat3d-256.hide.scoped.xplane.pb``: one
chunk of hidden heat steps at 256^3, made by ``tools/record_trace.py``)
and its step's compiled module (``data/heat3d-256.hide.scoped.hlo.txt``,
made by ``tools/record_hlo.py``).  The raw events carry each op's full
instruction name, so they give the exact join that ``harness.scopes``
reaches by order."""

import dataclasses
import json
import math
import os
import re
import subprocess
import sys
import types

import pytest

from _sub import BENCH
from harness import scopes, spec
from harness import trace as tr

ROOT = os.path.dirname(BENCH)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FIXTURE = os.path.join(DATA, "heat3d-256.hide.scoped.xplane.pb")
HLO = os.path.join(DATA, "heat3d-256.hide.scoped.hlo.txt")
PHASES = ("hide.shell", "hide.exchange", "hide.interior")
SPAN = "grid.parallel.dstep"


@pytest.fixture(scope="module")
def text():
    with open(HLO) as f:
        return f.read()


@pytest.fixture(scope="module")
def trace():
    return tr.Trace.load(FIXTURE)


@pytest.fixture(scope="module")
def raw():
    """Device 0's ops as ``(duration ns, full instruction name, opcode)``
    and the host's events as ``(name, start, end)``, read with nothing but
    the profiler's own reader."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(FIXTURE)
    dev = next(p for p in data.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in dev.lines if ln.name == "XLA Ops")
    ops = [(e.duration_ns, e.name.split(" = ")[0].lstrip("%"),
            tr.parse_hlo(e.name)[1]) for e in line.events]
    host = next(p for p in data.planes if p.name == "/host:CPU")
    events = [(e.name, e.start_ns, e.end_ns)
              for ln in host.lines for e in ln.events]
    return types.SimpleNamespace(ops=ops, host=events)


@pytest.fixture(scope="module")
def steps(raw):
    return sum(1 for name, _, _ in raw.host if name == "bench.step")


def _run(trace, text, steps):
    return types.SimpleNamespace(trace=trace, hlo_text=text, app=None,
                                 window={"units": steps})


def test_every_device_op_falls_in_one_phase_or_is_unscoped(text, raw):
    scope_of = scopes.instruction_scopes(text)
    ran = {n for names in scopes.schedule(text).values() for n in names}
    seen = {p: 0 for p in PHASES + (None,)}
    for _, name, _ in raw.ops:
        assert name in ran, name
        line = next(x for x in text.splitlines()
                    if re.match(rf"\s*(ROOT )?%{re.escape(name)} = ", x))
        op_name = re.search(r'op_name="([^"]*)"', line)
        parts = op_name.group(1).split("/") if op_name else []
        assert sum(p in PHASES for p in parts) <= 1, line
        seen[scope_of.get(name)] += 1
    assert seen["hide.shell"] and seen["hide.interior"]
    assert seen["hide.exchange"] == 0      # one chip: nothing to exchange
    assert sum(seen.values()) == len(raw.ops)


def test_unscoped_ops_are_copies_the_compiler_inserted(text, raw):
    scope_of = scopes.instruction_scopes(text)
    unscoped = {(name, opcode) for _, name, opcode in raw.ops
                if name not in scope_of}
    assert unscoped
    for name, opcode in unscoped:
        assert opcode in ("copy", "copy-start", "copy-done"), name
        line = next(x for x in text.splitlines()
                    if re.match(rf"\s*(ROOT )?%{re.escape(name)} = ", x))
        assert "op_name=" not in line, line


def test_one_dispatch_span_per_step(text, raw, steps):
    scope_of = scopes.instruction_scopes(text)
    launches = [name for _, name, opcode in raw.ops
                if opcode == "custom-call" and name.startswith("stencil3d_heat")]
    assert len(launches) == 7 * steps
    assert sum(scope_of[n] == "hide.interior" for n in launches) == steps
    spans = [(s, e) for name, s, e in raw.host if name == SPAN]
    outer = [(s, e) for name, s, e in raw.host if name == "bench.step"]
    assert len(spans) == steps > 0
    assert all(any(a <= s and e <= b for a, b in outer) for s, e in spans)


@pytest.mark.parametrize("metric,scope", [("shell_ms.heat", "hide.shell"),
                                          ("interior_ms.heat", "hide.interior")])
def test_scope_readers_equal_hand_sums(text, trace, raw, steps, metric, scope):
    scope_of = scopes.instruction_scopes(text)
    want = sum(d for d, name, _ in raw.ops if scope_of.get(name) == scope)
    got = spec.load_module("metrics", metric).read(_run(trace, text, steps))
    assert got == pytest.approx(want * 1e-6 / steps, rel=1e-12)


@pytest.mark.parametrize("fault,why", [("dropped", "instructions"),
                                       ("renamed", "not in the module")])
def test_a_join_that_fails_names_the_op_kind(text, trace, capsys, fault,
                                             why):
    """A scope metric left out of a run's line is explained on stderr:
    an op dropped from the trace, or one the module does not hold."""
    ops = list(trace.devices["/device:TPU:0"])
    i = next(k for k, o in enumerate(ops)
             if o.opcode == "dynamic-update-slice")
    if fault == "dropped":
        ops = ops[:i] + ops[i + 1:]
    else:
        ops[i] = dataclasses.replace(ops[i], name="no-such-instruction")
    cut = dataclasses.replace(trace, devices={"/device:TPU:0": ops})
    assert scopes.join(cut, text) is None
    err = capsys.readouterr().err
    assert err.startswith("[scopes] no join on /device:TPU:0: "
                          "dynamic-update-slice "), err
    assert why in err and err.count("\n") == 1


def test_harness_and_program_read_the_same_scopes(text):
    from repro import telemetry as tele

    assert scopes.SCOPES == tele.PROGRAM_SCOPES
    assert scopes.instruction_scopes(text) == tele.op_scopes(text)


def test_join_reads_none_when_an_op_is_missing(text, trace, steps):
    """An op of a kind several instructions share, dropped from the
    trace, leaves counts the instructions do not divide."""
    ops = list(trace.devices["/device:TPU:0"])
    i = next(k for k, o in enumerate(ops)
             if o.opcode == "dynamic-update-slice")
    cut = dataclasses.replace(trace, devices={"/device:TPU:0":
                                              ops[:i] + ops[i + 1:]})
    assert scopes.join(cut, text) is None
    assert scopes.scope_ms(_run(cut, text, steps), "hide.shell") is None
    assert scopes.join(trace, text) is not None


def test_untraced_runs_and_programs_without_lower_read_none():
    assert scopes.scope_ms(_run(None, "", 1), "hide.shell") is None
    app = types.SimpleNamespace(program=types.SimpleNamespace(
        _step=lambda T, Ci: T))
    assert scopes.program_text(app) is None


# The hide cell's rehearsal app on ``dims``, built in a child process that
# has as many CPU devices as the dims ask for: the scopes of its program.
CHILD = """
import dataclasses, json, sys
from harness import scopes, spec
dims = json.loads(sys.argv[1])
cell = spec.find_cell("heat3d-256.hide")
cell = dataclasses.replace(cell, config=dict(cell.config, dims=dims))
app = spec.load_module("apps", cell.app).App(cell, 1, True)
text = scopes.program_text(app)
print(json.dumps(sorted(set(scopes.instruction_scopes(text).values()))))
"""


def rehearsal_scopes(dims):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count="
                         f"{math.prod(dims)}",
               PYTHONPATH=os.pathsep.join([BENCH, os.path.join(ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", CHILD, json.dumps(dims)],
                          capture_output=True, text=True, timeout=600,
                          env=env, check=True)
    return set(json.loads(proc.stdout.strip().splitlines()[-1]))


def test_program_text_of_a_rehearsal_app_carries_the_scopes():
    """Where a dim exchanges (two devices along x) the hidden step has a
    shell; on one device nothing exchanges and the step is the plain one,
    under ``hide.interior`` alone."""
    assert "hide.shell" in rehearsal_scopes([2, 1, 1])
    one = rehearsal_scopes([1, 1, 1])
    assert "hide.interior" in one and "hide.shell" not in one
