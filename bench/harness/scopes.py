"""Device time by program scope.

The program names the phases of its hidden step with named scopes:
``hide.shell``, ``hide.exchange`` and ``hide.interior``
(``core/hide.py``), and ``halo.update`` (``core/halo.py``).  A scope
reaches the compiled module as the ``op_name`` metadata of each
instruction it made.  A trace's op events name the instruction and carry
no metadata, so device time is put down to a scope by joining the trace
with the module's text, which ``scope_ms`` compiles once per run from the
program's own step (``grid.parallel``'s ``lower``), after the window:
the compile cache serves it.  A run of a program without that entry
point reads None.

The join works on the trace's ops as ``harness.trace`` reads them: by
instruction name without its ``.N`` suffix, opcode and output shape.
Where several instructions share those, the k-th op of that kind on a
device is the (k mod n)-th of the n instructions in the module's order:
a device runs one program's ops in its schedule, each once per run of a
step with no loop.  A join whose counts do not fit reads None, and says
on stderr which kind of op did not fit.

The scope names and the reading of ``op_name`` are the harness's own,
although ``repro.telemetry.op_scopes`` does the same job in the program:
a metric must not move when the program's code changes, so the yardstick
imports nothing of it (as ``apps/heat3d.py`` keeps its own A_eff).
``tests/test_bench_scopes.py`` checks that the two still agree.
"""

from __future__ import annotations

import re
import sys

from harness.trace import CONTAINERS, parse_hlo

SCOPES = ("hide.shell", "hide.exchange", "hide.interior", "halo.update")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+ = .*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLED = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")


def _traced(run) -> bool:
    return run.trace is not None and bool(run.trace.devices)


def instruction_scopes(text: str) -> dict:
    """``{instruction name with its .N suffix: innermost scope}`` of a
    compiled module's text; an instruction under no scope is left out."""
    out = {}
    for line in text.splitlines():
        m = _OP_NAME.search(line)
        if m is None or _INSTR.match(line) is None:
            continue
        inner = [p for p in m.group(1).split("/") if p in SCOPES]
        if inner:
            out[line.split(" = ")[0].split()[-1].lstrip("%")] = inner[-1]
    return out


def schedule(text: str) -> dict:
    """The instructions that run as ops (those of computations no fusion
    or reduction calls), in the module's order, by the key
    ``(name without suffix, opcode, output shape)`` that a trace op
    carries: ``{key: [full names]}``."""
    called = set(_CALLED.findall(text))
    out: dict = {}
    inside = False
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None and " = " not in line:
            inside = head.group(1) not in called
            continue
        m = _INSTR.match(line)
        if not inside or m is None:
            continue
        body = m.group(1).lstrip("%")
        name, opcode, shape, _ = parse_hlo(body)
        full = body.split(" = ")[0]
        out.setdefault((name, opcode, shape), []).append(full)
    return out


def join(trace, text: str) -> dict | None:
    """``{device: [scope or None for each of its ops]}``, the ops in the
    trace's order; None where a kind of op ran a number of times that
    its instructions do not divide, or is not in the module."""
    scope_of = instruction_scopes(text)
    sched = schedule(text)
    out = {}
    for dev, ops in trace.devices.items():
        seen: dict = {}
        scopes = []
        for o in ops:
            if o.opcode in CONTAINERS:
                scopes.append(None)
                continue
            key = (o.name, o.opcode, o.shape)
            names = sched.get(key)
            if not names:
                _unjoined(dev, key, "not in the module")
                return None
            k = seen.get(key, 0)
            seen[key] = k + 1
            scopes.append(scope_of.get(names[k % len(names)]))
        for key, n in seen.items():
            if n % len(sched[key]):
                _unjoined(dev, key, f"ran {n} times, "
                          f"{len(sched[key])} instructions")
                return None
        out[dev] = scopes
    return out


def _unjoined(dev, key, why):
    name, opcode, shape = key
    print(f"[scopes] no join on {dev}: {opcode} {name} {shape} {why}; "
          "the scope metrics are left out", file=sys.stderr, flush=True)


def program_text(app) -> str | None:
    """The compiled module of the app's step, or None where the program
    has no ``lower`` on its ``grid.parallel`` step."""
    lower = getattr(app.program._step, "lower", None)
    if lower is None:
        return None
    return lower(app.T0, app.Ci).compile().as_text()


def run_scopes(run):
    """The run's join, built once and kept on the run: from the module
    text ``run.hlo_text`` where the run carries one (a recorded trace),
    else from the program's step compiled now."""
    if "scopes" not in vars(run):
        text = vars(run).get("hlo_text") or program_text(run.app)
        run.scopes = None if text is None else join(run.trace, text)
    return run.scopes


def scope_ms(run, scope: str):
    """Device time per unit (ms) in ops under ``scope``, averaged over
    the devices; None without a trace, a module or a join."""
    if not _traced(run):
        return None
    joined = run_scopes(run)
    if joined is None:
        return None
    t = sum(o.dur for dev, ops in run.trace.devices.items()
            for o, s in zip(ops, joined[dev]) if s == scope)
    return 1e-6 * t / len(run.trace.devices) / run.window["units"]

