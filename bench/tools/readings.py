"""Readings that set and test a cell's limits, in one process.

    python3 bench/tools/readings.py --workload <cell> --seeds 1 2 3 \\
        [--control-seeds 4 5 6] [--fault NAME ...] [--seconds S] [--rehearsal]

For each ``--seeds`` seed it runs the program as a benchmark run does
(build, warm up, a window of ``--seconds``, finish, release, compare) and
prints the numbers compared.  For each ``--control-seeds`` seed it puts the plain
reference, computed one precision below the configuration's (bfloat16
for float32), in the program's place, and prints what the same comparison
reads.  ``--fault`` breaks the timed path in one way (see ``FAULTS``) and
reads the program so broken.  One JSON object per reading, then a summary
line: the largest program reading, and the smallest control and fault
readings, of each compared number.  It never decides ``correct`` itself; the limits in
the cell's configuration do.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(TOOLS))

import run  # noqa: E402
from harness import spec  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[1])
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--rehearsal", action="store_true")
    return ap.parse_args(argv)


# --- faults: the timed path broken in one way each ------------------------

def _heat_unchanged(app):
    app.timed_step = lambda T, Ci: T


def _heat_altered(app):
    import jax

    step = app.program._step
    mid = tuple(n // 2 for n in app.local)  # inside the first block
    bump = jax.jit(lambda T: T.at[mid].add(1e-2))
    app.timed_step = lambda T, Ci: bump(step(T, Ci))


def _heat_coarse_increment(app):
    """The program's step with its increment rounded to bfloat16: a
    lower precision in part of the update only.  The rounding is a
    ``reduce_precision`` to bfloat16's bits: a round trip through
    ``astype(bfloat16)`` is dropped by the TPU's compiler, which may keep
    excess precision, and would leave the step sound."""
    import jax

    step = app.program._step
    coarse = jax.jit(lambda T, new: T + jax.lax.reduce_precision(
        new - T, exponent_bits=8, mantissa_bits=7))
    app.timed_step = lambda T, Ci: coarse(T, step(T, Ci))


FAULTS = {
    "heat3d": {"unchanged": _heat_unchanged, "altered": _heat_altered,
               "coarse_increment": _heat_coarse_increment},
}


# --- the control: the reference in the program's place ------------------

def heat_control(app):
    import jax
    import jax.numpy as jnp

    from reference import heat3d as ref

    if app.dims != (1, 1, 1):
        raise ValueError("the heat control runs on one block")
    coef = ref.coefficients(app.cfg, app.gshape)
    step = jax.jit(lambda T: ref.step(T, coef, xp=jnp, dtype=jnp.bfloat16)
                   .astype(app.dtype))
    app.timed_step = lambda T, Ci: step(T)


# -------------------------------------------------------------------------

def one(cell, App, seed, rehearsal, seconds, kind, fault=None):
    app = App(cell, seed, rehearsal)
    if kind == "control":
        heat_control(app)
    elif fault in FAULTS.get(cell.app, {}):
        FAULTS[cell.app][fault](app)
    app.warmup()
    win = app.window(seconds)
    app.finish()
    app.release()
    compared, each = app.check()
    return {"kind": kind if fault is None else f"fault.{fault}",
            "seed": seed, "units": win["units"], "failed": win["failed"],
            "compared": compared, "readings": each}


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = spec.find_cell(args.workload)
    jax = run.configure_jax(cell.chips, args.rehearsal)
    dev = jax.devices()
    print(json.dumps({"device": dev[0].device_kind, "count": len(dev)}),
          flush=True)
    App = spec.load_module("apps", cell.app).App
    rows = []
    runs = ([(s, "program", None) for s in args.seeds]
            + [(s, "control", None) for s in args.control_seeds]
            + [(s, "program", f) for f in args.fault for s in args.fault_seeds])
    for seed, kind, fault in runs:
        row = one(cell, App, seed, args.rehearsal, args.seconds, kind, fault)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for row in rows:
        for k, v in row["compared"].items():
            s = summary.setdefault(k, {})
            key = "program_max" if row["kind"] == "program" else (
                "control_min" if row["kind"] == "control" else row["kind"])
            if v != v:
                s[key + "_nan"] = s.get(key + "_nan", 0) + 1
                continue
            pick = max if key == "program_max" else min
            s[key] = v if key not in s else pick(s[key], v)
    print(json.dumps({"summary": summary,
                      "limits": cell.config["limits"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
