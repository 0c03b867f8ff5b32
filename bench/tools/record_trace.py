"""Record the small chip trace that ``bench/tests`` reads.

    python3 bench/tools/record_trace.py <workload> <out.xplane.pb>

Runs one chunk of the cell's window (the steps of ``CHUNK_S`` of host
time) under the profiler, as a ``--trace 1`` run does, and copies the
``.xplane.pb`` to ``<out>``.  Needs the chip.
"""

from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402
from harness import spec  # noqa: E402


def main(workload: str, out: str) -> int:
    cell = spec.find_cell(workload)
    jax = run.configure_jax(cell.chips, rehearsal=False)
    app = spec.load_module("apps", cell.app).App(cell, 1, False)
    app.warmup()
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    run.traced_window(jax, app, 0.0, keep=out)
    print(out, os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
