"""Save the compiled module of a cell's step, to read beside a trace that
``record_trace.py`` recorded.

    python3 bench/tools/record_hlo.py <workload> <out.hlo.txt>

Builds the cell's app from seed 1, as ``record_trace.py`` does, and writes
its step's compiled module (``Compiled.as_text()``), whose instructions
carry the program's scopes in their ``op_name``
(``harness.scopes``).  The compile cache serves the step that
``record_trace.py`` compiled.  Needs the chip.
"""

from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402
from harness import scopes, spec  # noqa: E402


def main(workload: str, out: str) -> int:
    cell = spec.find_cell(workload)
    run.configure_jax(cell.chips, rehearsal=False)
    app = spec.load_module("apps", cell.app).App(cell, 1, False)
    text = scopes.program_text(app)
    if text is None:
        print("the program's step has no lower()", file=sys.stderr)
        return 1
    # Source paths relative to the checkout: the file does not depend on
    # where it was recorded.
    text = text.replace(run.ROOT + os.sep, "")
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        f.write(text)
    print(out, len(text))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
