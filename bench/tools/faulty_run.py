"""A benchmark run with one fault planted under its timed path.

    python3 bench/tools/faulty_run.py <fault> <bench/run.py arguments>

``<fault>`` names one of ``readings.FAULTS`` for the cell's app.  The run is otherwise ``bench/run.py``'s
own; its comparison must find the answers not correct.
"""

from __future__ import annotations

import os
import sys

TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(TOOLS))
sys.path.insert(0, TOOLS)

import readings  # noqa: E402
from harness import spec  # noqa: E402


def main(fault: str, argv: list) -> int:
    import run

    load = spec.load_module

    def load_with_fault(kind, name, root=spec.REPO_ROOT):
        mod = load(kind, name, root)
        if kind != "apps":
            return mod
        plant = readings.FAULTS[name][fault]

        class Faulty(mod.App):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                plant(self)

        mod.App = Faulty
        return mod

    spec.load_module = load_with_fault
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2:]))
