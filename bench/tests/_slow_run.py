"""A benchmark run whose timed step is slowed, so that its window ends
before the steps its answers are drawn at.

    python3 _slow_run.py <sleep_s> <fault|none> <finish|no-finish> \\
        <bench/run.py arguments>

Each call of the app's timed step first sleeps ``sleep_s``.  ``fault``
plants one of ``tools/readings.FAULTS`` under the step as
``tools/faulty_run.py`` does; ``no-finish`` leaves the drawn steps that
the window did not reach unkept.  The run is otherwise ``bench/run.py``'s
own.
"""

from __future__ import annotations

import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "tools"))

import readings  # noqa: E402
import run  # noqa: E402
from harness import spec  # noqa: E402


def main(sleep_s: float, fault: str, finish: str, argv: list) -> int:
    load = spec.load_module

    def load_slowed(kind, name, root=spec.REPO_ROOT):
        mod = load(kind, name, root)
        if kind != "apps":
            return mod

        class Slow(mod.App):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                if fault != "none":
                    readings.FAULTS[name][fault](self)
                step = self.timed_step

                def slow(T, Ci):
                    time.sleep(sleep_s)
                    return step(T, Ci)

                self.timed_step = slow

            def finish(self):
                if finish == "finish":
                    super().finish()

        mod.App = Slow
        return mod

    spec.load_module = load_slowed
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(float(sys.argv[1]), sys.argv[2], sys.argv[3], sys.argv[4:]))
