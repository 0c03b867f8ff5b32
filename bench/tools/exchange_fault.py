"""A benchmark run whose hidden step exchanges its halos wrongly.

    python3 bench/tools/exchange_fault.py <fault> <bench/run.py arguments>

``<fault>`` names one of ``FAULTS``: ``repro.core.hide``'s
``update_halo``, the exchange that ``hide_communication`` makes after the
boundary shell, is replaced before the step is traced.  The run is
otherwise ``bench/run.py``'s own; on a cell whose dims split the field its
comparison must find the answers not correct.  Only the hidden step
(a mix with ``hide``) calls that exchange.
"""

from __future__ import annotations

import os
import sys

TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(TOOLS))

from harness import spec  # noqa: E402


def _none(topo, *arrays, **kw):
    """No exchange: the halos keep the values the shell step left."""
    return arrays[0] if len(arrays) == 1 else arrays


def _x_only(topo, *arrays, **kw):
    """The exchange along grid dim x alone; y and z keep stale halos."""
    from repro.core import halo

    return halo.update_halo(topo, *arrays, **dict(kw, dims=(0,)))


FAULTS = {"exchange_none": _none, "exchange_x_only": _x_only}


def plant(fault: str | None):
    """Make ``hide_communication`` exchange with ``FAULTS[fault]``, or
    with the program's own ``update_halo`` where ``fault`` is None."""
    from repro.core import halo, hide

    hide.update_halo = halo.update_halo if fault is None else FAULTS[fault]


def main(fault: str, argv: list) -> int:
    import run

    if fault not in FAULTS:
        raise SystemExit(f"no exchange fault {fault!r}; known: {sorted(FAULTS)}")
    load = spec.load_module

    def load_with_fault(kind, name, root=spec.REPO_ROOT):
        # The app is loaded after the run has configured JAX, before the
        # step is built or traced.
        if kind == "apps":
            plant(fault)
        return load(kind, name, root)

    spec.load_module = load_with_fault
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2:]))
