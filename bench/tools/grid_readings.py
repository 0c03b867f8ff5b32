"""``readings.py`` for a heat cell whose dims split the field over chips.

    python3 bench/tools/grid_readings.py --workload <cell> --seeds 1 2 3 \\
        [--control-seeds 4 5 6] [--fault NAME ...] [--seconds S] [--rehearsal]

The arguments and the output are ``bench/tools/readings.py``'s.  Two
things differ:

* the control (the plain reference computed in bfloat16 in the program's
  place) steps the global field, each cell once, and lays the result back
  out as the stacked blocks, each block's halo holding its neighbour's
  cells, as a sound exchange leaves it;
* ``--fault`` also takes ``exchange_fault.FAULTS``, which break the hidden
  step's halo exchange.  Every reading starts from the program's own
  exchange, so a fault holds for its own reading only.
"""

from __future__ import annotations

import os
import sys

TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(TOOLS))
sys.path.insert(0, TOOLS)

import exchange_fault  # noqa: E402
import readings  # noqa: E402


def stacked_control(app):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import layout
    from reference import heat3d as ref

    coef = ref.coefficients(app.cfg, app.gshape)
    # Along each axis: the global index of each stacked cell, and the
    # stacked index of each global cell's first copy.
    global_of = [layout.stacked_to_global(n, d)
                 for n, d in zip(app.local, app.dims)]
    first_copy = [np.unique(g, return_index=True)[1] for g in global_of]

    def step(T):
        for ax, idx in enumerate(first_copy):
            T = jnp.take(T, idx, axis=ax)
        new = ref.step(T, coef, xp=jnp, dtype=jnp.bfloat16).astype(app.dtype)
        for ax, idx in enumerate(global_of):
            new = jnp.take(new, idx, axis=ax)
        return new

    stepped = jax.jit(step, out_shardings=app.program.grid.sharding)
    app.timed_step = lambda T, Ci: stepped(T)


def main(argv=None) -> int:
    sound_one = readings.one

    def one(*args, **kw):
        exchange_fault.plant(None)
        return sound_one(*args, **kw)

    readings.one = one
    readings.heat_control = stacked_control
    readings.FAULTS["heat3d"].update(
        {name: lambda app, name=name: exchange_fault.plant(name)
         for name in exchange_fault.FAULTS})
    return readings.main(argv)


if __name__ == "__main__":
    sys.exit(main())
