"""Communication hiding — the paper's ``@hide_communication``.

The paper splits each time step into (1) computing the thin boundary shell
of the output, (2) launching the halo exchange of those freshly computed
boundary values on high-priority streams, and (3) computing the (much
larger) interior concurrently with the communication.

On TPU/XLA there are no user streams; overlap is a *scheduling* decision
made by XLA's latency-hiding scheduler.  What we control is the dependence
structure: here the ``ppermute`` (collective-permute) operands depend ONLY
on the boundary-slab computation, and the interior computation is fully
independent of the collectives, so the compiler is free to (and on TPU
does) run the interior fusion between ``collective-permute-start`` and
``-done``.

``hide_communication(topo, step_fn, inputs, width)`` is semantically
IDENTICAL to ``update_halo(topo, step_fn(*inputs))`` — a property tested
bitwise in ``tests/test_core_grid.py`` and ``tests/test_hide_contracts.py``
— but with the boundary/interior split dataflow.

The split follows the dims that exchange (``topo.exchanges(d)``: split
over more than one rank, or periodic).  Only they get boundary slabs;
along the others the interior takes the whole extent.  Where no dim
exchanges (one rank, open boundaries) there is nothing to hide and the
step is ``step_fn(*inputs)`` itself.

Conventions (matching the usual ParallelStencil step):

* ``step_fn(*inputs) -> out`` (array or tuple of arrays), every output the
  same shape as every input (all grid-rank local fields);
* output interior (all dims ``[h, n-h)``) is newly computed, the outer ring
  passes through old values of the matching input: output ``k`` keeps the
  ring of ``inputs[k]``;
* ``step_fn`` is shape-polymorphic (all :mod:`repro.stencil` ops are).
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.analysis import markers as _an

from .halo import _slc, update_halo
from .topology import CartesianTopology


def hide_communication(
    topo: CartesianTopology,
    step_fn: Callable,
    inputs: Sequence[jax.Array],
    width: int | Sequence[int] = 2,
    halo: int = 1,
):
    """Boundary-first step with overlapped halo exchange (local view).

    ``width[d]`` is the boundary-shell thickness along grid dim ``d`` (the
    paper's ``@hide_communication (16, 2, 2)`` tuple), clamped to >= halo
    so the halo send slabs lie inside the freshly computed shell.  Only
    the dims that exchange (``topo.exchanges(d)``) get a shell; along the
    others the interior spans the whole extent.
    """
    inputs = tuple(jnp.asarray(A) for A in inputs)
    ref = inputs[0]
    nd = ref.ndim
    if nd != topo.ndims:
        raise ValueError(
            f"hide_communication expects grid-rank arrays ({topo.ndims}-D), got {nd}-D"
        )
    h = int(halo)
    if isinstance(width, int):
        width = (width,) * nd
    w = tuple(max(int(wd), h) for wd in width)
    shape = ref.shape
    ex = tuple(d for d in range(nd) if topo.exchanges(d))
    for d in ex:
        if shape[d] < 2 * (w[d] + h):
            raise ValueError(
                f"local extent {shape[d]} too small for shell width {w[d]} + halo {h}"
            )

    def run(slabs):
        res = step_fn(*slabs)
        return tuple(res) if isinstance(res, (tuple, list)) else (res,)

    # Each phase runs under a named scope ("hide.shell", "hide.exchange",
    # "hide.interior"): the compiled ops carry it in their ``op_name``
    # metadata, so a profiler trace can be read phase by phase
    # (``repro.telemetry.op_scopes``).  Scopes are trace-time only.

    if not ex:
        # Nothing exchanges (one rank, open boundaries): the interior is
        # the whole field and there is nothing to hide, so the step is the
        # plain one, with no slices and no writes back into the field.
        with jax.named_scope("hide.interior"):
            outs = list(run(inputs))
        with jax.named_scope("hide.exchange"):
            updated = update_halo(topo, *outs, width=h)  # exchanges nothing
        outs = list(updated) if isinstance(updated, tuple) else [updated]
    else:
        # ---- 1. boundary shell: two face slabs per exchanging dim -------
        # Slabs span the full extent of the other dims; corners are
        # recomputed by later faces (same values — harmless).
        # Pass-through convention: output k starts as old inputs[k].
        outs = None
        with jax.named_scope("hide.shell"):
            for d in ex:
                n = shape[d]
                wd = w[d]
                lo = run(tuple(A[_slc(nd, d, 0, 2 * h + wd)] for A in inputs))
                hi = run(tuple(A[_slc(nd, d, n - 2 * h - wd, n)] for A in inputs))
                if outs is None:
                    outs = [inputs[k] for k in range(len(lo))]
                sl = _slc(nd, d, h, h + wd)  # valid region, slab-local == face-global (low)
                for k in range(len(outs)):
                    outs[k] = outs[k].at[sl].set(lo[k][sl])
                    outs[k] = outs[k].at[_slc(nd, d, n - h - wd, n - h)].set(
                        hi[k][_slc(nd, d, h, h + wd)]
                    )

        # ---- 2. halo exchange — depends only on the boundary shell -------
        with jax.named_scope("hide.exchange"):
            updated = update_halo(topo, *outs, width=h)
        outs = list(updated) if isinstance(updated, tuple) else [updated]

        # ---- 3. interior — independent of the collectives (overlappable) -
        # Along an exchanging dim the interior is [w, n-w) and its valid
        # part [w+h, n-w-h); along any other dim it is the whole extent,
        # and the output's ring there passes through the input's values,
        # so the write covers whole planes.
        def span(d, lo, hi):
            return slice(lo, hi) if d in ex else slice(None)

        with jax.named_scope("hide.interior"):
            int_in = tuple(A[tuple(span(d, w[d], shape[d] - w[d]) for d in range(nd))]
                           for A in inputs)
            int_out = run(int_in)
            sl_local = tuple(span(d, h, shape[d] - 2 * w[d] - h) for d in range(nd))
            sl_global = tuple(span(d, w[d] + h, shape[d] - w[d] - h) for d in range(nd))
            for k in range(len(outs)):
                outs[k] = outs[k].at[sl_global].set(int_out[k][sl_local])

    # Analyzer contract: semantically this IS ``update_halo(step(...))``
    # (bitwise-pinned in tests) — the exchanged planes mirror the
    # neighbor's boundary shell, written BEFORE the exchange, so the
    # output's ghosts are fresh even though the interior write lands
    # after it (which the plain min-rule can't see).
    outs = [_an.exchange_out(A, width=h, dims=tuple(range(nd)),
                             site="core.hide.hide_communication.contract",
                             contract=True)
            for A in outs]

    return outs[0] if len(outs) == 1 else tuple(outs)


def hide_apply(
    topo: CartesianTopology,
    op_fn: Callable,
    u: jax.Array,
    *extra: jax.Array,
    halo: int = 1,
):
    """Operator application with overlapped halo exchange (local view).

    Semantically IDENTICAL to ``op_fn(update_halo(topo, u, width=halo),
    *extra)`` — same arithmetic on the same values; the recomputed shell
    cells may differ by ~1 ulp where the compiler vectorizes the
    differently-shaped slab computation differently.  This is the dual of
    :func:`hide_communication`: a solver's operator needs FRESH halos of
    its *input* before the stencil, instead of exchanging its output
    afterwards.  The dependence structure exposed to the scheduler:

    1. the ``ppermute`` operands are slabs of ``u`` — the exchange starts
       immediately;
    2. the stencil is applied to ``u`` with its *stale* halos over the
       whole block — independent of the collectives, so XLA can run this
       (the bulk of the work) between ``collective-permute-start/-done``;
       only the inner shell of cells adjacent to the halos is wrong;
    3. after the exchange, that thin shell is recomputed from slabs of
       the halo-updated input and overwritten.

    Requirements on ``op_fn(u, *extra) -> out``: shape-polymorphic, writes
    each output cell of the all-dims interior ``[h, n - h)`` from the
    ``(2h + 1)``-neighborhood of its input cell, zeroes the outer ring,
    and ``extra`` operands (e.g. coefficient fields) are already
    halo-consistent.  All :mod:`repro.solvers` operators qualify.
    """
    h = int(halo)
    nd = u.ndim
    if nd != topo.ndims:
        raise ValueError(
            f"hide_apply expects grid-rank arrays ({topo.ndims}-D), got {nd}-D")
    for d in range(nd):
        if u.shape[d] < 4 * h:
            raise ValueError(
                f"local extent {u.shape[d]} too small for halo {h} overlap")

    u2 = update_halo(topo, u, width=h)
    # Analyzer contract: hide_apply's declared semantics are
    # ``op_fn(update_halo(u))`` — the shell recompute below discharges
    # the staleness of the bulk pass, so the stale-bulk operand is
    # marked as exchanged (contract=True keeps the redundancy rule from
    # pairing it with a later real exchange).
    ub = _an.exchange_out(u, width=h, site="core.hide.hide_apply.contract",
                          contract=True)
    out = op_fn(ub, *extra)  # stale halos: wrong only on the inner shell
    for d in range(nd):
        if not topo.exchanges(d):
            # No exchange along d: u2 == u there, and every cell needing
            # fresh halos of OTHER dims lies in those dims' shells.
            continue
        n = u.shape[d]
        # Recompute output cells [h, 2h) / [n-2h, n-h) along d (full extent
        # of the other dims, so corner/edge cells pick up fresh halos of
        # every dim in whichever pass reaches them first — same values).
        lo_in = _slc(nd, d, 0, 3 * h)
        hi_in = _slc(nd, d, n - 3 * h, n)
        lo = op_fn(u2[lo_in], *(e[lo_in] for e in extra))
        hi = op_fn(u2[hi_in], *(e[hi_in] for e in extra))
        sl = _slc(nd, d, h, 2 * h)  # slab-local valid rows (both slabs)
        out = out.at[_slc(nd, d, h, 2 * h)].set(lo[sl])
        out = out.at[_slc(nd, d, n - 2 * h, n - h)].set(hi[sl])
    return out
