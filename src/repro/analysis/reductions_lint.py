"""Rule family 4: reduction exactness.

The stacked-blocks storage duplicates overlap cells, so a bare
``jnp.sum(...)`` + ``jax.lax.psum`` over-counts them — global reductions
must route through :mod:`repro.solvers.reductions`, whose wrappers (a)
bind a blessed ``reduce`` marker on the all-reduce operand and (b)
multiply in an ownership mask before the local reduction.  Three checks
on every ``psum``/``pmax``/``pmin`` whose backward cone contains a
full-field local reduction (``reduce_sum``/``reduce_max``/... with an
input of rank >= 2 — scalar bookkeeping psums are exempt):

* **bare collective** — no ``reduce`` marker in the cone: the call
  bypassed the blessed wrappers (error);
* **unmasked reduction** — no ownership ``mask`` evidence in the cone:
  overlap cells are double-counted (error).  Mask evidence is either a
  ``mask`` marker equation, or a rank >= 2 constant terminal — on fully
  periodic grids ``owned_mask`` involves no ``axis_index`` and constant-
  folds into a jaxpr constvar, leaving no marker equation behind;
* **f32 accumulator** — a ``psum`` summing float32 while x64 is enabled:
  the masked helpers upcast via ``acc_dtype`` so f32 solves keep f64
  stopping tests; a float32 summand means that contract was dropped
  (warning).
"""

from __future__ import annotations

import jax
from jax.extend import core as jcore

from . import markers
from .findings import Finding
from .jaxpr_walk import Scope, subjaxprs, walk

RULE = "reduction-exactness"

_CHECKED = ("psum", "pmax", "pmin")
_LOCAL_REDUCES = ("reduce_sum", "reduce_max", "reduce_min",
                  "reduce_prod", "argmax", "argmin")


def _cone(scope: Scope, var, limit: int = 800):
    """Backward slice like :meth:`Scope.cone`, but also reporting
    terminal vars (jaxpr constvars / toplevel inputs) so constant-folded
    masks are visible.  Yields ``("eqn", eqn)`` and ``("term", var)``."""
    seen_eqns: set[int] = set()
    seen_vars: set[int] = set()
    frontier: list[tuple[Scope, object]] = [(scope, var)]
    count = 0
    while frontier and count < limit:
        sc, v = frontier.pop(0)
        if isinstance(v, jcore.Literal) or id(v) in seen_vars:
            continue
        seen_vars.add(id(v))
        s, eqn = sc.producer(v)
        if eqn is None:
            yield "term", (sc, v)
            continue
        if id(eqn) in seen_eqns:
            continue
        seen_eqns.add(id(eqn))
        count += 1
        yield "eqn", eqn
        for iv in eqn.invars:
            frontier.append((s, iv))
        for sub in subjaxprs(eqn):
            inner = s.child(sub, eqn)
            for ov in sub.jaxpr.outvars:
                frontier.append((inner, ov))


def _root_var(scope: Scope, v):
    """Follow the invar chain of a terminal var up to the scope that
    actually binds it (where it is an invar or constvar)."""
    while scope is not None:
        nxt = scope.invar_map.get(v)
        if nxt is None or isinstance(nxt, jcore.Literal):
            return scope, v
        v = nxt
        scope = scope.parent
    return None, v


def _describe_cone(scope: Scope, var):
    """Collect the facts the three checks need from one operand cone."""
    blessed = False
    masked = False
    big_reduces = []
    for tag, item in _cone(scope, var):
        if tag == "eqn":
            if markers.is_marker(item, "reduce"):
                blessed = True
            elif markers.is_marker(item, "mask"):
                masked = True
            elif item.primitive.name in _LOCAL_REDUCES:
                src = item.invars[0]
                aval = getattr(src, "aval", None)
                if aval is not None and getattr(aval, "ndim", 0) >= 2:
                    big_reduces.append(item)
        else:  # terminal var: a constvar or a program input
            sc, v = item
            rsc, rv = _root_var(sc, v)
            aval = getattr(rv, "aval", None)
            if (rsc is not None and aval is not None
                    and getattr(aval, "ndim", 0) >= 2
                    and any(cv is rv for cv in rsc.jaxpr.constvars)):
                # a rank>=2 CONSTANT flowing into the summand is the
                # constant-folded ownership mask (fully periodic grids);
                # plain program inputs are not mask evidence
                masked = True
    return blessed, masked, big_reduces


def run(closed) -> list[Finding]:
    findings: list[Finding] = []
    x64 = bool(jax.config.jax_enable_x64)
    for eqn, scope in walk(closed):
        prim = eqn.primitive.name
        if prim not in _CHECKED:
            continue
        site = f"{scope.path}/{prim}" if scope.path else prim
        for operand in eqn.invars:
            if isinstance(operand, jcore.Literal):
                continue
            blessed, masked, reduces = _describe_cone(scope, operand)
            if not reduces:
                continue  # scalar bookkeeping reduction — exempt
            if not blessed:
                findings.append(Finding(
                    RULE, "error", site,
                    f"bare {prim} over a full-field reduction bypasses "
                    "repro.solvers.reductions — overlap cells are "
                    "double-counted and telemetry misses the collective"))
            if not masked:
                findings.append(Finding(
                    RULE, "error", site,
                    f"{prim} over an unmasked field reduction: stacked-"
                    "blocks overlap cells enter the global sum twice — "
                    "multiply by reductions.owned_mask (or solve_mask) "
                    "before reducing"))
            if prim == "psum" and x64:
                for r in reduces:
                    dt = getattr(r.invars[0].aval, "dtype", None)
                    if dt is not None and str(dt) == "float32":
                        findings.append(Finding(
                            RULE, "warning", site,
                            "float32 accumulator in a global sum while "
                            "x64 is enabled — route through "
                            "reductions.acc_dtype so f32 solves keep "
                            "f64 stopping tests"))
                        break
    return findings
