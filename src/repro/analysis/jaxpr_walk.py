"""Generic jaxpr traversal shared by the rule passes.

A compiled solve is one closed jaxpr whose interesting structure hides
several levels down: the ``shard_map`` body, the ``lax.while_loop`` of
the Krylov iteration, ``cond`` branches, ``scan``/``fori`` bodies, and
``pjit`` sub-calls.  :func:`subjaxprs` enumerates the direct children of
one equation (with the invar correspondence needed to cross the
boundary), :func:`walk` yields every equation recursively with its
:class:`Scope`, and :class:`Scope` supports backward dataflow — the cone
search the reduction lint uses to find mask/blessed markers that were
built *outside* the loop body that consumes them.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator

from jax.extend import core as jcore

# Collective primitives the congruence rule orders (the set JAX can emit
# under shard_map for this codebase's topology layer).
COLLECTIVES = ("ppermute", "psum", "pmax", "pmin", "all_to_all",
               "all_gather", "reduce_scatter", "pbroadcast")


def _raw(j):
    """Unwrap ClosedJaxpr -> Jaxpr (shard_map stores a raw Jaxpr)."""
    return j.jaxpr if isinstance(j, jcore.ClosedJaxpr) else j


@dataclasses.dataclass
class SubJaxpr:
    """One child jaxpr of an equation.

    ``invar_map`` maps each child invar to the parent-side atom feeding
    it (None when there is no parent operand, e.g. scan slices are
    mapped to the full sequence operand — close enough for provenance).
    ``loop`` marks bodies that may execute repeatedly.
    """

    name: str
    jaxpr: "jcore.Jaxpr"
    invar_map: dict
    loop: bool = False


def subjaxprs(eqn) -> list[SubJaxpr]:
    """Direct child jaxprs of ``eqn`` with invar correspondences."""
    p = eqn.params
    prim = eqn.primitive.name
    out: list[SubJaxpr] = []

    def pair(jaxpr, parent_atoms):
        m = {}
        for v, a in zip(jaxpr.invars, parent_atoms):
            m[v] = a
        return m

    if prim == "cond":
        for i, bj in enumerate(p["branches"]):
            j = _raw(bj)
            out.append(SubJaxpr(f"cond.branch{i}", j,
                                pair(j, eqn.invars[1:])))
    elif prim == "while":
        nc = p["cond_nconsts"]
        nb = p["body_nconsts"]
        cj = _raw(p["cond_jaxpr"])
        bj = _raw(p["body_jaxpr"])
        carry = eqn.invars[nc + nb:]
        out.append(SubJaxpr("while.cond", cj,
                            pair(cj, list(eqn.invars[:nc]) + list(carry))))
        out.append(SubJaxpr("while.body", bj,
                            pair(bj, list(eqn.invars[nc:nc + nb])
                                 + list(carry)),
                            loop=True))
    elif prim == "scan":
        j = _raw(p["jaxpr"])
        out.append(SubJaxpr("scan.body", j, pair(j, eqn.invars), loop=True))
    elif prim == "pallas_call":
        pass  # kernel bodies are checked structurally by the blockspec rule
    elif "jaxpr" in p:  # pjit, shard_map, closed_call, custom_* wrappers
        j = _raw(p["jaxpr"])
        out.append(SubJaxpr(prim, j, pair(j, eqn.invars)))
    elif "call_jaxpr" in p:
        j = _raw(p["call_jaxpr"])
        out.append(SubJaxpr(prim, j, pair(j, eqn.invars)))
    return out


@dataclasses.dataclass
class Scope:
    """One jaxpr level of the traversal.

    ``producers`` maps each var bound at this level to the producing
    equation; ``invar_map``/``parent`` let backward searches cross into
    the enclosing jaxpr; ``axis_sizes`` accumulates mesh axis sizes from
    enclosing ``shard_map`` equations (for ppermute table checks).
    """

    jaxpr: "jcore.Jaxpr"
    path: str = ""
    parent: "Scope | None" = None
    invar_map: dict = dataclasses.field(default_factory=dict)
    axis_sizes: dict = dataclasses.field(default_factory=dict)
    producers: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        for eqn in self.jaxpr.eqns:
            for v in eqn.outvars:
                self.producers[v] = eqn

    def child(self, sub: SubJaxpr, eqn) -> "Scope":
        sizes = dict(self.axis_sizes)
        if eqn.primitive.name == "shard_map":
            mesh = eqn.params.get("mesh")
            shape = getattr(mesh, "shape", None)
            if shape:
                sizes.update({str(k): int(v) for k, v in dict(shape).items()})
        return Scope(jaxpr=sub.jaxpr,
                     path=f"{self.path}/{sub.name}" if self.path else sub.name,
                     parent=self, invar_map=sub.invar_map, axis_sizes=sizes)

    # -- backward dataflow ---------------------------------------------
    def producer(self, var):
        """(scope, eqn) producing ``var``, following invars into the
        parent scope; (None, None) for toplevel inputs and literals."""
        scope: Scope | None = self
        v = var
        while scope is not None:
            if isinstance(v, jcore.Literal):
                return None, None
            eqn = scope.producers.get(v)
            if eqn is not None:
                return scope, eqn
            nxt = scope.invar_map.get(v)
            if nxt is None:
                return None, None
            v = nxt
            scope = scope.parent
        return None, None

    def cone(self, var, limit: int = 800) -> Iterator:
        """Backward slice from ``var``: yields producing equations,
        breadth-first, crossing scope boundaries, up to ``limit``."""
        seen: set[int] = set()
        frontier: list[tuple[Scope, object]] = [(self, var)]
        count = 0
        while frontier and count < limit:
            scope, v = frontier.pop(0)
            s, eqn = scope.producer(v)
            if eqn is None or id(eqn) in seen:
                continue
            seen.add(id(eqn))
            count += 1
            yield eqn
            for iv in eqn.invars:
                if not isinstance(iv, jcore.Literal):
                    frontier.append((s, iv))
            # descend through sub-jaxpr outputs: the values flowing out
            # of a cond/while/pjit were computed inside it
            for sub in subjaxprs(eqn):
                inner = s.child(sub, eqn)
                for ov in sub.jaxpr.outvars:
                    if not isinstance(ov, jcore.Literal):
                        frontier.append((inner, ov))


def walk(closed, path: str = "") -> Iterator[tuple[object, Scope]]:
    """Yield ``(eqn, scope)`` for every equation, depth-first."""
    root = Scope(jaxpr=_raw(closed), path=path)
    yield from _walk_scope(root)


def _walk_scope(scope: Scope) -> Iterator[tuple[object, Scope]]:
    for eqn in scope.jaxpr.eqns:
        yield eqn, scope
        for sub in subjaxprs(eqn):
            yield from _walk_scope(scope.child(sub, eqn))
