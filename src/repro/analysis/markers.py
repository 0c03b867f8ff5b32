"""Trace-time contract markers the analyzer reads out of a jaxpr.

The instrumented layers (``core/halo.py``, ``core/hide.py``,
``solvers/reductions.py``, the stencil dispatchers) declare their
ghost-validity and reduction contracts by binding an identity primitive
around the arrays they touch.  The primitive:

* binds ONLY while an analysis trace is active (:func:`tracing`) — the
  production program never contains it, so lowered HLO is byte-identical
  with the analyzer installed or not (pinned in ``tests/test_analysis.py``
  the same way ``count_comm``'s zero-cost property is pinned);
* is a pure identity at every level: abstract eval passes the aval
  through, the impl returns its operand, and the MLIR lowering emits NO
  ops — a defensive guarantee that even a marker leaking into a compiled
  program could not change its HLO;
* carries hashable params (``kind``, ``site``, and a ``meta`` tuple of
  key/value pairs) that the rule passes read back from the jaxpr.

Marker kinds:

``exchange_in`` / ``exchange_out``
    Bound around each array's halo exchange in ``update_halo``.
    ``exchange_out`` sets ghost validity to the exchanged ``width``;
    ``exchange_in`` fed *directly* by another ``exchange_out`` of equal
    or wider coverage is a redundant back-to-back exchange (perf).
    ``hide_apply`` binds a contract ``exchange_out`` on its stale-bulk
    operand: its declared semantics are ``op(update_halo(u))``, and the
    internal shell recompute discharges the staleness obligation.

``consume``
    Bound on the input of a stencil spelling; declares the ghost demand
    ``radius``.  The staleness rule checks demand against validity.

``reduce``
    Bound on the operand of the blessed all-reduce wrappers of
    :mod:`repro.solvers.reductions` — a ``psum`` without one in its
    cone is a bare collective bypassing the dedup machinery.

``mask``
    Bound on the outputs of ``owned_mask`` / ``interior_mask`` so the
    reduction lint can prove a global sum was ownership-masked.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Iterator, Sequence

from jax.extend import core as jcore
from jax.interpreters import batching, mlir

PRIMITIVE_NAME = "analysis_marker"

marker_p = jcore.Primitive(PRIMITIVE_NAME)
marker_p.def_abstract_eval(lambda aval, **_: aval)
marker_p.def_impl(lambda x, **_: x)
# Identity lowering that emits no ops: even a leaked marker cannot
# perturb compiled HLO.
mlir.register_lowering(marker_p, lambda ctx, x, **_: [x])
batching.primitive_batchers[marker_p] = (
    lambda args, dims, **params: (marker_p.bind(args[0], **params), dims[0]))


_state = threading.local()


def active() -> bool:
    """True while an analysis trace is in flight (markers bind)."""
    return getattr(_state, "depth", 0) > 0


@contextlib.contextmanager
def tracing() -> Iterator[None]:
    """Activate marker binding for the dynamic extent of one analysis
    trace.  Production traces (everything outside this context) never
    see the primitive."""
    _state.depth = getattr(_state, "depth", 0) + 1
    try:
        yield
    finally:
        _state.depth -= 1


def _freeze(value: Any) -> Any:
    if isinstance(value, (list, tuple)):
        return tuple(_freeze(v) for v in value)
    return value


def mark(kind: str, x, site: str, **meta):
    """Bind a marker of ``kind`` at ``site`` around ``x`` (identity).

    No-op (returns ``x`` unchanged) outside an analysis trace.  ``meta``
    values must be hashable scalars or (nested) sequences thereof.
    """
    if not active():
        return x
    frozen = tuple(sorted((k, _freeze(v)) for k, v in meta.items()))
    return marker_p.bind(x, kind=kind, site=site, meta=frozen)


def meta_dict(eqn) -> dict:
    """Decode a marker eqn's ``meta`` param back into a dict."""
    return dict(eqn.params.get("meta", ()))


def is_marker(eqn, kind: str | None = None) -> bool:
    if eqn.primitive.name != PRIMITIVE_NAME:
        return False
    return kind is None or eqn.params.get("kind") == kind


# -- the instrumentation vocabulary ------------------------------------

def exchange_in(x, *, width: int, site: str):
    return mark("exchange_in", x, site, width=int(width))


def exchange_out(x, *, width: int, site: str,
                 dims: Sequence[int] = (), contract: bool = False):
    return mark("exchange_out", x, site, width=int(width),
                dims=tuple(int(d) for d in dims), contract=bool(contract))


def consume(x, *, radius: int, site: str):
    return mark("consume", x, site, radius=int(radius))


def blessed_reduce(x, *, op: str, site: str):
    return mark("reduce", x, site, op=op)


def mask(x, *, mask_kind: str, site: str):
    return mark("mask", x, site, mask_kind=mask_kind)


# -- public contract helper (also used by the mutation corpus) ---------

def stencil_read(x, radius: int, site: str = "user.stencil_read"):
    """Declare that the enclosing computation reads ``radius`` ghost
    planes of ``x``.  Instrumented stencils call this internally; user
    code with hand-rolled stencils can call it too so the staleness rule
    covers custom operators."""
    return consume(x, radius=radius, site=site)
