"""Rule family 2: halo-staleness dataflow.

Abstract interpretation of the captured jaxpr over a ghost-validity
lattice.  Each array value carries one integer: how many ghost planes of
its halo ring are FRESH (exchanged after the last write that could have
invalidated them).  Transfer rules:

* program inputs start at the grid halo width ``halo`` (the caller's
  contract: fields enter a solve halo-consistent);
* ``exchange_out`` markers (bound by ``update_halo``, and as an
  explicit contract by ``hide_apply`` on its stale-bulk operand) raise
  validity to the exchanged width;
* ``consume`` markers (bound by the stencil spellings) demand
  ``radius`` fresh planes — demand above validity is the staleness
  finding — and lower the output's validity by ``radius`` (a stencil
  output's ring is stale/zeroed by construction);
* every other op — including the ``dynamic_update_slice``/``scatter``
  family — propagates the minimum over its array inputs: an interior
  write leaves my ring untouched, but the NEIGHBOR's freshly written
  interior is exactly what my ring mirrors, so the result's ghosts are
  stale until the next exchange (``hide_communication``'s mid-protocol
  exchange is the one exception, asserted by its contract marker);
* ``while``/``scan`` bodies run to a min-join fixpoint before findings
  are emitted, so a loop body that consumes ghosts without re-exchanging
  is caught even though the first iteration's inputs were fresh;
* ``cond`` joins branches by minimum.

Redundancy: an ``exchange_in`` marker whose operand is *directly*
produced by an ``exchange_out`` of equal-or-wider coverage is a
back-to-back double exchange — a pure perf finding.
"""

from __future__ import annotations

from jax.extend import core as jcore

from . import markers
from .findings import Finding
from .jaxpr_walk import SubJaxpr, subjaxprs

RULE = "halo-staleness"
RULE_REDUNDANT = "redundant-exchange"


def run(closed, halo: int = 1) -> list[Finding]:
    findings: list[Finding] = []
    jaxpr = closed.jaxpr if isinstance(closed, jcore.ClosedJaxpr) else closed
    top = int(halo)
    in_vals = [top] * (len(jaxpr.invars) + len(jaxpr.constvars))
    _interp(jaxpr, in_vals, top, True, findings, "")
    return findings


def _interp(jaxpr, in_vals, top, emit, findings, path):
    """Abstract-interpret ``jaxpr``; returns outvar validities."""
    env: dict = {}
    for v, val in zip(list(jaxpr.constvars) + list(jaxpr.invars), in_vals):
        env[v] = val

    def read(atom):
        if isinstance(atom, jcore.Literal):
            return top
        return env.get(atom, top)

    def write(vars_, vals):
        for v, val in zip(vars_, vals):
            env[v] = val

    producers = {v: e for e in jaxpr.eqns for v in e.outvars}

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        ins = [read(a) for a in eqn.invars]
        if markers.is_marker(eqn):
            kind = eqn.params["kind"]
            site = eqn.params["site"]
            meta = markers.meta_dict(eqn)
            v = ins[0]
            if kind == "exchange_out":
                write(eqn.outvars, [max(v, int(meta.get("width", top)))])
            elif kind == "exchange_in":
                w = int(meta.get("width", top))
                src = eqn.invars[0]
                peqn = producers.get(src) if not isinstance(
                    src, jcore.Literal) else None
                if (emit and peqn is not None
                        and markers.is_marker(peqn, "exchange_out")):
                    pmeta = markers.meta_dict(peqn)
                    if (int(pmeta.get("width", 0)) >= w
                            and not pmeta.get("contract", False)):
                        findings.append(Finding(
                            RULE_REDUNDANT, "perf",
                            f"{path}/{site}" if path else site,
                            "redundant back-to-back halo exchange: input "
                            f"already exchanged at width {pmeta['width']} "
                            f"by {peqn.params['site']} with no intervening "
                            "stencil"))
                write(eqn.outvars, [v])
            elif kind == "consume":
                r = int(meta.get("radius", 1))
                if emit and v < r:
                    findings.append(Finding(
                        RULE, "error",
                        f"{path}/{site}" if path else site,
                        f"stencil reads {r} ghost plane(s) but only {v} "
                        "are fresh — a halo exchange is missing on this "
                        "path (wrong values on the inner shell)"))
                write(eqn.outvars, [max(v - r, 0)])
            else:
                write(eqn.outvars, [v])
            continue

        if prim == "while":
            nc = eqn.params["cond_nconsts"]
            nb = eqn.params["body_nconsts"]
            body = eqn.params["body_jaxpr"].jaxpr
            cond = eqn.params["cond_jaxpr"].jaxpr
            bconsts = ins[nc:nc + nb]
            carry = list(ins[nc + nb:])
            carry, _ = _fixpoint(
                body, bconsts, carry, [], top, findings,
                f"{path}/while.body" if path else "while.body", emit)
            _interp(cond, ins[:nc] + carry, top, emit, findings,
                    f"{path}/while.cond" if path else "while.cond")
            write(eqn.outvars, carry)
        elif prim == "scan":
            ncons = eqn.params.get("num_consts", 0)
            ncarry = eqn.params.get("num_carry", 0)
            body = eqn.params["jaxpr"].jaxpr
            consts = ins[:ncons]
            carry = list(ins[ncons:ncons + ncarry])
            xs = ins[ncons + ncarry:]
            carry, outs = _fixpoint(
                body, consts, carry, xs, top, findings,
                f"{path}/scan.body" if path else "scan.body", emit)
            write(eqn.outvars, carry + outs[ncarry:])
        elif prim == "cond":
            branch_outs = []
            for i, bj in enumerate(eqn.params["branches"]):
                sub = bj.jaxpr if isinstance(bj, jcore.ClosedJaxpr) else bj
                bpath = (f"{path}/cond.branch{i}" if path
                         else f"cond.branch{i}")
                branch_outs.append(
                    _interp(sub, ins[1:], top, emit, findings, bpath))
            joined = [min(vals) for vals in zip(*branch_outs)]
            write(eqn.outvars, joined)
        elif prim == "pallas_call":
            val = min(ins) if ins else top
            write(eqn.outvars, [val] * len(eqn.outvars))
        else:
            subs = subjaxprs(eqn)
            if subs and prim not in ("while", "scan", "cond"):
                sub = subs[0]
                spath = f"{path}/{sub.name}" if path else sub.name
                outs = _interp(sub.jaxpr, _map_ins(sub, eqn, ins, top),
                               top, emit, findings, spath)
                write(eqn.outvars, outs[:len(eqn.outvars)])
            else:
                val = min(ins) if ins else top
                write(eqn.outvars, [val] * len(eqn.outvars))

    return [read(a) for a in jaxpr.outvars]


def _map_ins(sub: SubJaxpr, eqn, ins, top):
    by_atom = {id(a): v for a, v in zip(eqn.invars, ins)}
    vals = []
    for v in list(sub.jaxpr.constvars) + list(sub.jaxpr.invars):
        a = sub.invar_map.get(v)
        vals.append(by_atom.get(id(a), top))
    return vals


def _fixpoint(body, consts, carry, xs, top, findings, path, emit):
    """Min-join fixpoint over the loop carry; findings are emitted only
    on the final pass at the fixpoint so transient first-iteration
    freshness neither hides nor duplicates loop-body findings.

    Validity values only decrease and live in ``[0, top]``, so ``top+2``
    passes always converge.  Returns ``(carry, last_full_outs)``.
    """
    cur = list(carry)
    for _ in range(top + 2):
        sink: list = []
        outs = _interp(body, consts + cur + xs, top, False, sink, path)
        new = [min(c, o) for c, o in zip(cur, outs[:len(cur)])]
        if new == cur:
            break
        cur = new
    outs = _interp(body, consts + cur + xs, top, emit, findings, path)
    return cur, outs
