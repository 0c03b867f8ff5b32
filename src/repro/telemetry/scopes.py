"""Program scopes of a compiled module's instructions.

The core layer names its phases with ``jax.named_scope``:
``hide.shell``, ``hide.exchange`` and ``hide.interior`` in
:func:`repro.core.hide.hide_communication`, and ``halo.update`` in
:func:`repro.core.halo.update_halo`.  The scope reaches the compiled
module as each instruction's ``op_name`` metadata
(``jit(dstep)/shard_map/hide.shell/jit(heat_step_pallas)/stencil3d_heat``)
and changes nothing else.  A profiler trace names each device op by its
instruction and carries no metadata, so an op is put down to a scope by
joining the trace with the module's text::

    text = step.lower(T, Ci).compile().as_text()
    scope = tele.op_scopes(text)          # {"stencil3d_heat.3": "hide.shell", ...}
"""

from __future__ import annotations

import re

PROGRAM_SCOPES = ("hide.shell", "hide.exchange", "hide.interior",
                  "halo.update")

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"')


def op_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: innermost program scope}`` of a compiled
    module's text (``Compiled.as_text()``).  Names keep their ``.N``
    suffix; an instruction under no program scope (such as a copy the
    compiler inserts) is left out."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        scopes = [p for p in m.group(2).split("/") if p in PROGRAM_SCOPES]
        if scopes:
            out[m.group(1)] = scopes[-1]
    return out
