"""Rule family 3: Pallas BlockSpec verification.

The x-ghost rows of every kernel in ``kernels/stencil3d`` and
``kernels/solver3d`` come from mapping the SAME array through shifted
BlockSpecs — so the correctness of the ghost CONTENT is entirely a
property of the ``index_map`` lambdas.  The historical bug class this
rule exists for: clamped neighbor maps (``max(i-1, 0)``) that silently
feed boundary blocks their own edge rows as ghosts instead of the wrap
rows the reference ``jnp.roll`` reads.

For every ``pallas_call`` equation the rule enumerates each block
mapping's ``index_map`` image over the full launch grid (the jaxprs are
tiny integer programs — evaluated concretely, no kernel runs) and
proves, per blocked dimension:

* **divisibility** — the global extent is a multiple of the block
  extent (the same contract ``kernels/dispatch.py`` probes at runtime);
* **range** — every mapped block index lands in ``[0, n_blocks)``;
* **shape** — input mappings are the identity or a constant shift
  *modulo* the block count (identity = the block's own rows; wrap shift
  = a true neighbor/wrap ghost).  Anything else — duplicated reads with
  a non-uniform shift — is the clamp signature;
* **output identity** — output mappings must be the identity (a shifted
  output scatters blocks over each other's slots);
* **broadcast honesty** — a mapping that sends every grid step to the
  same block is only legal when that dimension has a single block
  (e.g. the SMEM coefficient vector).
"""

from __future__ import annotations

from jax.extend import core as jcore

from .findings import Finding
from .jaxpr_walk import walk

RULE = "pallas-blockspec"


def _call_name(eqn) -> str:
    info = eqn.params.get("name_and_src_info")
    name = getattr(info, "name", None)
    return name or "pallas_call"


def _static_grid(grid):
    out = []
    for g in grid:
        try:
            out.append(int(g))
        except (TypeError, ValueError):
            return None
    return tuple(out)


def _image(bm, grid_points):
    """Evaluate one index_map over the launch grid -> list of tuples."""
    cj = bm.index_map_jaxpr
    img = []
    for pt in grid_points:
        res = jcore.jaxpr_as_fun(cj)(*pt)
        img.append(tuple(int(r) for r in res))
    return img


def _check_dim(vals, nb, grid_size, is_output):
    """Classify one blocked dimension's index sequence.

    Returns ``None`` when acceptable, else a reason string.
    """
    if any(v < 0 or v >= nb for v in vals):
        bad = next(v for v in vals if v < 0 or v >= nb)
        return (f"block index {bad} out of range [0, {nb}) — reads/writes "
                "outside the array")
    if all(v == vals[0] for v in vals):
        if nb == 1:
            return None  # whole-dim block (broadcast operand)
        return (f"every grid step maps to block {vals[0]} of {nb} — "
                "all instances touch the same slab")
    if all(v == i for i, v in enumerate(vals)):
        return None  # identity
    if is_output:
        return ("output index_map is not the identity — shifted outputs "
                "scatter blocks over each other's slots")
    shifts = {(v - i) % nb for i, v in enumerate(vals)}
    if len(shifts) == 1:
        return None  # constant shift mod nb: true wrap-mapped neighbor
    dupes = len(vals) - len(set(vals))
    if dupes:
        return (f"non-uniform shift with {dupes} duplicated block "
                "read(s) — the clamped-neighbor signature (a boundary "
                "block's ghost row aliases its own edge row instead of "
                "the wrap row the reference reads); use (i +- 1) mod nb")
    return "index_map is neither the identity nor a constant shift mod nb"


def check_call(eqn, site: str) -> list[Finding]:
    findings: list[Finding] = []
    gm = eqn.params["grid_mapping"]
    grid = _static_grid(gm.grid)
    name = _call_name(eqn)
    where = f"{site}/{name}" if site else name
    if grid is None or not grid:
        return findings  # dynamic or zero-dim grid: nothing provable
    # enumerate the full launch grid (row-major)
    points = [()]
    for g in grid:
        points = [p + (i,) for p in points for i in range(g)]
    n_in = gm.num_inputs
    for k, bm in enumerate(gm.block_mappings):
        is_output = k >= n_in
        role = f"out{k - n_in}" if is_output else f"in{k}"
        shape = bm.array_aval.shape
        block = bm.block_shape
        nbs = []
        for d, b in enumerate(block):
            try:
                b = int(getattr(b, "block_size", b))  # pl.Blocked(n) or n
            except (TypeError, ValueError):
                nbs.append(1)  # squeezed/mapped dim: treat as whole-dim
                continue
            if shape[d] % b != 0:
                findings.append(Finding(
                    RULE, "error", f"{where}/{role}",
                    f"block extent {b} does not tile dim {d} of global "
                    f"shape {tuple(shape)} — the trailing partial block "
                    "reads out of bounds (dispatch.pick_bx enforces "
                    "divisibility; this call bypassed it)"))
                nbs.append(max(shape[d] // b, 1))
            else:
                nbs.append(shape[d] // b)
        try:
            img = _image(bm, points)
        except Exception:  # non-standard index machinery: skip, don't lie
            continue
        for d, nb in enumerate(nbs):
            vals = [idx[d] for idx in img]
            reason = _check_dim(vals, nb, len(points), is_output)
            if reason is not None:
                findings.append(Finding(
                    RULE, "error", f"{where}/{role}",
                    f"dim {d} (block count {nb}): {reason}"))
    return findings


def run(closed) -> list[Finding]:
    findings: list[Finding] = []
    for eqn, scope in walk(closed):
        if eqn.primitive.name == "pallas_call":
            findings.extend(check_call(eqn, scope.path))
    return findings


# ---------------------------------------------------------------------------
# kernel-library sweep: trace every wrapper shape the dispatch layer can
# launch and verify their specs without running a single kernel
# ---------------------------------------------------------------------------

def check_kernel_library(bx: int = 4, nbs=(1, 2, 3)) -> list[Finding]:
    """Trace the ``stencil3d``/``solver3d`` pallas wrappers for block
    counts ``nbs`` and run the BlockSpec rule on each traced call."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.solver3d import kernel as sk
    from repro.kernels.stencil3d import kernel as hk

    findings: list[Finding] = []
    h2 = (1.0, 1.0, 1.0)
    for nb in nbs:
        nx, ny, nz = bx * nb, 6, 6
        f3 = jax.ShapeDtypeStruct((nx, ny, nz), jnp.float32)

        targets = {
            f"stencil3d.heat_step_pallas[nb={nb}]":
                (lambda T, Ci: hk.heat_step_pallas(
                    T, Ci, 1.0, 0.1, 1.0, 1.0, 1.0, bx=bx), (f3, f3)),
            f"solver3d.apply_pallas[nb={nb}]":
                (lambda u, c: sk.apply_pallas(u, c, h2=h2, bx=bx), (f3, f3)),
            f"solver3d.apply_pallas_face[nb={nb}]":
                (lambda u, e: sk.apply_pallas(u, e, h2=h2, sd=0, bx=bx),
                 (f3, f3)),
            f"solver3d.residual_pallas[nb={nb}]":
                (lambda u, c, f: sk.residual_pallas(u, c, f, h2=h2, bx=bx),
                 (f3, f3, f3)),
            f"solver3d.jacobi_pallas[nb={nb}]":
                (lambda u, c, f, dia: sk.jacobi_pallas(
                    u, c, f, dia, omega=0.8, h2=h2, bx=bx), (f3, f3, f3, f3)),
            f"solver3d.cheb_pallas[nb={nb}]":
                (lambda u, c, f, dia, d: sk.cheb_pallas(
                    u, c, f, dia, d, a=0.5, b=0.5, h2=h2, bx=bx),
                 (f3, f3, f3, f3, f3)),
        }
        for label, (fn, avals) in targets.items():
            closed = jax.make_jaxpr(fn)(*avals)
            for eqn, scope in walk(closed):
                if eqn.primitive.name == "pallas_call":
                    findings.extend(check_call(eqn, label))
    return findings
