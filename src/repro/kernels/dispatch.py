"""Shared ``use_kernel`` dispatch for the Pallas kernel families.

One contract for every fused op (``kernels/stencil3d``,
``kernels/solver3d``)::

    use_kernel = "auto" | "pallas" | "interpret" | "ref"

* ``"ref"`` — always the pure-jnp reference spelling.
* ``"auto"`` — the Pallas kernel when a CAPABILITY PROBE passes
  (TPU backend, supported dtype, 3-D field, x extent divisible by a
  block size whose working set fits VMEM), otherwise a graceful
  fallback to ``"ref"``.  Auto NEVER
  raises: a probe failure that would have been a crash on the explicit
  path (e.g. ``nx % bx != 0`` on an odd rank count or a coarse MG
  level) degrades to the reference with a one-time warning instead.
* ``"pallas"`` / ``"interpret"`` — the kernel is demanded explicitly;
  a failed probe is a programming error and raises ``ValueError`` (this
  preserves the historical ``heat_step`` contract).

:func:`resolve` is the single entry point; it returns the concrete
implementation (``"pallas"``, ``"interpret"`` or ``"ref"``) plus the
block size to use.  It runs at trace time (plain Python), so the choice
is baked into the jitted program and costs nothing at run time.
"""

from __future__ import annotations

import contextlib
import warnings

MODES = ("auto", "pallas", "interpret", "ref")

# Compiled TPU kernels: no f64 (TPU VPU) — interpret mode (plain XLA
# ops on the host backend) additionally handles f64.
PALLAS_DTYPES = ("float32", "bfloat16")
INTERPRET_DTYPES = ("float32", "bfloat16", "float16", "float64")

_WARNED: set = set()


def warn_once(key, msg: str) -> None:
    """One warning per (reason, site) pair per process — auto fallbacks
    must be visible but must not spam a 100-sweep smoother loop."""
    if key in _WARNED:
        return
    _WARNED.add(key)
    warnings.warn(msg, RuntimeWarning, stacklevel=3)


def reset_warnings() -> None:
    """Forget warn-once state (tests)."""
    _WARNED.clear()


# Scoped VMEM the TPU compiler grants one kernel by default; past it,
# compilation fails with "Scoped allocation with size ... and limit
# 16.00M" (v5e).
VMEM_LIMIT_BYTES = 16 << 20

# Each kernel's VMEM working set in (bx, ny, nz) blocks: twice its mapped
# in/out streams (the pipeline double-buffers each one) plus what Mosaic
# keeps for temporaries.  Rounded up from compile-only measurements on
# v5e: the smallest vmem limit that compiles, over the block footprint,
# at planes of 18^2 to 512^2 (``tests/test_tpu_compile.py`` pins the
# shapes the apps launch).  Face kernels stream the interior mask too.
VMEM_BLOCKS = {
    "heat": 16,            # 5 streams
    "apply": 20,           # 7
    "residual": 23,        # 8
    "jacobi": 22,          # 9
    "cheb": 26,            # 11
    "apply_face": 23,      # 7
    "residual_face": 25,   # 9
    "jacobi_face": 29,     # 10
    "cheb_face": 33,       # 12
}


def block_bytes(bx: int, ny: int, nz: int, itemsize: int) -> int:
    """VMEM bytes of one ``(bx, ny, nz)`` block in the TPU tiled layout:
    each (y, z) plane padded to whole (sublane, 128-lane) tiles, with
    8 sublanes of 32-bit words (16 for bf16)."""
    sub = 8 * max(1, 4 // itemsize)
    return bx * -(-ny // sub) * sub * -(-nz // 128) * 128 * itemsize


def fits_vmem(bx: int, shape, itemsize: int, blocks: int) -> bool:
    _, ny, nz = shape
    return blocks * block_bytes(bx, ny, nz, itemsize) <= VMEM_LIMIT_BYTES


def pick_bx(shape, itemsize: int = 4, blocks: int = 1,
            limit: int = 8) -> int | None:
    """Largest x-block extent ``2..limit`` that divides ``nx`` and whose
    working set of ``blocks`` blocks (see :data:`VMEM_BLOCKS`) fits
    :data:`VMEM_LIMIT_BYTES`; None if none does.  Every MG level stays
    usable (the coarsest local extents 6 and 4 pick 6 and 4), and wide
    planes get thin blocks: bx 4 for the heat kernel on 256^2 f32
    planes, none at all on 512^2."""
    nx = int(shape[0])
    for b in range(min(limit, nx), 1, -1):
        if nx % b == 0 and fits_vmem(b, shape, itemsize, blocks):
            return b
    return None


_RECORDS: list | None = None


@contextlib.contextmanager
def recording():
    """Collect ``(where, shape, impl, bx)`` for every :func:`resolve`
    made inside the block (at trace time, so only fresh traces show)."""
    global _RECORDS
    outer, _RECORDS = _RECORDS, []
    try:
        yield _RECORDS
    finally:
        _RECORDS = outer


def resolve(use_kernel: str, *, shape, dtype, bx: int | None = None,
            backend: str | None = None, unsupported: str | None = None,
            where: str = "kernel",
            blocks: int = max(VMEM_BLOCKS.values())) -> tuple[str, int | None]:
    """Resolve ``use_kernel`` to ``(impl, bx)``.

    ``impl`` is ``"pallas"``, ``"interpret"`` or ``"ref"``; ``bx`` is the
    x-block extent for the kernel paths (None for ref).  ``unsupported``
    names a feature the kernels do not implement (Helmholtz shift,
    hidden/overlapped apply, ...): auto falls back to ref silently —
    it is an architectural limit, not a broken configuration — while an
    explicit kernel request raises.  ``backend`` overrides
    ``jax.default_backend()`` (tests probe the TPU path from CPU).
    ``blocks`` is the kernel's VMEM working set in blocks
    (:data:`VMEM_BLOCKS`; the default is the largest kernel's).
    """
    impl, b = _resolve(use_kernel, shape=shape, dtype=dtype, bx=bx,
                       backend=backend, unsupported=unsupported, where=where,
                       blocks=blocks)
    if _RECORDS is not None:
        _RECORDS.append((where, tuple(shape), impl, b))
    return impl, b


def _resolve(use_kernel, *, shape, dtype, bx, backend, unsupported, where,
             blocks):
    if use_kernel not in MODES:
        raise ValueError(f"unknown use_kernel={use_kernel!r}; pick from {MODES}")
    if use_kernel == "ref":
        return "ref", None
    dtype = jnp_dtype(dtype)
    name = str(dtype)

    if use_kernel == "auto":
        if unsupported is not None:
            return "ref", None
        if backend is None:
            import jax
            backend = jax.default_backend()
        if backend != "tpu":
            # CPU/GPU backends run the reference spelling; this is the
            # normal non-TPU configuration, not a degraded one.
            return "ref", None
        if len(shape) != 3:
            warn_once((where, "ndim", len(shape)),
                      f"{where}: use_kernel='auto' needs a 3-D field, got "
                      f"{len(shape)}-D — falling back to the reference")
            return "ref", None
        if name not in PALLAS_DTYPES:
            warn_once((where, "dtype", name),
                      f"{where}: use_kernel='auto' on TPU supports "
                      f"{PALLAS_DTYPES}, got {name} — falling back to the "
                      f"reference")
            return "ref", None
        nx = int(shape[0])
        b = bx if bx is not None else pick_bx(shape, dtype.itemsize, blocks)
        if b is None:
            warn_once((where, "vmem", tuple(shape)),
                      f"{where}: no x-block of the local shape "
                      f"{tuple(shape)} both divides nx and fits "
                      f"{VMEM_LIMIT_BYTES >> 20} MiB of VMEM — falling back "
                      f"to the reference")
            return "ref", None
        if nx % b != 0:
            warn_once((where, "divisibility", nx, b),
                      f"{where}: local extent nx={nx} is not divisible by "
                      f"block bx={b} — falling back to the reference "
                      f"(pass bx=None to auto-pick a divisor)")
            return "ref", None
        if not fits_vmem(b, shape, dtype.itemsize, blocks):
            warn_once((where, "vmem", tuple(shape), b),
                      f"{where}: block bx={b} of the local shape "
                      f"{tuple(shape)} does not fit {VMEM_LIMIT_BYTES >> 20} "
                      f"MiB of VMEM — falling back to the reference")
            return "ref", None
        return "pallas", b

    # explicit "pallas" / "interpret": probe failures raise
    if unsupported is not None:
        raise ValueError(
            f"{where}: use_kernel={use_kernel!r} does not support "
            f"{unsupported} (use 'ref' or 'auto')")
    if len(shape) != 3:
        raise ValueError(
            f"{where}: use_kernel={use_kernel!r} needs a 3-D field, got "
            f"shape {tuple(shape)}")
    allowed = PALLAS_DTYPES if use_kernel == "pallas" else INTERPRET_DTYPES
    if name not in allowed:
        raise ValueError(
            f"{where}: use_kernel={use_kernel!r} supports dtypes {allowed}, "
            f"got {name}")
    b = bx if bx is not None else pick_bx(shape, dtype.itemsize, blocks)
    if b is None:
        if use_kernel == "pallas":
            raise ValueError(
                f"{where}: no x-block of the local shape {tuple(shape)} both "
                f"divides nx and fits {VMEM_LIMIT_BYTES >> 20} MiB of VMEM")
        b = 1  # the interpreter has no VMEM limit
    if shape[0] % b != 0:
        raise ValueError(f"nx={shape[0]} must be divisible by block bx={b}")
    return use_kernel, b


def jnp_dtype(dtype):
    import jax.numpy as jnp

    return jnp.dtype(dtype)
