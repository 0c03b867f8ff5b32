"""Fused solver3d kernels: bitwise pin vs the reference spellings,
dispatch contract (auto never raises), and the multigrid wiring.

The bitwise discipline (see ``kernels/solver3d/kernel.py``): the EAGER
block harness ``kernel.blocked_ref`` — the exact per-block arithmetic the
pallas bodies run, fed the exact wrap-mapped ghost rows the BlockSpecs
map in — must agree BITWISE with the eager reference spellings at every
block count, because outside ``jit`` both sides execute plain IEEE ops.
The compiled paths (jitted ref vs jitted interpret-mode ``pallas_call``)
are pinned bitwise at ``nb == 1`` (XLA simplifies the trip-count-1 grid
loop to straight-line code) and to a 1e-6 instruction-selection envelope
at ``nb > 1`` (FMA contraction differs inside compiled loop bodies).
"""

import functools
import os
import sys
import warnings

import numpy as np
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

import jax
import jax.numpy as jnp

from repro.core import locations as _loc
from repro.kernels import dispatch
from repro.kernels.solver3d import kernel as K
from repro.kernels.solver3d import ops
from repro.kernels.solver3d import ref as R

from _mp import run

LOCS = ("center", "xface", "yface", "zface")
SP = (0.5, 0.7, 1.1)
H2 = tuple(float(s) ** 2 for s in SP)
OMEGA = 6.0 / 7.0

# (shape, bx) covering nb = 1, 2, 3, 4 and non-cubic extents — every
# case has boundary blocks on both ends plus (nb >= 3) pure-interior ones
CASES = [
    ((8, 8, 8), 8),      # nb = 1
    ((8, 8, 8), 4),      # nb = 2: both blocks are boundary blocks
    ((12, 6, 8), 4),     # nb = 3: interior block between two boundary ones
    ((8, 8, 8), 2),      # nb = 4
    ((6, 6, 6), 6),      # nb = 1, odd-ish extent
    ((16, 10, 12), 8),   # nb = 2, non-cubic
]


def _fields(shape, dtype, loc, seed=0):
    rng = np.random.RandomState(seed)
    u = jnp.asarray(rng.rand(*shape), dtype)
    c = jnp.asarray(rng.rand(*shape) + 0.5, dtype)
    f = jnp.asarray(rng.rand(*shape), dtype)
    d0 = jnp.asarray(rng.rand(*shape), dtype)
    sd = _loc.stagger_dim(loc)
    imask = None
    if sd is not None:
        m = np.zeros(shape)
        m[1:-1, 1:-1, 1:-1] = 1.0
        imask = jnp.asarray(m, dtype)
    dia = R.full_diag(c, SP, loc, imask)
    return u, c, f, d0, dia, imask, sd


def _assert_bitwise(name, a, b):
    np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b), err_msg=name)


# ---------------------------------------------------------------------------
# eager bitwise pin: blocked_ref vs the reference spellings
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("loc", LOCS)
@pytest.mark.parametrize("shape,bx", CASES)
def test_blocked_ref_bitwise(shape, bx, loc):
    u, c, f, d0, dia, imask, sd = _fields(shape, jnp.float32, loc)
    _assert_bitwise(
        "apply",
        K.blocked_ref("apply", u, c, h2=H2, sd=sd, bx=bx),
        R.apply_op_ref(u, c, SP, loc))
    _assert_bitwise(
        "residual",
        K.blocked_ref("residual", u, c, f, h2=H2, sd=sd, imask=imask, bx=bx),
        R.residual_op_ref(u, c, f, SP, loc, imask))
    _assert_bitwise(
        "jacobi",
        K.blocked_ref("jacobi", u, c, f, dia, h2=H2, sd=sd, imask=imask,
                      bx=bx, omega=OMEGA),
        R.jacobi_sweep_ref(u, c, f, dia, omega=OMEGA, spacing=SP, loc=loc,
                           imask=imask))
    for a, b in ((None, 1.25), (0.3, 0.9)):  # first step, then a later one
        ku, kd = K.blocked_ref("cheb", u, c, f, dia, d0, h2=H2, sd=sd,
                               imask=imask, bx=bx, a=a, b=b)
        ru, rd = R.cheb_sweep_ref(u, c, f, dia, d0, a=a, b=b, spacing=SP,
                                  loc=loc, imask=imask)
        _assert_bitwise(f"cheb(a={a}) u", ku, ru)
        _assert_bitwise(f"cheb(a={a}) d", kd, rd)


def test_blocked_ref_bitwise_f64():
    """Same pin at float64 (x64 flips global state -> subprocess)."""
    run("""
jax.config.update("jax_enable_x64", True)
from repro.core import locations as _loc
from repro.kernels.solver3d import kernel as K, ref as R

SP = (0.5, 0.7, 1.1)
H2 = tuple(float(s) ** 2 for s in SP)
rng = np.random.RandomState(3)
shape = (8, 8, 8)
for loc in ("center", "xface", "yface", "zface"):
    for bx in (8, 4):
        sd = _loc.stagger_dim(loc)
        u = jnp.asarray(rng.rand(*shape))
        c = jnp.asarray(rng.rand(*shape) + 0.5)
        f = jnp.asarray(rng.rand(*shape))
        d0 = jnp.asarray(rng.rand(*shape))
        imask = None
        if sd is not None:
            m = np.zeros(shape)
            m[1:-1, 1:-1, 1:-1] = 1.0
            imask = jnp.asarray(m)
        dia = R.full_diag(c, SP, loc, imask)
        assert u.dtype == jnp.float64
        pairs = [
            (K.blocked_ref("apply", u, c, h2=H2, sd=sd, bx=bx),
             R.apply_op_ref(u, c, SP, loc)),
            (K.blocked_ref("residual", u, c, f, h2=H2, sd=sd, imask=imask,
                           bx=bx),
             R.residual_op_ref(u, c, f, SP, loc, imask)),
            (K.blocked_ref("jacobi", u, c, f, dia, h2=H2, sd=sd,
                           imask=imask, bx=bx, omega=6.0 / 7.0),
             R.jacobi_sweep_ref(u, c, f, dia, omega=6.0 / 7.0, spacing=SP,
                                loc=loc, imask=imask)),
        ]
        for a, b in ((None, 1.25), (0.3, 0.9)):
            ku, kd = K.blocked_ref("cheb", u, c, f, dia, d0, h2=H2, sd=sd,
                                   imask=imask, bx=bx, a=a, b=b)
            ru, rd = R.cheb_sweep_ref(u, c, f, dia, d0, a=a, b=b,
                                      spacing=SP, loc=loc, imask=imask)
            pairs += [(ku, ru), (kd, rd)]
        for got, want in pairs:
            assert (np.asarray(got) == np.asarray(want)).all(), (loc, bx)
print("OK")
""", ndev=1)


# ---------------------------------------------------------------------------
# compiled paths: jitted interpret-mode pallas_call vs jitted ref
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("loc", LOCS)
@pytest.mark.parametrize("shape,bx", [((8, 8, 8), 8), ((8, 8, 8), 4),
                                      ((12, 6, 8), 4)])
def test_interpret_matches_ref_jitted(shape, bx, loc):
    u, c, f, d0, dia, imask, sd = _fields(shape, jnp.float32, loc)
    nb = shape[0] // bx

    def compare(name, kfn, rfn, *args):
        got = jax.jit(kfn)(*args)
        want = jax.jit(rfn)(*args)
        if nb == 1:
            _assert_bitwise(name, got, want)
        else:
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6,
                err_msg=name)

    compare(
        "apply",
        lambda u, c: K.apply_pallas(u, c, h2=H2, sd=sd, bx=bx,
                                    interpret=True),
        lambda u, c: R.apply_op_ref(u, c, SP, loc),
        u, c)
    compare(
        "residual",
        lambda u, c, f: K.residual_pallas(u, c, f, h2=H2, sd=sd,
                                          imask=imask, bx=bx,
                                          interpret=True),
        lambda u, c, f: R.residual_op_ref(u, c, f, SP, loc, imask),
        u, c, f)
    compare(
        "jacobi",
        lambda u, c, f, dia: K.jacobi_pallas(u, c, f, dia, omega=OMEGA,
                                             h2=H2, sd=sd, imask=imask,
                                             bx=bx, interpret=True),
        lambda u, c, f, dia: R.jacobi_sweep_ref(u, c, f, dia, omega=OMEGA,
                                                spacing=SP, loc=loc,
                                                imask=imask),
        u, c, f, dia)
    compare(
        "cheb",
        lambda u, c, f, dia, d0: K.cheb_pallas(u, c, f, dia, d0, a=0.3,
                                               b=0.9, h2=H2, sd=sd,
                                               imask=imask, bx=bx,
                                               interpret=True)[0],
        lambda u, c, f, dia, d0: R.cheb_sweep_ref(u, c, f, dia, d0, a=0.3,
                                                  b=0.9, spacing=SP,
                                                  loc=loc, imask=imask)[0],
        u, c, f, dia, d0)


@pytest.mark.parametrize("loc", LOCS)
def test_ops_dispatch_interpret_vs_ref(loc):
    """Public ops: 'interpret' == 'ref' bitwise at nb=1; 'auto' on a CPU
    host IS the ref path."""
    u, c, f, d0, dia, imask, sd = _fields((8, 8, 8), jnp.float32, loc)
    kw = dict(spacing=SP, loc=loc, imask=imask, bx=8)

    def jit(fn, mode, **fixed):  # compiled-vs-compiled (nb=1: bitwise)
        return jax.jit(functools.partial(fn, use_kernel=mode, **fixed,
                                         **kw))

    _assert_bitwise(
        "jacobi",
        jit(ops.jacobi_sweep, "interpret", omega=OMEGA)(u, c, f, dia),
        jit(ops.jacobi_sweep, "ref", omega=OMEGA)(u, c, f, dia))
    _assert_bitwise(
        "residual",
        jit(ops.residual_op, "interpret")(u, c, f),
        jit(ops.residual_op, "ref")(u, c, f))
    _assert_bitwise(
        "auto==ref",
        ops.apply_op(u, c, spacing=SP, loc=loc, use_kernel="auto"),
        ops.apply_op(u, c, spacing=SP, loc=loc, use_kernel="ref"))


def test_ops_face_needs_mask():
    u, c, f, d0, dia, imask, sd = _fields((8, 8, 8), jnp.float32, "xface")
    with pytest.raises(ValueError, match="imask"):
        ops.jacobi_sweep(u, c, f, dia, omega=OMEGA, spacing=SP, loc="xface")


# ---------------------------------------------------------------------------
# dispatch contract
# ---------------------------------------------------------------------------

def test_auto_never_raises():
    """The hardened contract: 'auto' degrades, never crashes — including
    the historical nx % bx != 0 ValueError on TPU."""
    dispatch.reset_warnings()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for backend in (None, "cpu", "gpu", "tpu"):
            for dtype in (jnp.float32, jnp.float64, jnp.int32):
                for shape in ((8, 8, 8), (10, 8, 8), (7, 5, 3), (8, 8),
                              (4,)):
                    for bx in (None, 3, 5, 8):
                        for unsup in (None, "some feature"):
                            impl, b = dispatch.resolve(
                                "auto", shape=shape, dtype=dtype, bx=bx,
                                backend=backend, unsupported=unsup)
                            assert impl in ("pallas", "ref")
                            if impl == "pallas":
                                assert backend == "tpu"
                                assert shape[0] % b == 0
    dispatch.reset_warnings()


def test_auto_tpu_probe():
    dispatch.reset_warnings()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        # good config -> the kernel, with an auto-picked divisor block
        assert dispatch.resolve("auto", shape=(12, 8, 8), dtype=jnp.float32,
                                backend="tpu") == ("pallas", 6)
        assert dispatch.resolve("auto", shape=(8, 8, 8), dtype=jnp.bfloat16,
                                backend="tpu") == ("pallas", 8)
        # f64 has no compiled TPU kernel -> ref
        assert dispatch.resolve("auto", shape=(8, 8, 8), dtype=jnp.float64,
                                backend="tpu")[0] == "ref"
        # non-TPU backends are the normal ref configuration
        assert dispatch.resolve("auto", shape=(8, 8, 8), dtype=jnp.float32,
                                backend="cpu") == ("ref", None)
    dispatch.reset_warnings()


def test_auto_fallback_warns_once():
    dispatch.reset_warnings()
    args = dict(shape=(10, 8, 8), dtype=jnp.float32, bx=4, backend="tpu",
                where="test.site")
    with pytest.warns(RuntimeWarning, match="not divisible"):
        assert dispatch.resolve("auto", **args) == ("ref", None)
    with warnings.catch_warnings():  # second hit: silent
        warnings.simplefilter("error")
        assert dispatch.resolve("auto", **args) == ("ref", None)
    dispatch.reset_warnings()  # forget -> warns again
    with pytest.warns(RuntimeWarning, match="not divisible"):
        dispatch.resolve("auto", **args)
    dispatch.reset_warnings()


def test_explicit_kernel_raises():
    with pytest.raises(ValueError, match="must be divisible"):
        dispatch.resolve("interpret", shape=(10, 8, 8), dtype=jnp.float32,
                         bx=4)
    with pytest.raises(ValueError, match="dtypes"):
        dispatch.resolve("pallas", shape=(8, 8, 8), dtype=jnp.float64)
    with pytest.raises(ValueError, match="3-D"):
        dispatch.resolve("interpret", shape=(8, 8), dtype=jnp.float32)
    with pytest.raises(ValueError, match="does not support"):
        dispatch.resolve("interpret", shape=(8, 8, 8), dtype=jnp.float32,
                         unsupported="Helmholtz shifts")
    with pytest.raises(ValueError, match="unknown use_kernel"):
        dispatch.resolve("cuda", shape=(8, 8, 8), dtype=jnp.float32)


def test_pick_bx():
    assert dispatch.pick_bx((8, 8, 8)) == 8
    assert dispatch.pick_bx((12, 8, 8)) == 6
    assert dispatch.pick_bx((7, 8, 8)) == 7
    assert dispatch.pick_bx((13, 8, 8)) is None   # prime above the limit
    assert dispatch.pick_bx((1, 8, 8)) is None


@pytest.mark.parametrize("shape, kernel, itemsize, want", [
    # small planes: VMEM never binds, the largest divisor <= 8 wins
    ((34, 34, 34), "heat", 4, 2),
    ((32, 34, 34), "cheb", 4, 8),
    # 256^2 f32: 16 blocks of bx=4 rows fill the 16 MiB exactly
    ((256, 256, 256), "heat", 4, 4),
    ((256, 256, 256), "heat", 2, 8),      # bf16 halves the block
    ((18, 256, 256), "heat", 4, 3),       # hide_communication x-slab
    ((130, 130, 130), "cheb", 4, 2),
    ((130, 130, 130), "apply", 4, 5),
    # 512^2 f32: even bx=2 overflows — no block fits
    ((512, 512, 512), "heat", 4, None),
    ((16, 512, 512), "jacobi", 4, None),
])
def test_pick_bx_fits_vmem(shape, kernel, itemsize, want):
    blocks = dispatch.VMEM_BLOCKS[kernel]
    assert dispatch.pick_bx(shape, itemsize, blocks) == want
    if want is not None:
        assert blocks * dispatch.block_bytes(want, *shape[1:], itemsize) \
            <= dispatch.VMEM_LIMIT_BYTES


def test_block_bytes_pads_to_tiles():
    # (y, z) planes pad to (8, 128) f32 tiles and (16, 128) bf16 tiles
    assert dispatch.block_bytes(1, 130, 130, 4) == 136 * 256 * 4
    assert dispatch.block_bytes(2, 130, 130, 2) == 2 * 144 * 256 * 2
    assert dispatch.block_bytes(4, 256, 256, 4) == 1 << 20


def test_auto_plane_too_large_warns_once_and_falls_back():
    """On a TPU a 512^2 plane has no block that fits VMEM: ``auto``
    warns once and takes the reference, an explicit ``pallas`` raises,
    and ``interpret`` (no VMEM) still runs with a one-row block."""
    dispatch.reset_warnings()
    args = dict(shape=(512, 512, 512), dtype=jnp.float32, backend="tpu",
                where="test.wide", blocks=dispatch.VMEM_BLOCKS["heat"])
    with pytest.warns(RuntimeWarning, match="VMEM"):
        assert dispatch.resolve("auto", **args) == ("ref", None)
    with warnings.catch_warnings():  # second hit: silent
        warnings.simplefilter("error")
        assert dispatch.resolve("auto", **args) == ("ref", None)
    # an explicit block that overflows is refused by auto too
    dispatch.reset_warnings()
    with pytest.warns(RuntimeWarning, match="VMEM"):
        assert dispatch.resolve("auto", **{**args, "shape": (256, 256, 256),
                                           "bx": 8}) == ("ref", None)
    with pytest.raises(ValueError, match="VMEM"):
        dispatch.resolve("pallas", **args)
    assert dispatch.resolve("interpret", **args) == ("interpret", 1)
    dispatch.reset_warnings()


def test_recording_lists_every_resolution():
    with dispatch.recording() as rec:
        dispatch.resolve("ref", shape=(8, 8, 8), dtype=jnp.float32,
                         where="a")
        dispatch.resolve("auto", shape=(8, 8, 8), dtype=jnp.float32,
                         backend="tpu", where="b")
    assert rec == [("a", (8, 8, 8), "ref", None),
                   ("b", (8, 8, 8), "pallas", 8)]


# ---------------------------------------------------------------------------
# multigrid wiring
# ---------------------------------------------------------------------------

def _lower_cycle(use_kernel):
    from repro.core import init_global_grid, make_grid_mesh
    from repro.solvers.multigrid import (
        build_coefficients, level_spacings, make_v_cycle)
    # subset mesh: stays a 1-rank grid even when the process fakes 8 devices
    mesh1 = make_grid_mesh(3, dims=(1, 1, 1), devices=jax.devices()[:1])
    g = init_global_grid(10, 10, 10, mesh=mesh1, dtype=jnp.float32)
    grids = g.hierarchy()
    hs = level_spacings(g, grids, (0.1, 0.1, 0.1))

    def local(b, c):
        cs = build_coefficients(g, grids, c)
        v_cycle, _ = make_v_cycle(g, grids, hs, cs, use_kernel=use_kernel)
        return v_cycle(0, jnp.zeros_like(b), b)

    sm = jax.shard_map(local, mesh=g.mesh, in_specs=(g.spec, g.spec),
                       out_specs=g.spec, check_vma=False)
    b = jnp.zeros(g.local_shape, jnp.float32)
    c = jnp.ones(g.local_shape, jnp.float32)
    return jax.jit(sm).lower(b, c).as_text()


def test_ref_cycle_hlo_pinned():
    """use_kernel='ref' and 'auto' (on a CPU host) lower the V-cycle to
    byte-identical HLO — the fused plumbing costs the default path
    nothing; 'interpret' genuinely changes the program."""
    ref = _lower_cycle("ref")
    assert _lower_cycle("auto") == ref
    assert _lower_cycle("interpret") != ref


@pytest.mark.parametrize("smoother", ["jacobi", "chebyshev"])
def test_multigrid_fused_converges_like_ref(smoother):
    from repro.core import init_global_grid, make_grid_mesh
    from repro.solvers.multigrid import multigrid_solve
    mesh1 = make_grid_mesh(3, dims=(1, 1, 1), devices=jax.devices()[:1])
    g = init_global_grid(16, 16, 16, mesh=mesh1, dtype=jnp.float32)
    rng = np.random.RandomState(0)
    b = jnp.asarray(rng.standard_normal(g.local_shape), jnp.float32)
    c = jnp.ones(g.local_shape, jnp.float32)
    sp = (1.0 / 16,) * 3
    x_ref, i_ref = multigrid_solve(g, c, b, sp, smoother=smoother,
                                   use_kernel="ref")
    x_fus, i_fus = multigrid_solve(g, c, b, sp, smoother=smoother,
                                   use_kernel="interpret")
    assert i_fus.converged
    assert i_fus.iterations == i_ref.iterations
    np.testing.assert_allclose(np.asarray(x_fus), np.asarray(x_ref),
                               rtol=1e-5, atol=1e-6)


def test_fused_mg_2rank_matches_ref():
    """Fused cycle under shard_map: 2-rank fused == 2-rank ref."""
    run("""
from repro.apps.poisson import Poisson3D

p = Poisson3D(nx=8, ny=8, nz=8, dims=(2, 1, 1), dtype=jnp.float32)
x_ref, i_ref = p.solve("mg", tol=1e-5, use_kernel="ref")
x_fus, i_fus = p.solve("mg", tol=1e-5, use_kernel="interpret", bx=8)
assert i_ref.converged and i_fus.converged
assert i_ref.iterations == i_fus.iterations, (i_ref.iterations,
                                              i_fus.iterations)
a, b = p.grid.gather(x_ref), p.grid.gather(x_fus)
err = float(np.abs(a - b).max())
print("2-rank fused vs ref:", i_fus.iterations, "iters, err", err)
assert err < 1e-5, err
print("OK")
""", ndev=2)


def test_fused_mg_1rank_vs_2rank():
    """Fused solve is partitioning-independent (same global field)."""
    run("""
from repro.core import make_grid_mesh
from repro.apps.poisson import Poisson3D

multi = Poisson3D(nx=8, ny=8, nz=8, dims=(2, 1, 1), dtype=jnp.float32,
                  use_kernel="interpret", bx=8)
mesh1 = make_grid_mesh(3, dims=(1, 1, 1), devices=jax.devices()[:1])
single = Poisson3D(nx=14, ny=8, nz=8, mesh=mesh1, dtype=jnp.float32,
                   use_kernel="interpret")
assert single.grid.global_shape == multi.grid.global_shape
u_m, _ = multi.solve("mg", tol=1e-5)
u_s, _ = single.solve("mg", tol=1e-5)
a, b = multi.grid.gather(u_m), single.grid.gather(u_s)
err = float(np.abs(a - b).max() / np.abs(b).max())
print("1-rank vs 2-rank fused err", err)
assert err < 1e-4, err
print("OK")
""", ndev=2)


def test_fused_mgcg_2rank_smoke():
    """MG-preconditioned CG with the fused cycle AND the fused operator
    apply, distributed over 2 ranks."""
    run("""
from repro.apps.poisson import Poisson3D

p = Poisson3D(nx=8, ny=8, nz=8, dims=(2, 1, 1), dtype=jnp.float32,
              use_kernel="interpret", bx=8)
u, info = p.solve("mgcg", tol=1e-5)
print("mgcg/fused 2-rank:", info.iterations, "iters, relres", info.relres)
assert info.converged
assert p.residual_norm(u) < 1e-4
print("OK")
""", ndev=2)
