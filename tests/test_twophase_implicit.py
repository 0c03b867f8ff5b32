"""Implicit (backward-Euler) two-phase pressure solve: operator oracle,
explicit-vs-implicit agreement, stability beyond the explicit dt limit,
and periodic staggered smoke — all on multi-rank topologies."""

from _mp import run


def test_pressure_operator_matches_numpy():
    """The distributed Helmholtz-like pressure operator, its rhs assembly,
    and the staggered Darcy fluxes == independent NumPy slicing formulas;
    the hide_apply overlap application is bitwise-equivalent (atol 1e-12)."""
    run(
        """
jax.config.update("jax_enable_x64", True)
from repro.apps.twophase import TwoPhase3D
from repro.apps.twophase_ops import pressure_apply
from repro.fields import Field, FieldSet
from repro import fields

app = TwoPhase3D(nx=10, ny=8, nz=8, dims=(2, 2, 2), method="cg", dt=3e-4)
g = app.grid
N = g.global_shape
rng = np.random.RandomState(0)
GPe = rng.rand(*N)
Gphi = 0.005 + 0.02 * rng.rand(*N)
Kg = (Gphi / app.phi0) ** app.npow
Dg = 1.0 / app.dt + (app.phi0 / app.eta0) * (Gphi / app.phi0) ** app.m
Pe, K, D = g.scatter(GPe), g.scatter(Kg), g.scatter(Dg)

# halo-update the outputs so gather() sees computed values at the seams
def plain(u, k, d):
    return g.update_halo(pressure_apply(g, u, k, d, app.spacing))

def hidden(u, k, d):
    return g.update_halo(pressure_apply(g, u, k, d, app.spacing, hide=True))

sm = lambda f: jax.jit(jax.shard_map(
    f, mesh=g.mesh, in_specs=(g.spec,) * 3, out_specs=g.spec,
    check_vma=False))
A1 = g.gather(sm(plain)(Pe, K, D))
A2 = g.gather(sm(hidden)(Pe, K, D))

# independent NumPy reference: diag*u - div(k grad u), flux-form
inner = (slice(1, -1),) * 3
h2 = np.asarray(app.spacing) ** 2
u0, k0 = GPe[inner], Kg[inner]
acc = np.zeros_like(u0)
for d in range(3):
    sp = [slice(1, -1)] * 3; sp[d] = slice(2, None)
    sm_ = [slice(1, -1)] * 3; sm_[d] = slice(None, -2)
    acc += (0.5 * (k0 + Kg[tuple(sp)]) * (GPe[tuple(sp)] - u0)
            - 0.5 * (k0 + Kg[tuple(sm_)]) * (u0 - GPe[tuple(sm_)])) / h2[d]
ref = np.zeros_like(GPe)
ref[inner] = Dg[inner] * u0 - acc
np.testing.assert_allclose(A1, ref, rtol=1e-12, atol=1e-12)
np.testing.assert_allclose(A2, A1, rtol=0, atol=1e-12)

# rhs assembly: Pe/dt - d_z(k_zface) on the interior, zero ring
S = FieldSet(Pe=Field(g, Pe, "center"), phi=Field(g, g.scatter(Gphi), "center"))
_, _, rhs = app._assemble(S.Pe, S.phi)
kz = 0.5 * (Kg[1:-1, 1:-1, 1:] + Kg[1:-1, 1:-1, :-1])
ref_rhs = np.zeros_like(GPe)
ref_rhs[inner] = GPe[inner] / app.dt - np.diff(kz, axis=2) / app.dz
np.testing.assert_allclose(g.gather(g.update_halo_g(rhs.data)), ref_rhs,
                           rtol=1e-12, atol=1e-12)

# staggered Darcy fluxes (face FieldSet) == NumPy on the valid arrays
Q = app.fluxes(S)
kxf = 0.5 * (Kg[1:, :, :] + Kg[:-1, :, :])
np.testing.assert_allclose(fields.gather(Q.qx),
                           -kxf * np.diff(GPe, axis=0) / app.dx, rtol=1e-12)
kzf = 0.5 * (Kg[:, :, 1:] + Kg[:, :, :-1])
np.testing.assert_allclose(fields.gather(Q.qz),
                           -kzf * (np.diff(GPe, axis=2) / app.dz - 1.0),
                           rtol=1e-12)
print("OK")
""",
        ndev=8,
    )


def test_implicit_matches_explicit_small_dt():
    """Acceptance: over 10 small-dt steps on a multi-rank grid, the
    implicit (mgcg) integrator matches the explicit one to rtol 1e-5, and
    the distributed implicit run matches the independent NumPy
    backward-Euler oracle."""
    run(
        """
jax.config.update("jax_enable_x64", True)
from repro.apps.twophase import TwoPhase3D
from repro import fields

kw = dict(nx=10, ny=10, nz=10, dims=(2, 2, 2))
dt = 1e-8
ex = TwoPhase3D(**kw, hide=None, dt=dt)
assert ex.dt == dt  # below the stability limit: not clamped
Se, infos_e = ex.run(10)
assert infos_e == []
im = TwoPhase3D(**kw, method="mgcg", dt=dt, tol=1e-12)
Si, infos = im.run(10)
assert len(infos) == 10 and all(i.converged for i in infos)

Pe_e, Pe_i = fields.gather(Se.Pe), fields.gather(Si.Pe)
phi_e, phi_i = fields.gather(Se.phi), fields.gather(Si.phi)
pe_rel = np.abs(Pe_i - Pe_e).max() / np.abs(Pe_e).max()
phi_rel = np.abs(phi_i - phi_e).max() / np.abs(phi_e).max()
print("Pe rel", pe_rel, "phi rel", phi_rel)
assert pe_rel < 1e-5, pe_rel
assert phi_rel < 1e-5, phi_rel

# distributed implicit == sequential NumPy backward Euler
Pe_ref, phi_ref = im.oracle(10)
err = np.abs(Pe_i - Pe_ref).max() / np.abs(Pe_ref).max()
print("oracle rel err", err)
assert err < 1e-6, err
assert np.abs(phi_i - phi_ref).max() < 1e-12
print("OK")
""",
        ndev=8,
    )


def test_implicit_stable_beyond_explicit_limit():
    """Acceptance: the implicit step is stable at dt >= 10x the explicit
    stability limit (where the explicit scheme is clamped), every
    per-step solve converges, and the cg/mgcg integrators agree."""
    run(
        """
jax.config.update("jax_enable_x64", True)
from repro.apps.twophase import TwoPhase3D
from repro import fields

kw = dict(nx=10, ny=10, nz=10, dims=(2, 2, 2))
ex = TwoPhase3D(**kw, hide=None, dt=1.0)       # clamped to the limit
assert ex.dt == ex.dt_limit
im = TwoPhase3D(**kw, method="mgcg")           # default dt: 10x the limit
assert im.dt >= 10.0 * ex.dt_limit
Si, infos = im.run(20)
assert all(i.converged for i in infos), [i.relres for i in infos]
Pe, phi = fields.gather(Si.Pe), fields.gather(Si.phi)
assert np.isfinite(Pe).all() and np.isfinite(phi).all()
assert np.abs(Pe).max() < 10.0, np.abs(Pe).max()
assert phi.min() >= 1e-4 and phi.max() <= 0.25

# plain-CG implicit agrees with mgcg (same system, same tolerance)
ic = TwoPhase3D(**kw, method="cg", dt=im.dt, tol=1e-10)
im2 = TwoPhase3D(**kw, method="mgcg", dt=im.dt, tol=1e-10)
Sc, infos_c = ic.run(5)
Sm, infos_m = im2.run(5)
diff = np.abs(fields.gather(Sc.Pe) - fields.gather(Sm.Pe)).max()
print("cg iters", [i.iterations for i in infos_c],
      "mgcg iters", [i.iterations for i in infos_m], "diff", diff)
assert diff < 1e-7, diff
# the Helmholtz-shifted cycle must actually help
assert sum(i.iterations for i in infos_m) < sum(i.iterations for i in infos_c)
print("OK")
""",
        ndev=8,
    )


def test_twophase_smoke_2rank():
    """CI smoke: one implicit (mgcg, overlap) two-phase step on 2 CPU
    ranks converges and stays finite."""
    run(
        """
jax.config.update("jax_enable_x64", True)
from repro.apps.twophase import TwoPhase3D
from repro import fields

app = TwoPhase3D(nx=10, ny=10, nz=10, dims=(2, 1, 1), method="mgcg",
                 overlap=True, tol=1e-8)
S, infos = app.run(2)
assert len(infos) == 2 and all(i.converged for i in infos), infos
Pe = fields.gather(S.Pe)
assert np.isfinite(Pe).all() and np.abs(Pe).max() < 10.0
print("iters", [i.iterations for i in infos], "OK")
""",
        ndev=2,
        timeout=900,
    )


def test_periodic_twophase_smoke():
    """Periodic staggered halos: the explicit two-phase step with periodic
    x/y dims gives the SAME global field on 8 ranks as on 1 rank (the
    wraparound semantics are topology-independent), with and without
    communication hiding, and the face-located Darcy fluxes halo-update
    cleanly across the periodic wrap."""
    run(
        """
jax.config.update("jax_enable_x64", True)
from repro.core import make_grid_mesh
from repro.apps.twophase import TwoPhase3D
from repro import fields

per = (True, True, False)
multi = TwoPhase3D(nx=10, ny=10, nz=10, dims=(2, 2, 2), hide=None,
                   periodic=per)
S, _ = multi.run(5)
mesh1 = make_grid_mesh(3, dims=(1, 1, 1), devices=jax.devices()[:1])
single = TwoPhase3D(nx=18, ny=18, nz=18, mesh=mesh1, hide=None,
                    periodic=per)
assert single.grid.global_shape == multi.grid.global_shape
S1, _ = single.run(5)
np.testing.assert_array_equal(fields.gather(S.Pe), fields.gather(S1.Pe))
np.testing.assert_array_equal(fields.gather(S.phi), fields.gather(S1.phi))

# hide path wraps identically
hid = TwoPhase3D(nx=10, ny=10, nz=10, dims=(2, 2, 2), hide=(2, 2, 2),
                 periodic=per)
Sh, _ = hid.run(5)
np.testing.assert_array_equal(fields.gather(Sh.Pe), fields.gather(S1.Pe))

# face fluxes on periodic dims: allowed (was rejected) and finite
Q = multi.fluxes(S)
for q in Q:
    assert np.isfinite(np.asarray(q.data)).all()

# implicit + periodic is now supported (wrap-aware solve masks); the
# capability check only rejects genuinely unsupported combos
try:
    TwoPhase3D(nx=7, ny=7, nz=7, dims=(2, 2, 2), method="mgcg")
    raise SystemExit("expected ValueError for an uncoarsenable mgcg grid")
except ValueError as e:
    assert "coarsen" in str(e)
print("OK")
""",
        ndev=8,
        # Bitwise across slab shapes needs FMA-free codegen: XLA:CPU
        # contracts a*b+c to an FMA in some loop shapes and not others,
        # so the hidden step's 4-wide z-slabs would round differently
        # from the full-width step on an FMA-capable host.
        xla_flags="--xla_cpu_max_isa=AVX",
    )


def test_periodic_implicit_twophase_single_vs_multi_rank():
    """Periodic implicit (mgcg) two-phase steps: 8 ranks match 1 rank on
    the same global problem — the wrap-aware masks, the nonsingular
    Helmholtz-shifted solve, and the periodic V-cycle are all
    layout-independent.  cg + overlap (hide_apply) stays consistent."""
    run(
        """
jax.config.update("jax_enable_x64", True)
from repro.core import make_grid_mesh
from repro.apps.twophase import TwoPhase3D
from repro import fields

per = (True, True, False)
kw = dict(method="mgcg", tol=1e-10, periodic=per)
multi = TwoPhase3D(nx=10, ny=10, nz=10, dims=(2, 2, 2), **kw)
S, infos = multi.run(3)
assert all(i.converged for i in infos)
mesh1 = make_grid_mesh(3, dims=(1, 1, 1), devices=jax.devices()[:1])
single = TwoPhase3D(nx=18, ny=18, nz=18, mesh=mesh1, **kw)
assert single.grid.global_shape == multi.grid.global_shape
S1, infos1 = single.run(3)
assert all(i.converged for i in infos1)
dPe = np.abs(fields.gather(S.Pe) - fields.gather(S1.Pe)).max()
dphi = np.abs(fields.gather(S.phi) - fields.gather(S1.phi)).max()
print("mgcg iters", [i.iterations for i in infos],
      "vs", [i.iterations for i in infos1], "dPe", dPe, "dphi", dphi)
assert dPe < 1e-12 and dphi < 1e-12, (dPe, dphi)

# the overlapped (hide_apply) implicit operator wraps identically
hid = TwoPhase3D(nx=10, ny=10, nz=10, dims=(2, 2, 2), method="cg",
                 overlap=True, tol=1e-10, periodic=per)
Sh, infosh = hid.run(3)
assert all(i.converged for i in infosh)
dPe_h = np.abs(fields.gather(Sh.Pe) - fields.gather(S1.Pe)).max()
print("cg+hide dPe", dPe_h)
assert dPe_h < 1e-9, dPe_h
print("OK")
""",
        ndev=8,
        timeout=900,
    )
