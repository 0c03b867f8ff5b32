"""Mutation corpus: the analyzer must CATCH each reintroduced bug class.

Every test plants one historical (or representative) distributed bug —
the clamped-BlockSpec kind from the fused-kernel PR, missing/duplicated
halo exchanges, branch-local collectives, broken ppermute tables,
unmasked/bare reductions — and asserts the matching rule fires.  A
mutant the analyzer misses is a test failure, so rule regressions show
up as escaped mutants, not as silently-green sweeps.

Marker-level and Pallas mutants run in-process (single device);
mesh-dependent mutants run on 8 fake devices via ``_mp.run``.
"""

import jax
import jax.numpy as jnp

from repro import analysis
from repro.analysis import markers

from _mp import run

jax.config.update("jax_platform_name", "cpu")


def _rules(rep):
    return {f.rule for f in rep}


# ---------------------------------------------------------------------------
# M1-M3: Pallas BlockSpec mutants (the PR 8 bug class), in-process
# ---------------------------------------------------------------------------

def _pallas_one_in_one_out(in_spec, out_spec, grid, shape=(16, 8, 8)):
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def f(x):
        return pl.pallas_call(
            kern, grid=grid, in_specs=[in_spec], out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
            interpret=True,
        )(x)

    return f, jnp.zeros(shape, jnp.float32)


def test_mutant_clamped_index_map_caught():
    # The historical bug: clamping the neighbor index silently re-reads
    # the first block instead of the neighbor block.
    from jax.experimental import pallas as pl

    f, x = _pallas_one_in_one_out(
        pl.BlockSpec((4, 8, 8), lambda i: (jnp.maximum(i - 1, 0), 0, 0)),
        pl.BlockSpec((4, 8, 8), lambda i: (i, 0, 0)),
        grid=(4,))
    rep = analysis.check(f, x)
    assert rep.by_rule("pallas-blockspec") and rep.errors()
    assert any("duplicated block" in f.message or "non-uniform" in f.message
               for f in rep.by_rule("pallas-blockspec"))


def test_mutant_nontiling_block_caught():
    from jax.experimental import pallas as pl

    f, x = _pallas_one_in_one_out(
        pl.BlockSpec((5, 8, 8), lambda i: (i, 0, 0)),
        pl.BlockSpec((5, 8, 8), lambda i: (i, 0, 0)),
        grid=(3,))
    rep = analysis.check(f, x)
    assert rep.by_rule("pallas-blockspec") and rep.errors()


def test_mutant_noniterating_output_map_caught():
    # Output map ignores the grid index: every program instance writes
    # block 0 (last-writer-wins garbage for the rest of the array).
    from jax.experimental import pallas as pl

    f, x = _pallas_one_in_one_out(
        pl.BlockSpec((4, 8, 8), lambda i: (i, 0, 0)),
        pl.BlockSpec((4, 8, 8), lambda i: (0, 0, 0)),
        grid=(4,))
    rep = analysis.check(f, x)
    assert rep.by_rule("pallas-blockspec") and rep.errors()


# ---------------------------------------------------------------------------
# M4-M5: staleness mutants (marker level), in-process
# ---------------------------------------------------------------------------

def test_mutant_loop_without_exchange_caught():
    # A time loop that steps the stencil but never exchanges: only the
    # first iteration sees fresh ghosts.
    def f(u):
        def body(k, u):
            return markers.consume(u, radius=1, site="mutant.step")

        return jax.lax.fori_loop(0, 10, body, u)

    rep = analysis.check(f, jnp.zeros((6, 6, 6)), halo=1)
    assert rep.by_rule("halo-staleness") and rep.errors()


def test_mutant_read_deeper_than_halo_caught():
    # A radius-2 custom stencil behind a width-1 exchange.
    def f(u):
        u = markers.exchange_out(u, width=1, site="mutant.halo", dims=(0,))
        u = markers.consume(u, radius=1, site="mutant.op1")
        return analysis.stencil_read(u, radius=2, site="mutant.wide_op")

    rep = analysis.check(f, jnp.zeros((8, 8, 8)), halo=1)
    assert rep.by_rule("halo-staleness") and rep.errors()


# ---------------------------------------------------------------------------
# M6-M8: congruence mutants (need a real mesh), 8 fake devices
# ---------------------------------------------------------------------------

def test_mutants_collective_congruence_caught():
    run("""
from jax.sharding import PartitionSpec as P
from repro import analysis

mesh = jax.make_mesh((4, 2), ("x", "y"))
spec = P("x", "y")
u = jnp.zeros((8, 8))

def check(f, in_specs=(spec,), out_specs=spec, args=(u,)):
    sm = jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return analysis.check(sm, *args)

# M6: collective inside only one cond branch -> ranks disagree on
# whether to enter the all-reduce (deadlock/garbage at runtime).
def branch_local(u, p):
    def yes(u):
        return jax.lax.psum(jnp.sum(u), ("x",))
    def no(u):
        return jnp.sum(u)
    return jax.lax.cond(p > 0, yes, no, u)

rep = check(branch_local, in_specs=(spec, P()), out_specs=P(),
            args=(u, jnp.zeros(())))
assert rep.by_rule("collective-congruence") and rep.errors(), rep.summary()

# M7: partial ppermute table (missing the (2, 3) pair).
def partial(u):
    return jax.lax.ppermute(u, "x", [(0, 1), (1, 2)])
rep = check(partial)
assert any("partial" in f.message
           for f in rep.by_rule("collective-congruence")), rep.summary()

# M8: duplicate destination (two ranks send to rank 1).
def dup(u):
    return jax.lax.ppermute(u, "x", [(0, 1), (2, 1)])
rep = check(dup)
assert any("destination" in f.message
           for f in rep.by_rule("collective-congruence")), rep.summary()
print("OK")
""", ndev=8)


# ---------------------------------------------------------------------------
# M9-M11: reduction-exactness mutants, 8 fake devices
# ---------------------------------------------------------------------------

def test_mutants_reduction_exactness_caught():
    run("""
jax.config.update("jax_enable_x64", True)
import repro
from jax.sharding import PartitionSpec as P
from repro import analysis
from repro.core import init_global_grid
from repro.solvers import reductions as red

g = init_global_grid(10, 10, 10, dims=(2, 2, 2), dtype=jnp.float64)

def check(f, *args):
    sm = jax.shard_map(f, mesh=g.mesh, in_specs=(g.spec,) * len(args),
                       out_specs=P(), check_vma=False)
    return analysis.check(sm, *args)

u = jnp.zeros(g.stacked_shape, jnp.float64)

# M9: blessed reduction but NO ownership mask -- overlap cells are
# double-counted across ranks.
rep = check(lambda A: red.psum(g.topo, jnp.sum(A * 1.0)), u)
assert any("mask" in f.message.lower()
           for f in rep.by_rule("reduction-exactness")), rep.summary()
assert rep.errors()

# M10: bare jax.lax.psum bypassing repro.solvers.reductions entirely.
names = tuple(g.mesh.axis_names)
def bare(A):
    m = red.owned_mask(g, dtype=A.dtype)
    return jax.lax.psum(jnp.sum(A * m), names)
rep = check(bare, u)
assert any("bare" in f.message
           for f in rep.by_rule("reduction-exactness")), rep.summary()

# M11: f32 accumulator under x64 -- the stopping test loses half its
# mantissa (warning, not error).
uf = jnp.zeros(g.stacked_shape, jnp.float32)
def f32acc(A):
    m = red.owned_mask(g, dtype=A.dtype)
    return red.psum(g.topo, jnp.sum(A * m))
rep = check(f32acc, uf)
warns = [f for f in rep.by_rule("reduction-exactness")
         if f.severity == "warning"]
assert warns, rep.summary()
print("OK")
""", ndev=8)


# ---------------------------------------------------------------------------
# M12: redundant double exchange (perf mutant), 8 fake devices
# ---------------------------------------------------------------------------

def test_mutant_double_exchange_caught():
    run("""
jax.config.update("jax_enable_x64", True)
import repro
from repro import analysis
from repro.core import init_global_grid
from repro.kernels.solver3d import ref

g = init_global_grid(10, 10, 10, dims=(2, 2, 2), dtype=jnp.float64)
c = jnp.ones(tuple(g.local_shape), jnp.float64)

def step(u):
    u = g.update_halo(g.update_halo(u))   # the mutation: doubled
    return ref.poisson_stencil(u, c, (1.0, 1.0, 1.0))

sm = jax.shard_map(step, mesh=g.mesh, in_specs=(g.spec,),
                   out_specs=g.spec, check_vma=False)
rep = analysis.check(sm, jnp.zeros(g.stacked_shape, jnp.float64))
red_f = rep.by_rule("redundant-exchange")
assert red_f and all(f.severity == "perf" for f in red_f), rep.summary()
assert not rep.errors(), rep.summary()
print("OK")
""", ndev=8)


# ---------------------------------------------------------------------------
# M13: a real solver spelling with the exchange deleted, 8 fake devices
# ---------------------------------------------------------------------------

def test_mutant_solver_loop_missing_exchange_caught():
    run("""
jax.config.update("jax_enable_x64", True)
import repro
from repro import analysis
from repro.core import init_global_grid
from repro.kernels.solver3d import ref

g = init_global_grid(10, 10, 10, dims=(2, 2, 2), dtype=jnp.float64)
c = jnp.ones(tuple(g.local_shape), jnp.float64)

def sweep(u):
    # 10 damped-Jacobi-ish sweeps with the per-iteration halo exchange
    # deleted -- iteration 2+ smooths against stale ghost planes.
    def body(k, u):
        Au = ref.poisson_stencil(u, c, (1.0, 1.0, 1.0))
        return u - 0.1 * Au

    return jax.lax.fori_loop(0, 10, body, u)

sm = jax.shard_map(sweep, mesh=g.mesh, in_specs=(g.spec,),
                   out_specs=g.spec, check_vma=False)
rep = analysis.check(sm, jnp.zeros(g.stacked_shape, jnp.float64))
assert rep.by_rule("halo-staleness") and rep.errors(), rep.summary()

# ... and restoring the exchange silences it.
def fixed(u):
    def body(k, u):
        u = g.update_halo(u)
        Au = ref.poisson_stencil(u, c, (1.0, 1.0, 1.0))
        return u - 0.1 * Au

    return jax.lax.fori_loop(0, 10, body, u)

sm2 = jax.shard_map(fixed, mesh=g.mesh, in_specs=(g.spec,),
                    out_specs=g.spec, check_vma=False)
rep2 = analysis.check(sm2, jnp.zeros(g.stacked_shape, jnp.float64))
assert not rep2.errors(), rep2.summary()
print("OK")
""", ndev=8)
