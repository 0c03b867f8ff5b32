"""Contracts of the comm-hiding transforms (repro/core/hide.py).

Pins the two subtle branches the static analyzer leans on:

* ``hide_apply``'s skip branch — along a dim with ``dims[d] == 1`` and
  no wrap there is no exchange, so the shell recompute is skipped; the
  result must still be bitwise identical to the unskipped spelling
  (every cell that needs fresh halos of OTHER dims lies inside those
  dims' recomputed shells).
* ``hide_communication``'s split along the exchanging dims only — on
  partial topologies (some dims on one rank, open) the non-exchanging
  dims get no shell and the interior spans them whole; with no
  exchanging dim the step is ``step_fn(*inputs)`` itself.  Results stay
  bitwise equal to ``update_halo(step(...))``.
* ``hide_communication``'s width clamp — a requested shell thinner than
  the halo is silently widened to the halo so the send slabs stay
  inside freshly computed cells; results stay bitwise equal to the
  plain ``update_halo(step(...))`` spelling.

Integer-valued f64 fields keep every sum exact, so "bitwise" is robust
to vectorization differences between slab shapes.
"""

import math

import pytest

from _mp import run


def test_hide_apply_skip_branch_bitwise():
    run("""
jax.config.update("jax_enable_x64", True)
from repro.core import init_global_grid
from repro.core.halo import _slc, update_halo
from repro.core.hide import hide_apply
from repro.kernels.solver3d import ref

# dims=(2, 1, 1) non-periodic: dims 1 and 2 take the skip branch.
g = init_global_grid(12, 10, 10, dims=(2, 1, 1))
rng = np.random.RandomState(7)
c = jnp.asarray(np.round(rng.rand(*g.local_shape) * 8))
spacing = (1.0, 1.0, 1.0)

def op(u, c):
    return ref.poisson_stencil(u, c, spacing)

def hide_apply_noskip(topo, op_fn, u, *extra, halo=1):
    # Literal copy of hide_apply's recompute loop WITHOUT the
    # dims[d]==1-and-open skip: the reference the skip must match.
    h = halo
    nd = u.ndim
    u2 = update_halo(topo, u, width=h)
    out = op_fn(u, *extra)
    for d in range(nd):
        n = u.shape[d]
        lo_in = _slc(nd, d, 0, 3 * h)
        hi_in = _slc(nd, d, n - 3 * h, n)
        lo = op_fn(u2[lo_in], *(e[lo_in] for e in extra))
        hi = op_fn(u2[hi_in], *(e[hi_in] for e in extra))
        sl = _slc(nd, d, h, 2 * h)
        out = out.at[_slc(nd, d, h, 2 * h)].set(lo[sl])
        out = out.at[_slc(nd, d, n - 2 * h, n - h)].set(hi[sl])
    return out

@g.parallel
def skipped(u):
    return hide_apply(g.topo, op, u, c)

@g.parallel
def unskipped(u):
    return hide_apply_noskip(g.topo, op, u, c)

@g.parallel
def plain(u):
    return op(update_halo(g.topo, u, width=1), c)

u = g.scatter(np.round(rng.rand(*g.global_shape) * 64))
a = np.asarray(skipped(u))
b = np.asarray(unskipped(u))
p = np.asarray(plain(u))
np.testing.assert_array_equal(a, b)   # skip branch == unskipped copy
np.testing.assert_array_equal(a, p)   # ... == the declared semantics
print("OK")
""", ndev=2)


def test_hide_communication_width_clamped_to_halo():
    run("""
jax.config.update("jax_enable_x64", True)
from repro.core import init_global_grid
from repro.stencil import fd3d as fd

g = init_global_grid(12, 10, 10, dims=(2, 1, 1))
rng = np.random.RandomState(11)
T = g.scatter(np.round(rng.rand(*g.global_shape) * 32))
Ci = g.scatter(np.round(rng.rand(*g.global_shape) * 8))

def step(T, Ci):
    Tn = fd.inn(T) + fd.inn(Ci) * (fd.d2_xi(T) + fd.d2_yi(T) + fd.d2_zi(T))
    return T.at[1:-1, 1:-1, 1:-1].set(Tn)

@g.parallel
def plain(T, Ci):
    return g.update_halo(step(T, Ci))

# width=0 requests a shell thinner than the halo; the clamp widens it
# to halo width so the exchange slabs hold freshly computed values.
@g.parallel
def clamped(T, Ci):
    return g.hide(step, (T, Ci), width=(0, 0, 0))

a = np.asarray(plain(T, Ci))
b = np.asarray(clamped(T, Ci))
np.testing.assert_array_equal(a, b)
print("OK")
""", ndev=2)


# (dims, periodic): one rank open, one rank wrapping in x, and partial
# meshes with and without a wrap along a dim that is not split.
PARTIAL_TOPOLOGIES = [
    ((1, 1, 1), (False, False, False)),
    ((1, 1, 1), (True, False, False)),
    ((2, 1, 1), (False, False, False)),
    ((2, 2, 1), (False, False, False)),
    ((2, 1, 1), (False, False, True)),
]


@pytest.mark.parametrize("outputs", [1, 2])
@pytest.mark.parametrize("dims,periodic", PARTIAL_TOPOLOGIES)
def test_hide_communication_partial_topology_bitwise(dims, periodic, outputs):
    run("""
jax.config.update("jax_enable_x64", True)
from repro.core import init_global_grid
from repro.stencil import fd3d as fd

g = init_global_grid(12, 10, 10, dims=%r, periodic=%r)
rng = np.random.RandomState(5)
A = g.scatter(np.round(rng.rand(*g.global_shape) * 32))
B = g.scatter(np.round(rng.rand(*g.global_shape) * 8))

def lap(U):
    return fd.d2_xi(U) + fd.d2_yi(U) + fd.d2_zi(U)

def one(A, B):
    return A.at[1:-1, 1:-1, 1:-1].set(fd.inn(A) + fd.inn(B) * lap(A))

def two(A, B):
    return (A.at[1:-1, 1:-1, 1:-1].set(fd.inn(A) + 2.0 * lap(B)),
            B.at[1:-1, 1:-1, 1:-1].set(fd.inn(B) - lap(A)))

step = one if %d == 1 else two

@g.parallel
def plain(A, B):
    return g.update_halo(*jax.tree_util.tree_leaves(step(A, B)))

@g.parallel
def hidden(A, B):
    return g.hide(step, (A, B), width=(3, 2, 2))

for p, q in zip(jax.tree_util.tree_leaves(plain(A, B)),
                jax.tree_util.tree_leaves(hidden(A, B))):
    np.testing.assert_array_equal(np.asarray(p), np.asarray(q))
print("OK")
""" % (dims, periodic, outputs), ndev=math.prod(dims))


# (dims, periodic, step calls): one call where no dim exchanges, else two
# slabs per exchanging dim and the interior.
@pytest.mark.parametrize("dims,periodic,calls", [
    ((1, 1, 1), (False, False, False), 1),
    ((1, 1, 1), (True, False, False), 3),
    ((2, 1, 1), (False, False, False), 3),
    ((2, 2, 1), (False, False, False), 5),
])
def test_hide_communication_splits_only_exchanging_dims(dims, periodic, calls):
    run("""
from repro.core import init_global_grid
from repro.stencil import fd3d as fd

g = init_global_grid(12, 10, 10, dims=%r, periodic=%r)
calls = []

def step(T, Ci):
    # Ring passed through by zero padding: the step itself writes no
    # slice, so every write in the program is the hide layer's.
    calls.append(T.shape)
    lap = fd.d2_xi(T) + fd.d2_yi(T) + fd.d2_zi(T)
    return T + Ci * jnp.pad(lap, 1)

f = jax.jit(jax.shard_map(
    lambda T, Ci: g.hide(step, (T, Ci), width=(3, 2, 2)),
    mesh=g.mesh, in_specs=(g.spec, g.spec), out_specs=g.spec))
T = g.zeros()
txt = f.lower(T, T).as_text()
assert len(calls) == %d, calls
if %d == 1:
    # The plain program: the whole field in one call, nothing written back.
    assert calls == [g.local_shape], calls
    assert "dynamic_update_slice" not in txt and "scatter" not in txt, txt
else:
    assert "dynamic_update_slice" in txt or "scatter" in txt, txt
    # Non-exchanging dims keep their full extent in every call.
    for d in range(3):
        if not g.topo.exchanges(d):
            assert all(s[d] == g.local_shape[d] for s in calls), calls
print("OK")
""" % (dims, periodic, calls, calls), ndev=math.prod(dims))
