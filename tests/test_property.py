"""Hypothesis property tests on system invariants."""

import os
import sys

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

import jax
import jax.numpy as jnp


@settings(max_examples=30, deadline=None)
@given(
    nprocs=st.integers(1, 4096),
    ndims=st.integers(1, 3),
)
def test_dims_create_invariants(nprocs, ndims):
    from repro.core import dims_create

    dims = dims_create(nprocs, ndims)
    assert len(dims) == ndims
    assert int(np.prod(dims)) == nprocs
    assert list(dims) == sorted(dims, reverse=True)


@settings(max_examples=20, deadline=None)
@given(
    shape=st.tuples(st.integers(1, 4), st.integers(1, 300)),
    scale=st.floats(1e-6, 1e6),
    p=st.sampled_from([1, 4]),
    data=st.data(),
)
def test_quantize_roundtrip_bound(shape, scale, p, data):
    """|dequant(quant(x)) - x| <= per-block bound, any shape/scale/codebook."""
    from repro.optim.quant import BLOCK, dequantize, quantize

    rng = np.random.RandomState(data.draw(st.integers(0, 2 ** 31 - 1)))
    x = jnp.asarray(rng.randn(*shape) * scale, jnp.float32)
    back = dequantize(quantize(x, p=p), p=p)
    # per-block error bound: amax * (1/127) for p=1; amax * p/127-ish for p=4
    xb = np.asarray(x)
    n = xb.shape[-1]
    nb = -(-n // BLOCK)
    pad = np.pad(xb, [(0, 0)] * (xb.ndim - 1) + [(0, nb * BLOCK - n)])
    blocks = pad.reshape(*xb.shape[:-1], nb, BLOCK)
    amax = np.abs(blocks).max(-1, keepdims=True)
    bound = np.repeat(amax * (1.05 / 127 if p == 1 else 4.2 / 127), BLOCK, -1)
    bound = bound.reshape(*xb.shape[:-1], nb * BLOCK)[..., :n]
    err = np.abs(np.asarray(back) - xb)
    assert (err <= bound + 1e-12).all()


@settings(max_examples=10, deadline=None)
@given(
    T=st.sampled_from([16, 32, 48]),
    window=st.integers(1, 64),
    seed=st.integers(0, 10_000),
)
def test_swa_block_local_equals_dense(T, window, seed):
    """Block-local sliding-window attention == dense masked softmax."""
    from repro.kernels.swa import swa_ref
    from repro.models.attention import _attend_swa, _expand_kv

    rng = np.random.RandomState(seed)
    B, H, Hkv, D = 1, 2, 1, 8
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.float32) * 0.4
    k = jnp.asarray(rng.randn(B, Hkv, T, D), jnp.float32) * 0.4
    v = jnp.asarray(rng.randn(B, Hkv, T, D), jnp.float32)
    ref = swa_ref(q, k, v, window=window)
    got = _attend_swa(
        q.transpose(0, 2, 1, 3),
        _expand_kv(k.transpose(0, 2, 1, 3), H),
        _expand_kv(v.transpose(0, 2, 1, 3), H),
        window=window, positions=jnp.arange(T), q_chunk=16,
    )
    np.testing.assert_allclose(
        np.asarray(got.transpose(0, 2, 1, 3)), np.asarray(ref),
        rtol=3e-5, atol=3e-5,
    )


@settings(max_examples=10, deadline=None)
@given(
    T=st.sampled_from([8, 16, 24]),
    chunk=st.sampled_from([2, 4, 8, 5]),
    seed=st.integers(0, 10_000),
)
def test_ssd_chunk_invariance(T, chunk, seed):
    """SSD output must not depend on the chunk size."""
    from repro.kernels.ssd import ssd_chunked_ref, ssd_ref

    rng = np.random.RandomState(seed)
    Ba, H, G, N, P = 1, 2, 1, 4, 8
    x = jnp.asarray(rng.randn(Ba, T, H, P), jnp.float32)
    dt = jnp.asarray(rng.rand(Ba, T, H) * 0.2 + 0.01, jnp.float32)
    A = jnp.asarray(-np.abs(rng.rand(H)) - 0.1, jnp.float32)
    B = jnp.asarray(rng.randn(Ba, T, G, N), jnp.float32) * 0.4
    C = jnp.asarray(rng.randn(Ba, T, G, N), jnp.float32) * 0.4
    y0, h0 = ssd_ref(x, dt, A, B, C)
    c = max(cc for cc in range(1, chunk + 1) if T % cc == 0)
    y1, h1 = ssd_chunked_ref(x, dt, A, B, C, chunk=c)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), rtol=3e-4, atol=3e-4)


@settings(max_examples=15, deadline=None)
@given(
    vocab=st.integers(100, 3000),
    batch=st.integers(1, 4),
    seq=st.integers(2, 33),
    step=st.integers(0, 1 << 20),
)
def test_data_pipeline_pure_function_of_step(vocab, batch, seq, step):
    from repro.data import SyntheticLMData

    d = SyntheticLMData(vocab=vocab, batch=batch, seq=seq, seed=1)
    b1 = d.batch_at(jnp.asarray(step))
    b2 = d.batch_at(jnp.asarray(step))
    np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    t = np.asarray(b1["tokens"])
    assert t.min() >= 0 and t.max() < vocab
    np.testing.assert_array_equal(
        np.asarray(b1["labels"])[:, :-1], t[:, 1:]
    )


@settings(max_examples=30, deadline=None)
@given(
    shape=st.tuples(st.integers(3, 9), st.integers(3, 9), st.integers(3, 9)),
    d=st.integers(0, 2),
    seed=st.integers(0, 10_000),
)
def test_fields_ops_diff_adjointness(shape, d, seed):
    """Summation-by-parts adjointness of the staggered differences:
    <diff_to_face(c), f> == -<c, diff_to_center(f)> whenever f's plane 0
    and its dead plane along d vanish (homogeneous flux BCs) — the
    discrete div = -grad^T identity every staggered solve relies on."""
    from repro.fields.ops import diff_to_center, diff_to_face

    rng = np.random.RandomState(seed)
    c = rng.randn(*shape).astype(np.float32)
    f = rng.randn(*shape).astype(np.float32)
    edge = [slice(None)] * 3
    edge[d] = np.array([0, shape[d] - 1])
    f[tuple(edge)] = 0.0
    h = float(0.5 + rng.rand())
    lhs = float((np.asarray(diff_to_face(jnp.asarray(c), d, h)) * f).sum())
    rhs = float((c * np.asarray(diff_to_center(jnp.asarray(f), d, h))).sum())
    scale = (np.linalg.norm(c) * np.linalg.norm(f)) / h + 1.0
    assert abs(lhs + rhs) <= 1e-4 * scale, (lhs, rhs)


@settings(max_examples=30, deadline=None)
@given(
    shape=st.tuples(st.integers(3, 9), st.integers(3, 9), st.integers(3, 9)),
    d=st.integers(0, 2),
    seed=st.integers(0, 10_000),
)
def test_fields_ops_avg_adjointness(shape, d, seed):
    """<avg_to_face(c), f> == <c, avg_to_center(f)> under the same
    boundary-plane conditions (interpolation is its own transpose)."""
    from repro.fields.ops import avg_to_center, avg_to_face

    rng = np.random.RandomState(seed)
    c = rng.randn(*shape).astype(np.float32)
    f = rng.randn(*shape).astype(np.float32)
    edge = [slice(None)] * 3
    edge[d] = np.array([0, shape[d] - 1])
    f[tuple(edge)] = 0.0
    lhs = float((np.asarray(avg_to_face(jnp.asarray(c), d)) * f).sum())
    rhs = float((c * np.asarray(avg_to_center(jnp.asarray(f), d))).sum())
    scale = np.linalg.norm(c) * np.linalg.norm(f) + 1.0
    assert abs(lhs - rhs) <= 1e-4 * scale, (lhs, rhs)


@settings(max_examples=15, deadline=None)
@given(
    shape=st.tuples(st.integers(4, 8), st.integers(4, 8), st.integers(4, 8)),
    loc=st.sampled_from(["xface", "yface", "zface"]),
    seed=st.integers(0, 10_000),
)
def test_fields_ops_mask_consistency(shape, loc, seed):
    """Center->face ops land exactly on the valid points of the target
    location (dead plane zero, so out * valid_mask == out), and
    gather/scatter round-trips the valid array, for random local shapes
    and locations on a 1-rank grid."""
    from repro.core import init_global_grid
    from repro import fields
    from repro.fields import ops

    grid = init_global_grid(*shape, dims=(1, 1, 1))
    d = fields.stagger_dim(loc)
    rng = np.random.RandomState(seed)
    c = fields.scatter(grid, rng.rand(*grid.global_shape).astype(np.float32))
    # masks are local-view functions (they read the rank coordinate)
    mask = np.asarray(jax.jit(jax.shard_map(
        lambda: fields.valid_mask(grid, loc, jnp.float32),
        mesh=grid.mesh, in_specs=(), out_specs=grid.spec,
        check_vma=False))())
    for raw in (ops.diff_to_face(c.data, d), ops.avg_to_face(c.data, d)):
        out = np.asarray(raw)
        np.testing.assert_array_equal(out * mask, out)
    F = ops.to_face(c, d)
    assert F.loc == loc
    np.testing.assert_array_equal(np.asarray(F.data) * mask, np.asarray(F.data))
    # scatter/gather round-trip of the valid (dead-plane-free) array
    G = rng.rand(*fields.valid_global_shape(grid, loc)).astype(np.float32)
    np.testing.assert_array_equal(fields.gather(fields.scatter(grid, G, loc)), G)


@settings(max_examples=30, deadline=None)
@given(
    shape=st.tuples(st.sampled_from([6, 8, 10]), st.sampled_from([6, 8, 10]),
                    st.sampled_from([6, 8, 10])),
    loc=st.sampled_from(["center", "xface", "yface", "zface"]),
    seed=st.integers(0, 10_000),
)
def test_transfer_adjointness_per_location(shape, loc, seed):
    """<R u, v>_coarse == <u, P v>_fine / 2**ndims per staggering location
    — the per-location transfer pairs of ``repro.solvers.transfers`` are
    transposes up to the standard scaling whenever ``u`` vanishes on the
    fine ring and ``v`` on the coarse ring (the zero planes every V-cycle
    maintains).  This is what keeps the location-generic V-cycle a
    symmetric (CG-compatible) preconditioner at every location."""
    from repro.solvers import transfers

    rng = np.random.RandomState(seed)
    cshape = tuple((n - 2) // 2 + 2 for n in shape)
    u = rng.randn(*shape).astype(np.float64)
    v = rng.randn(*cshape).astype(np.float64)
    for d in range(3):
        edge = [slice(None)] * 3
        edge[d] = np.array([0, shape[d] - 1])
        u[tuple(edge)] = 0.0
        edge[d] = np.array([0, cshape[d] - 1])
        v[tuple(edge)] = 0.0
    with jax.enable_x64(True):  # the 1e-12 bound is an f64 bound
        R_u = np.asarray(transfers.restrict(jnp.asarray(u), loc))
        P_v = np.asarray(transfers.prolong(jnp.asarray(v), loc))
    lhs = float((R_u * v).sum())
    rhs = float((u * P_v).sum()) / 8.0
    scale = np.linalg.norm(u) * np.linalg.norm(v) + 1.0
    assert abs(lhs - rhs) <= 1e-12 * scale, (lhs, rhs)


@settings(max_examples=20, deadline=None)
@given(
    shape=st.sampled_from([(10, 10, 10), (8, 10, 10), (10, 8, 12)]),
    loc=st.sampled_from(["center", "xface", "yface", "zface"]),
)
def test_transfer_partition_of_unity(shape, loc):
    """Prolongation reproduces constants on the interior away from the
    boundary-adjacent planes (linear interpolation partition of unity),
    for every staggering location — a transfer that loses constants
    cannot coarse-grid-correct smooth error."""
    from repro.solvers import transfers

    cshape = tuple((n - 2) // 2 + 2 for n in shape)
    v = np.ones(cshape)
    p = np.asarray(transfers.prolong(jnp.asarray(v), loc))
    # away from the ring and the first/last interior plane, where the
    # zero boundary data of the padded ring legitimately leaks in
    deep = tuple(slice(3, n - 3) for n in shape)
    np.testing.assert_allclose(p[deep], 1.0, atol=1e-12)


@settings(max_examples=8, deadline=None)
@given(
    shape=st.sampled_from([(8, 8, 8), (10, 8, 8), (8, 12, 10)]),
    k=st.sampled_from([7, 12]),
    replace_every=st.sampled_from([5, 50]),
    periodic=st.booleans(),
)
def test_pipelined_cg_iterates_match_classic(shape, k, replace_every,
                                             periodic):
    """Ghysels–Vanroose pipelined CG is the SAME Krylov method as classic
    CG, just rescheduled: after a fixed number of iterations (tol=0
    forces exactly k steps) the iterates agree to roundoff, for any
    residual-replacement period and for singular (periodic, projected)
    problems alike."""
    from repro import fields
    from repro.apps.poisson import Poisson3D

    app = Poisson3D(nx=shape[0], ny=shape[1], nz=shape[2],
                    periodic=(periodic,) * 3, dtype=jnp.float32)
    xc, ic = app.solve(method="cg", tol=0.0, maxiter=k)
    xp, ip = app.solve(method="pipecg", tol=0.0, maxiter=k,
                       replace_every=replace_every)
    assert ic.iterations == ip.iterations == k
    a = fields.gather(xc) if hasattr(xc, "loc") else np.asarray(xc)
    b = fields.gather(xp) if hasattr(xp, "loc") else np.asarray(xp)
    scale = np.abs(a).max() + 1e-30
    np.testing.assert_allclose(b / scale, a / scale, atol=2e-5)
    # the recurrences track the TRUE residual too (float32 here); the
    # pipelined history is one step stale: its entry j+1 is classic's j
    np.testing.assert_allclose(
        np.asarray(ip.residuals)[1:],
        np.asarray(ic.residuals)[: k - 1], rtol=1e-3, atol=1e-6)


@settings(max_examples=8, deadline=None)
@given(
    n=st.integers(6, 20),
    width=st.integers(1, 4),
    seed=st.integers(0, 1000),
)
def test_hide_width_invariance_single_device(n, width, seed):
    """hide_communication result is width-independent (1-device topology)."""
    from repro.core import CartesianTopology, hide_communication, update_halo
    from repro.stencil import fd3d as fd
    from jax.sharding import Mesh

    if n < 2 * (width + 1):
        return
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1), ("a", "b", "c"))
    topo = CartesianTopology(mesh=mesh, axes=("a", "b", "c"),
                             periodic=(True, True, True))
    rng = np.random.RandomState(seed)
    A = jnp.asarray(rng.rand(n, n, n), jnp.float32)

    def step(A):
        return A.at[1:-1, 1:-1, 1:-1].set(
            fd.inn(A) + 0.1 * (fd.d2_xi(A) + fd.d2_yi(A) + fd.d2_zi(A))
        )

    def plain(A):
        return update_halo(topo, step(A), width=1)

    def hidden(A):
        return hide_communication(topo, step, (A,), width=(width,) * 3)

    f1 = jax.jit(jax.shard_map(plain, mesh=mesh, in_specs=topo.spec(),
                               out_specs=topo.spec()))
    f2 = jax.jit(jax.shard_map(hidden, mesh=mesh, in_specs=topo.spec(),
                               out_specs=topo.spec()))
    np.testing.assert_array_equal(np.asarray(f1(A)), np.asarray(f2(A)))
