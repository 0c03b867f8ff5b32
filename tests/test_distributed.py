"""Sequence-parallel halo ops and ring attention vs single-device oracles."""

from _mp import run


def test_seq_conv1d_halo():
    run(
        """
from jax.sharding import Mesh, PartitionSpec as P
from repro.distributed.seqpar import seq_conv1d_causal

mesh = jax.make_mesh((8,), ("sp",))
rng = np.random.RandomState(0)
B, T, C, K = 2, 64, 6, 4
x = jnp.asarray(rng.randn(B, T, C), jnp.float32)
w = jnp.asarray(rng.randn(K, C), jnp.float32)

ref = seq_conv1d_causal(x, w, axis_name=None)

f = jax.jit(jax.shard_map(
    lambda x: seq_conv1d_causal(x, w, axis_name="sp"),
    mesh=mesh, in_specs=P(None, "sp", None), out_specs=P(None, "sp", None)))
got = f(x)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)
print("OK")
""",
        ndev=8,
    )


def test_seq_sliding_window_attention():
    run(
        """
from jax.sharding import PartitionSpec as P
from repro.distributed.seqpar import seq_sliding_window_attention
from repro.kernels.swa import swa_ref

mesh = jax.make_mesh((4,), ("sp",))
rng = np.random.RandomState(1)
B, H, Hkv, T, D, W = 2, 4, 2, 64, 16, 12
q = jnp.asarray(rng.randn(B, H, T, D), jnp.float32) * 0.4
k = jnp.asarray(rng.randn(B, Hkv, T, D), jnp.float32) * 0.4
v = jnp.asarray(rng.randn(B, Hkv, T, D), jnp.float32)

ref = swa_ref(q, k, v, window=W)
f = jax.jit(jax.shard_map(
    lambda q, k, v: seq_sliding_window_attention(q, k, v, window=W, axis_name="sp"),
    mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
    out_specs=P(None, None, "sp", None)))
got = f(q, k, v)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)
print("OK")
""",
        ndev=4,
    )


def test_ring_attention_matches_dense():
    run(
        """
from jax.sharding import PartitionSpec as P
from repro.distributed.ring import ring_attention
from repro.kernels.swa import swa_ref

mesh = jax.make_mesh((8,), ("sp",))
rng = np.random.RandomState(2)
B, H, Hkv, T, D = 1, 4, 2, 64, 16
q = jnp.asarray(rng.randn(B, H, T, D), jnp.float32) * 0.4
k = jnp.asarray(rng.randn(B, Hkv, T, D), jnp.float32) * 0.4
v = jnp.asarray(rng.randn(B, Hkv, T, D), jnp.float32)

ref = swa_ref(q, k, v, window=10**9)  # plain causal
f = jax.jit(jax.shard_map(
    lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
    mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
    out_specs=P(None, None, "sp", None)))
got = f(q, k, v)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)
print("OK")
""",
        ndev=8,
    )


def test_seq_ssd_scan_matches_full():
    run(
        """
from jax.sharding import PartitionSpec as P
from repro.distributed.seqpar import seq_ssd_scan
from repro.kernels.ssd import ssd_ref

mesh = jax.make_mesh((8,), ("sp",), axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.RandomState(3)
Ba, T, H, G, N, Pd = 2, 64, 4, 1, 8, 16
x = jnp.asarray(rng.randn(Ba, T, H, Pd), jnp.float32)
dt = jnp.asarray(rng.rand(Ba, T, H) * 0.2 + 0.01, jnp.float32)
A = jnp.asarray(-np.abs(rng.rand(H)) - 0.1, jnp.float32)
B = jnp.asarray(rng.randn(Ba, T, G, N), jnp.float32) * 0.4
C = jnp.asarray(rng.randn(Ba, T, G, N), jnp.float32) * 0.4

y_ref, h_ref = ssd_ref(x, dt, A, B, C)

f = jax.jit(jax.shard_map(
    lambda x, dt, B, C: seq_ssd_scan(x, dt, A, B, C, chunk=4, axis_name="sp"),
    mesh=mesh,
    in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp"), P(None, "sp")),
    out_specs=(P(None, "sp"), P("sp"))))  # h_out per rank: stacked on a new axis? -> use (P(None,'sp'), P('sp')) won't match shape
# simpler: return only y from the mapped fn; check final state separately
f = jax.jit(jax.shard_map(
    lambda x, dt, B, C: seq_ssd_scan(x, dt, A, B, C, chunk=4, axis_name="sp")[0],
    mesh=mesh,
    in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp"), P(None, "sp")),
    out_specs=P(None, "sp")))
y = f(x, dt, B, C)
np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=3e-4, atol=3e-4)

# final state: gather h_out from every rank, take the last
g = jax.jit(jax.shard_map(
    lambda x, dt, B, C: seq_ssd_scan(x, dt, A, B, C, chunk=4, axis_name="sp")[1][None],
    mesh=mesh,
    in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp"), P(None, "sp")),
    out_specs=P("sp")))
h_all = g(x, dt, B, C)
np.testing.assert_allclose(np.asarray(h_all[-1]), np.asarray(h_ref), rtol=3e-4, atol=3e-4)
print("OK")
""",
        ndev=8,
    )


def test_lse_combine_decode():
    run(
        """
from jax.sharding import PartitionSpec as P
from repro.distributed.ring import lse_combine_decode
from repro.kernels.swa import swa_ref

mesh = jax.make_mesh((8,), ("sp",))
rng = np.random.RandomState(4)
B, H, Hkv, S, D = 2, 4, 2, 128, 16
q = jnp.asarray(rng.randn(B, H, D), jnp.float32) * 0.4
k = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32) * 0.4
v = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
kv_len = jnp.asarray([100, 77], jnp.int32)  # ragged valid lengths

# oracle: dense masked attention over the valid prefix
ref = []
for b in range(B):
    L = int(kv_len[b])
    r = swa_ref(q[b:b+1, :, None], k[b:b+1, :L].transpose(0, 2, 1, 3),
                v[b:b+1, :L].transpose(0, 2, 1, 3), window=10**9)
    ref.append(np.asarray(r[0, :, 0]))
ref = np.stack(ref)

Sl = S // 8
f = jax.jit(jax.shard_map(
    lambda q, k, v, kl: lse_combine_decode(
        q, k, v,
        jnp.clip(kl[:, None] - jax.lax.axis_index("sp") * Sl, 0, Sl)[:, 0],
        axis_name="sp"),
    mesh=mesh,
    in_specs=(P(), P(None, "sp"), P(None, "sp"), P()),
    out_specs=P()))
got = f(q, k, v, kv_len)
np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5, atol=2e-5)
print("OK")
""",
        ndev=8,
    )
