"""ssd_chunk kernel sweep + cross-pod compressed gradient mean on a mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import need_devices
from repro.kernels.ssd_chunk import ssd_chunk, ssd_chunk_ref


@pytest.mark.parametrize("B,Q,H,P,N", [(2, 16, 3, 8, 4), (1, 32, 2, 16, 8),
                                       (3, 8, 4, 4, 16)])
def test_ssd_chunk_sweep(B, Q, H, P, N):
    ks = jax.random.split(jax.random.PRNGKey(Q + H), 6)
    x = jax.random.normal(ks[0], (B, Q, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, Q, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, Q, N))
    Cm = jax.random.normal(ks[4], (B, Q, N))
    h = jax.random.normal(ks[5], (B, H, P, N))
    y, hn = ssd_chunk(x, dt, A, Bm, Cm, h)
    yr, hr = ssd_chunk_ref(x, dt, A, Bm, Cm, h)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(hn), np.asarray(hr), rtol=1e-4,
                               atol=1e-4)


def test_compressed_pod_mean_on_mesh():
    """int8 cross-pod gradient mean with error feedback converges to the
    true mean over steps (2x2 pod x data CPU device mesh)."""
    from jax.sharding import PartitionSpec as P
    from repro.optim.grad_compress import compressed_psum_leaf
    need_devices(4)
    mesh = jax.make_mesh((2, 2), ("pod", "data"))

    def step(g, err):
        def body(g_l, e_l):
            # compressed_psum_leaf already returns the cross-pod MEAN
            red, e = compressed_psum_leaf(g_l, e_l, "pod")
            return red, e
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P("pod"), P("pod")),
                             out_specs=(P("pod"), P("pod")),
                             check_vma=False)(g, err)

    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(0, 1e-2, (2, 256)), jnp.float32)
    err = jnp.zeros_like(g_true)
    acc = jnp.zeros((2, 256), jnp.float32)
    steps = 150
    f = jax.jit(step)
    for _ in range(steps):
        red, err = f(g_true, err)
        acc = acc + red
    true_mean = jnp.mean(g_true, axis=0, keepdims=True)
    got = np.asarray(acc / steps)
    want = np.broadcast_to(np.asarray(true_mean), got.shape)
    # error feedback makes the running average unbiased (single-step
    # int8 error is ~1%; the average converges ~1/steps)
    np.testing.assert_allclose(got, want, atol=3e-4)


def test_serve_ep_moe_matches_local():
    """EP-over-data MoE == single-shard fallback (2x2 CPU device mesh)."""
    from dataclasses import replace
    from repro.config import smoke_config
    from repro.models.moe import init_moe, moe_forward
    need_devices(4)
    cfg = smoke_config("grok-1-314b")
    cfg = replace(cfg, moe=replace(cfg.moe, capacity_factor=8.0))
    p = init_moe(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 2, cfg.d_model),
                          jnp.float32)
    local, _ = moe_forward(cfg, p, x, mesh=None)
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    ep, _ = moe_forward(cfg, p, x, mesh=mesh, ep_data=True)
    np.testing.assert_allclose(np.asarray(local), np.asarray(ep),
                               rtol=3e-2, atol=3e-2)
