"""End-to-end behaviour tests: training converges, serving round-trips,
MoE routing behaves, Green500 trace accounting is self-consistent."""
import jax
import jax.numpy as jnp
import numpy as np

from conftest import need_devices
from repro.config import ShapeConfig, TrainConfig, smoke_config
from repro.data import make_batch_iterator
from repro.models import init_params
from repro.optim import adamw_init
from repro.runtime.steps import make_train_step


def test_training_reduces_loss():
    cfg = smoke_config("olmo-1b")
    shape = ShapeConfig("t", 128, 4, "train")
    tc = TrainConfig(learning_rate=3e-3, total_steps=30, warmup_steps=3,
                     remat="none")
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, tc))
    data = make_batch_iterator(cfg, shape)
    losses = []
    for i in range(30):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[::6]
    assert all(np.isfinite(l) for l in losses)


def test_microbatched_step_matches_plain():
    """Gradient accumulation over M microbatches == one big batch step."""
    cfg = smoke_config("llama3-8b")
    shape = ShapeConfig("t", 64, 8, "train")
    params = init_params(cfg, jax.random.PRNGKey(0))
    data = make_batch_iterator(cfg, shape)
    batch = {k: jnp.asarray(v) for k, v in next(data).items()}

    tc1 = TrainConfig(remat="none", microbatches=1)
    tc4 = TrainConfig(remat="none", microbatches=4)
    p1, o1, m1 = jax.jit(make_train_step(cfg, tc1))(
        params, adamw_init(params), batch)
    p4, o4, m4 = jax.jit(make_train_step(cfg, tc4))(
        params, adamw_init(params), batch)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 0.02
    # updated params agree to accumulation tolerance
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-2)


def test_block_remat_matches_layer_remat():
    cfg = smoke_config("llama3-8b")
    shape = ShapeConfig("t", 64, 4, "train")
    params = init_params(cfg, jax.random.PRNGKey(0))
    data = make_batch_iterator(cfg, shape)
    batch = {k: jnp.asarray(v) for k, v in next(data).items()}
    outs = {}
    for policy in ("layer", "block"):
        tc = TrainConfig(remat=policy)
        _, _, m = jax.jit(make_train_step(cfg, tc))(
            params, adamw_init(params), batch)
        outs[policy] = float(m["loss"])
    assert abs(outs["layer"] - outs["block"]) < 1e-3


def test_moe_routing_mass_conservation():
    """Per-token combine weights sum to ~1 (after capacity drops <= 1)."""
    from repro.models.moe import _moe_local
    cfg = smoke_config("grok-1-314b")
    e = cfg.moe
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, cfg.d_model), jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(1),
                               (cfg.d_model, e.n_experts)) * 0.1
    wg = jax.random.normal(jax.random.PRNGKey(2),
                           (e.n_experts, cfg.d_model, e.expert_d_ff)) * 0.02
    wu = jax.random.normal(jax.random.PRNGKey(3),
                           (e.n_experts, cfg.d_model, e.expert_d_ff)) * 0.02
    wd = jax.random.normal(jax.random.PRNGKey(4),
                           (e.n_experts, e.expert_d_ff, cfg.d_model)) * 0.02
    y, aux = _moe_local(cfg, x, router, wg, wu, wd, 0, e.n_experts, 1,
                        "expert")
    assert np.isfinite(np.asarray(y)).all()
    assert float(aux) > 0.5        # aux ~ 1 for balanced-ish routing


def test_moe_sharded_matches_local():
    """shard_map MoE == single-shard fallback (2x2 CPU device mesh)."""
    from dataclasses import replace
    from repro.models.moe import init_moe, moe_forward
    need_devices(4)
    cfg = smoke_config("grok-1-314b")
    cfg = replace(cfg, moe=replace(cfg.moe, capacity_factor=8.0))  # no drops
    p = init_moe(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model),
                          jnp.float32)
    local, aux_l = moe_forward(cfg, p, x, mesh=None)
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    shard, aux_s = moe_forward(cfg, p, x, mesh=mesh)
    np.testing.assert_allclose(np.asarray(local), np.asarray(shard),
                               rtol=3e-2, atol=3e-2)


def test_train_step_small_mesh():
    """Full sharded train step on a 2x2 CPU device mesh."""
    from jax.sharding import PartitionSpec as P
    from repro.config import MeshConfig
    from repro.distributed.sharding import (batch_pspecs, named_shardings,
                                            param_pspecs)
    from repro.launch.mesh import make_mesh_from_config
    need_devices(4)
    cfg = smoke_config("grok-1-314b")
    mesh_cfg = MeshConfig((2, 2), ("data", "model"))
    mesh = make_mesh_from_config(mesh_cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    batch = {"tokens": jnp.zeros((4, 32), jnp.int32),
             "labels": jnp.zeros((4, 32), jnp.int32)}
    pspecs = param_pspecs(cfg, params, mesh_cfg)
    pshard = named_shardings(mesh, pspecs)
    oshard = named_shardings(mesh, {"m": pspecs, "v": pspecs, "step": P()})
    bshard = named_shardings(mesh, batch_pspecs(cfg, batch, mesh_cfg))
    params = jax.device_put(params, pshard)
    opt = jax.device_put(opt, oshard)
    batch = jax.device_put(batch, bshard)
    tc = TrainConfig(remat="block", microbatches=2)
    step = jax.jit(make_train_step(cfg, tc, mesh=mesh, mesh_cfg=mesh_cfg),
                   in_shardings=(pshard, oshard, bshard),
                   out_shardings=(pshard, oshard, None))
    params, opt, m = step(params, opt, batch)
    assert np.isfinite(float(m["loss"]))
