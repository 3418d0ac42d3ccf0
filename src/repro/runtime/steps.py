"""Step functions: train (fwd+bwd+AdamW), prefill, decode.

Builders return plain Python callables ready for ``jax.jit``; the launch
layer attaches in/out shardings and (for the dry-run) lowers them against
``ShapeDtypeStruct`` inputs.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import MeshConfig, ModelConfig, TrainConfig
from repro.models import (forward_decode, forward_prefill,
                          forward_train_loss)
from repro.optim import adamw_update, lr_schedule


def make_train_step(cfg: ModelConfig, tc: TrainConfig,
                    mesh=None, mesh_cfg: Optional[MeshConfig] = None,
                    block_skip: bool = False):
    data_axes = mesh_cfg.data_axes if mesh_cfg is not None else ("data",)
    remat = tc.remat != "none"
    gdt = jnp.dtype(tc.grad_accum_dtype)

    def loss_fn(p, b):
        loss, metrics = forward_train_loss(
            cfg, p, b, mesh=mesh, data_axes=data_axes, remat=remat,
            block_skip=block_skip, remat_policy=tc.remat)
        return loss, metrics

    def train_step(params, opt_state, batch):
        M = tc.microbatches
        if M == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            # (B/M, M, ...) then swap: the data-sharded batch axis stays
            # on the per-microbatch rows, the scanned axis is unsharded
            mb = jax.tree.map(
                lambda x: jnp.swapaxes(
                    x.reshape((x.shape[0] // M, M) + x.shape[1:]), 0, 1),
                batch)

            def body(carry, b):
                gsum, lsum = carry
                (l, _), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, b)
                gsum = jax.tree.map(
                    lambda a, x: a + x.astype(gdt), gsum, g)
                return (gsum, lsum + l), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, gdt), params)
            (gsum, lsum), _ = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32)), mb)
            grads = jax.tree.map(lambda x: x / M, gsum)
            loss = lsum / M
            metrics = {"lm_loss": loss,
                       "aux_loss": jnp.zeros((), jnp.float32)}
        lr = lr_schedule(opt_state["step"], tc)
        params, opt_state, gnorm = adamw_update(grads, opt_state, params,
                                                lr, tc)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr)
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, mesh=None,
                      mesh_cfg: Optional[MeshConfig] = None,
                      block_skip: bool = False, moe_fsdp: bool = True,
                      quantize_kv_cache: bool = False):
    data_axes = mesh_cfg.data_axes if mesh_cfg is not None else ("data",)

    def prefill_step(params, batch):
        return forward_prefill(cfg, params, batch, mesh=mesh,
                               data_axes=data_axes, block_skip=block_skip,
                               moe_fsdp=moe_fsdp,
                               quantize_kv_cache=quantize_kv_cache)

    return prefill_step


def grow_decode_cache(cfg: ModelConfig, cache: dict, batch_size: int,
                      total_len: int, *, dtype=None,
                      quantize_kv_cache: bool = False) -> dict:
    """Grow a prefill-sized decode cache to ``total_len`` positions.

    Allocates a fresh full-length cache via ``init_decode_cache`` and
    copies the prefilled entries into its leading slice (``pos`` moves
    verbatim; same-shape entries — e.g. SSM states, whose shape doesn't
    depend on sequence length — move without slicing).  Shared by the
    ``launch.serve`` driver and the replay engine's executed admission
    path (:class:`repro.serve.executed.ExecutedGroupRuntime`)."""
    from repro.models import init_decode_cache
    full = init_decode_cache(cfg, batch_size, total_len, dtype=dtype,
                             quantize_kv_cache=quantize_kv_cache)
    for k in cache:
        if k == "pos":
            full["pos"] = cache["pos"]
        elif full[k].shape == cache[k].shape:
            full[k] = cache[k]
        else:
            sl = tuple(slice(0, s) for s in cache[k].shape)
            full[k] = full[k].at[sl].set(cache[k])
    return full


def make_decode_step(cfg: ModelConfig, mesh=None,
                     mesh_cfg: Optional[MeshConfig] = None,
                     moe_fsdp: bool = True, moe_ep_data: bool = False):
    data_axes = mesh_cfg.data_axes if mesh_cfg is not None else ("data",)

    def decode_step(params, tokens, cache):
        return forward_decode(cfg, params, tokens, cache, mesh=mesh,
                              data_axes=data_axes, moe_fsdp=moe_fsdp,
                              moe_ep_data=moe_ep_data)

    return decode_step
