"""int8 gradient compression with error feedback for cross-pod DP.

The pod axis rides DCN-class links (~4x slower than ICI); compressing the
cross-pod gradient all-reduce 4x (fp32 -> int8 + per-tensor scale) recovers
most of it.  Error feedback (Seide et al.) accumulates the quantization
residual locally so the compression bias vanishes over steps.

Used when TrainConfig.grad_compress=True and the mesh has a 'pod' axis:
parameters are then FSDP-sharded over 'data' only; this module performs the
explicit pod-axis mean.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compressed_psum_leaf(g: jnp.ndarray, err: jnp.ndarray, axis: str,
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One leaf: quantize(g + err) -> psum(int32) -> dequantize; returns
    (reduced gradient, new error feedback)."""
    n = jax.lax.axis_size(axis)
    g_fb = g.astype(jnp.float32) + err
    q, scale = quantize_int8(g_fb)
    # int8 sums can overflow int8; widen to int32 on the wire model —
    # real deployments sum scales separately; we psum q and mean scales
    q_sum = jax.lax.psum(q.astype(jnp.int32), axis)
    scale_mean = jax.lax.pmean(scale, axis)
    # error feedback MUST measure against the dequantization the sum
    # actually used (the mean scale), otherwise the per-pod scale skew is a
    # bias the feedback never sees
    new_err = g_fb - dequantize_int8(q, scale_mean)
    g_red = q_sum.astype(jnp.float32) * scale_mean / n
    return g_red, new_err


def compressed_pod_mean(grads: Any, err_state: Any, mesh,
                        data_axes=("data",), pod_axis: str = "pod",
                        ) -> Tuple[Any, Any]:
    """Apply compressed mean over the pod axis to a gradient pytree.

    Gradients are FSDP-sharded over ``data_axes`` and replicated over the
    pod axis on entry (per-pod partial means); exit is the cross-pod mean.
    """
    def one(g, e):
        def body(g_l, e_l):
            return compressed_psum_leaf(g_l, e_l, pod_axis)

        spec = P()   # leaves arrive pod-replicated per-shard; shard_map over
        # pod only: treat other axes as replicated within this collective
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P()), out_specs=(P(), P()),
            axis_names={pod_axis}, check_vma=False)(g, e)

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err_state)
    outs = [one(g, e) for g, e in zip(flat_g, flat_e)]
    new_g = jax.tree.unflatten(tdef, [o[0] for o in outs])
    new_e = jax.tree.unflatten(tdef, [o[1] for o in outs])
    return new_g, new_e


def init_error_state(grads_like: Any) -> Any:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32),
                        grads_like)
