"""Mixture-of-Experts with expert parallelism via ``shard_map``.

Dispatch uses scatter/gather with a static per-shard capacity instead of the
(tokens, E, capacity) one-hot einsum — the one-hot dispatch tensor is
O(T·E·C) and does not fit HBM at 1M-token global batches; scatter dispatch is
O(E·C·D) and is how MegaBlocks-style implementations behave.

Expert weights are sharded over the ``model`` axis on the expert dim when
``E % model_size == 0`` (deepseek: 160/16), otherwise on the expert-FFN dim
(grok: 8 experts -> TP inside experts).  The FSDP (``data``/``pod``) shard on
d_model is all-gathered explicitly inside the shard_map body right before
use, which lets XLA overlap the gather with the router math.

The same code path serves train, prefill and decode (S=1): only the token
count changes.  Outside a mesh (CPU smoke tests) the single-shard fallback
runs the identical inner function.
"""
from __future__ import annotations

from typing import Dict, Tuple

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.models.layers import param_dtype

Params = Dict[str, jnp.ndarray]


def moe_sharding_plan(cfg: ModelConfig, model_size: int) -> str:
    """'expert' — shard expert dim; 'ffn' — shard expert-FFN dim."""
    e = cfg.moe
    return "expert" if e.n_experts % model_size == 0 else "ffn"


def init_moe(cfg: ModelConfig, key) -> Params:
    e = cfg.moe
    d, f = cfg.d_model, e.expert_d_ff
    dt = param_dtype(cfg)
    ks = jax.random.split(key, 8)
    s_in, s_out = 0.02, 0.02 / math.sqrt(2.0 * cfg.n_layers)

    def mk(k, shape, s):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dt)

    p = {
        "router": mk(ks[0], (d, e.n_experts), s_in),
        "w_gate": mk(ks[1], (e.n_experts, d, f), s_in),
        "w_up": mk(ks[2], (e.n_experts, d, f), s_in),
        "w_down": mk(ks[3], (e.n_experts, f, d), s_out),
    }
    if e.n_shared_experts:
        fs = f * e.n_shared_experts
        p["shared_gate"] = mk(ks[4], (d, fs), s_in)
        p["shared_up"] = mk(ks[5], (d, fs), s_in)
        p["shared_down"] = mk(ks[6], (fs, d), s_out)
    return p


def _capacity(tokens: int, cfg: ModelConfig, n_local_experts: int) -> int:
    e = cfg.moe
    c = int(tokens * e.top_k / e.n_experts * e.capacity_factor) + 1
    return max(c, e.top_k)


def _expert_ffn(cfg: ModelConfig, xin, wg, wu, wd):
    """xin: (E_loc, C, D); weights (E_loc, D, F) / (E_loc, F, D).

    bf16 inputs, fp32 MXU accumulation."""
    g = jnp.einsum("ecd,edf->ecf", xin, wg,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", xin, wu,
                   preferred_element_type=jnp.float32)
    act = jax.nn.silu(g) if cfg.mlp_variant != "geglu" else jax.nn.gelu(g)
    return jnp.einsum("ecf,efd->ecd", (act * u).astype(xin.dtype), wd,
                      preferred_element_type=jnp.float32)


def _moe_local(cfg: ModelConfig, x2d, router_w, wg, wu, wd,
               expert_offset: int, n_local: int, model_size: int,
               plan: str):
    """Per-shard MoE body.  x2d: (T, D) local tokens (full D).

    Returns (y_partial (T, D) — needs psum over 'model', aux_stats).
    """
    e = cfg.moe
    T, D = x2d.shape
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
    gates, idx = jax.lax.top_k(probs, e.top_k)               # (T, k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)

    # aux load-balance stats (Switch-style), computed on full E locally
    assign = jnp.zeros((T, e.n_experts), jnp.float32)
    for r in range(e.top_k):
        assign = assign + jax.nn.one_hot(idx[:, r], e.n_experts)
    frac_tokens = jnp.mean(assign, axis=0) / e.top_k
    frac_probs = jnp.mean(probs, axis=0)
    aux = jnp.sum(frac_tokens * frac_probs) * e.n_experts

    # local experts owned by this shard
    local = (idx >= expert_offset) & (idx < expert_offset + n_local)
    lidx = jnp.where(local, idx - expert_offset, n_local)    # n_local = drop
    C = _capacity(T, cfg, n_local) if plan == "expert" else _capacity(
        T, cfg, e.n_experts)

    # slot position per (t, r): running count per local expert
    flat_e = lidx.reshape(-1)                                # (T*k,)
    onehot = jax.nn.one_hot(flat_e, n_local + 1, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot                # exclusive
    slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = (flat_e < n_local) & (slot < C)
    dest_e = jnp.where(keep, flat_e, n_local)                # overflow row
    dest_c = jnp.where(keep, slot, 0)

    # scatter tokens into (E_loc+1, C, D); last row collects drops.
    # bf16 buffers: the expert matmuls accumulate in fp32 via
    # preferred_element_type, so only the token copies lose precision.
    cdt = x2d.dtype
    tok = jnp.repeat(x2d, e.top_k, axis=0)                   # (T*k, D)
    buf = jnp.zeros((n_local + 1, C, D), cdt)
    buf = buf.at[dest_e, dest_c].add(tok)
    xin = buf[:n_local]

    y_exp = _expert_ffn(cfg, xin, wg, wu, wd).astype(cdt)
    # gather back: token (t, r) reads y_exp[dest_e, dest_c]
    y_pad = jnp.concatenate(
        [y_exp, jnp.zeros((1, C, D), cdt)], axis=0)
    y_tok = y_pad[dest_e, dest_c].astype(jnp.float32)        # (T*k, D)
    g_flat = (gates.reshape(-1) * keep.astype(jnp.float32))
    y = jnp.sum((y_tok * g_flat[:, None]).reshape(T, e.top_k, D), axis=1)
    return y, aux


def _ep_data_forward(cfg: ModelConfig, p: Params, x, mesh, data_axes,
                     model_axis):
    """Serve-EP: experts sharded over the DATA axes (E % dp == 0), FFN dim
    over the model axis — weights fully resident, ZERO per-step weight
    gathers.  Tokens are all-gathered over data (tiny at decode batch
    sizes), each shard runs its local experts over ALL tokens, and outputs
    reduce-scatter back to the token owners.  This is the classic MoE
    dispatch/combine all-to-all realized as AG+RS (§Perf hillclimb for the
    collective-bound MoE decode cells)."""
    e = cfg.moe
    B, S, D = x.shape
    dp_size = 1
    for a in data_axes:
        dp_size *= mesh.shape[a]
    n_local = e.n_experts // dp_size

    def body(xl, router_w, wg, wu, wd):
        # gather all tokens over the data axes
        xa = xl
        for a in reversed(data_axes):
            xa = jax.lax.all_gather(xa, a, axis=0, tiled=True)
        T = xa.shape[0] * xa.shape[1]
        off = 0
        mult = 1
        for a in reversed(data_axes):
            off = off + jax.lax.axis_index(a) * mult * n_local
            mult *= mesh.shape[a]
        y, aux = _moe_local(cfg, xa.reshape(T, D), router_w, wg, wu, wd,
                            off, n_local, dp_size, "expert")
        y = y.astype(xl.dtype)
        # partial sums: over model (F-sharded down proj is NOT sharded in
        # this plan, but psum over model keeps replicas consistent when F
        # is sharded) and return tokens to their owners over data
        y = jax.lax.psum(y, model_axis)
        y = y.reshape(xa.shape)
        for a in data_axes:
            y = jax.lax.psum_scatter(y, a, scatter_dimension=0, tiled=True)
        aux = jax.lax.pmean(aux, model_axis)
        for a in data_axes:
            aux = jax.lax.pmean(aux, a)
        return y, aux

    in_specs = (P(data_axes, None, None),
                P(None, None),
                P(data_axes, None, model_axis),    # (E, D, F)
                P(data_axes, None, model_axis),
                P(data_axes, model_axis, None))    # (E, F, D)
    out, aux = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs,
        out_specs=(P(data_axes, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, aux


def moe_forward(cfg: ModelConfig, p: Params, x: jnp.ndarray, *,
                mesh=None, data_axes: Tuple[str, ...] = ("data",),
                model_axis: str = "model", fsdp: bool = True,
                ep_data: bool = False,
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar)."""
    e = cfg.moe
    B, S, D = x.shape
    shape3 = x.shape

    if mesh is None:
        # single-shard fallback (CPU smoke tests)
        y, aux = _moe_local(cfg, x.reshape(-1, D), p["router"], p["w_gate"],
                            p["w_up"], p["w_down"], 0, e.n_experts, 1,
                            "expert")
        out = y.reshape(shape3).astype(x.dtype)
    elif ep_data:
        out, aux = _ep_data_forward(cfg, p, x, mesh, data_axes, model_axis)
    else:
        msize = mesh.shape[model_axis]
        plan = moe_sharding_plan(cfg, msize)
        dp = P(data_axes)

        wdp = data_axes if fsdp else None
        if plan == "expert":
            n_local = e.n_experts // msize
            in_specs = (P(data_axes, None, None),            # x
                        P(None, None),                       # router (repl)
                        P(model_axis, wdp, None),            # w_gate (E, D, F)
                        P(model_axis, wdp, None),            # w_up
                        P(model_axis, None, wdp))            # w_down (E, F, D)
        else:
            n_local = e.n_experts
            in_specs = (P(data_axes, None, None),
                        P(None, None),
                        P(None, wdp, model_axis),            # shard F
                        P(None, wdp, model_axis),
                        P(None, model_axis, wdp))

        def body(xl, router_w, wg, wu, wd):
            # all-gather the FSDP (data) shard of the expert weights
            def ag(w, axis):
                if not fsdp:
                    return w
                for a in reversed(data_axes):
                    w = jax.lax.all_gather(w, a, axis=axis, tiled=True)
                return w
            wg = ag(wg, 1)
            wu = ag(wu, 1)
            wd = ag(wd, 2)
            if plan == "expert":
                off = jax.lax.axis_index(model_axis) * n_local
            else:
                off = 0
            Tl = xl.shape[0] * xl.shape[1]
            y, aux = _moe_local(cfg, xl.reshape(Tl, D), router_w, wg, wu, wd,
                                off, n_local, msize, plan)
            # bf16 on the wire: halves the psum bytes; the fp32 partial sums
            # were already MXU-accumulated per shard
            y = jax.lax.psum(y.astype(xl.dtype), model_axis)
            aux = jax.lax.pmean(aux, model_axis)
            for a in data_axes:
                aux = jax.lax.pmean(aux, a)
            return y.reshape(xl.shape), aux

        out, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(data_axes, None, None), P()),
            check_vma=False,
        )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if e.n_shared_experts:
        g = jnp.einsum("bsd,df->bsf", x, p["shared_gate"])
        u = jnp.einsum("bsd,df->bsf", x, p["shared_up"])
        shared = jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                            p["shared_down"])
        out = out + shared
    return out, aux * e.aux_loss_weight
