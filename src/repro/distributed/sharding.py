"""Sharding rules: FSDP (data/pod axes) × TP (model axis) × EP.

Every rule is a *candidate list*: the first PartitionSpec whose sharded dims
all divide evenly on the mesh wins (JAX rejects uneven shards).  This is what
makes one rule set serve whisper (12 heads, 51865 vocab) and grok (48 heads,
8 KV heads) alike: e.g. attention K/V projections prefer head sharding and
fall back to head-dim sharding when KVH < model-axis size.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import MeshConfig, ModelConfig
from repro.models.moe import moe_sharding_plan

TP = "model"


def data_axes_of(mesh_cfg: MeshConfig) -> Tuple[str, ...]:
    return mesh_cfg.data_axes


def _axis_size(mesh_cfg: MeshConfig, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= _axis_size(mesh_cfg, a)
        return n
    return mesh_cfg.shape[mesh_cfg.axis_names.index(axis)]


def fits(shape: Sequence[int], spec: P, mesh_cfg: MeshConfig) -> bool:
    for dim, axis in zip(shape, tuple(spec) + (None,) * len(shape)):
        size = _axis_size(mesh_cfg, axis)
        if size > 1 and dim % size != 0:
            return False
    return True


def pick(shape: Sequence[int], candidates: List[P],
         mesh_cfg: MeshConfig) -> P:
    for c in candidates:
        if fits(shape, c, mesh_cfg):
            return c
    return P()


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

def _param_rule(cfg: ModelConfig, mesh_cfg: MeshConfig, path: Tuple[str, ...],
                shape: Sequence[int]) -> P:
    dp = data_axes_of(mesh_cfg)
    name = path[-1]
    parent = path[-2] if len(path) >= 2 else ""

    if parent == "embed":                         # (V, D)
        return pick(shape, [P(TP, dp), P(TP, None), P(None, TP), P(dp, None)],
                    mesh_cfg)
    if parent == "lm_head":                       # (D, V)
        return pick(shape, [P(dp, TP), P(None, TP), P(dp, None)], mesh_cfg)
    if parent == "frontend":
        if name == "proj_w":
            return pick(shape, [P(dp, TP), P(None, TP)], mesh_cfg)
        return P()

    if parent in ("attn", "xattn"):
        if name == "wq":                          # (D, H, dh)
            return pick(shape, [P(dp, TP, None), P(dp, None, TP),
                                P(None, None, TP)], mesh_cfg)
        if name in ("wk", "wv"):                  # (D, KVH, dh)
            return pick(shape, [P(dp, TP, None), P(dp, None, TP),
                                P(None, None, TP)], mesh_cfg)
        if name == "wo":                          # (H, dh, D)
            return pick(shape, [P(TP, None, dp), P(None, TP, dp),
                                P(None, TP, None)], mesh_cfg)
        if name in ("bq", "bk", "bv"):            # (H, dh)
            return pick(shape, [P(TP, None), P(None, TP)], mesh_cfg)
        # MLA
        if name in ("wq_a", "wkv_a"):             # (D, r)
            return pick(shape, [P(dp, None)], mesh_cfg)
        if name == "wq_b":                        # (r, H, qk)
            return pick(shape, [P(dp, TP, None), P(None, TP, None)], mesh_cfg)
        if name in ("wkv_b_nope", "wkv_b_v"):     # (r, H, x)
            return pick(shape, [P(dp, TP, None), P(None, TP, None)], mesh_cfg)
        return P()                                # norms

    if parent == "moe":
        if name == "router":
            return P()
        plan = moe_sharding_plan(cfg, _axis_size(mesh_cfg, TP))
        if name in ("w_gate", "w_up"):            # (E, D, F)
            if plan == "expert":
                return pick(shape, [P(TP, dp, None), P(TP, None, None)],
                            mesh_cfg)
            return pick(shape, [P(None, dp, TP), P(None, None, TP)], mesh_cfg)
        if name == "w_down":                      # (E, F, D)
            if plan == "expert":
                return pick(shape, [P(TP, None, dp), P(TP, None, None)],
                            mesh_cfg)
            return pick(shape, [P(None, TP, dp), P(None, TP, None)], mesh_cfg)
        if name in ("shared_gate", "shared_up"):  # (D, F)
            return pick(shape, [P(dp, TP), P(None, TP)], mesh_cfg)
        if name == "shared_down":                 # (F, D)
            return pick(shape, [P(TP, dp), P(TP, None)], mesh_cfg)

    if parent == "mlp":
        if name in ("w_gate", "w_up"):            # (D, F)
            return pick(shape, [P(dp, TP), P(None, TP), P(dp, None)],
                        mesh_cfg)
        if name == "w_down":                      # (F, D)
            return pick(shape, [P(TP, dp), P(TP, None), P(None, dp)],
                        mesh_cfg)

    if parent == "ssm":
        if name == "w_in":                        # (D, E)
            return pick(shape, [P(dp, None)], mesh_cfg)
        if name == "w_out":                       # (E, D)
            return pick(shape, [P(None, dp)], mesh_cfg)
        return P()

    return P()                                    # norms, scalars


def _path_names(path) -> Tuple[str, ...]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return tuple(out)


SERVE_TP_ONLY_BUDGET = 12 * 2**30   # leave headroom below 16 GiB HBM


def param_bytes(params_shapes: Any) -> int:
    return sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(params_shapes))


def _strip_dp(spec: P, dp: Tuple[str, ...]) -> P:
    drop = set(dp)

    def clean(axis):
        if axis is None:
            return None
        if isinstance(axis, (tuple, list)):
            kept = tuple(a for a in axis if a not in drop)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return None if axis in drop else axis

    return P(*[clean(a) for a in spec])


def param_pspecs(cfg: ModelConfig, params_shapes: Any,
                 mesh_cfg: MeshConfig, mode: str = "train",
                 serve_tp_only: "Optional[bool]" = None,
                 moe_ep_data: bool = False) -> Any:
    """PartitionSpec pytree matching a params (shape) pytree.

    Works on both concrete arrays and ShapeDtypeStructs.  Stacked layer
    leaves carry a leading L axis which is never sharded — rules apply to
    ``shape[1:]`` for anything under ``layers``/``enc_layers``.

    ``mode='serve'``: when the TP-sharded weights fit the per-chip budget,
    drop the FSDP (data/pod) factors so serving never all-gathers weights
    per step; models too large for TP-only (grok, deepseek) keep FSDP.
    """
    tp_only = False
    if mode == "serve":
        if serve_tp_only is not None:
            tp_only = serve_tp_only
        else:
            tp_only = (param_bytes(params_shapes)
                       // _axis_size(mesh_cfg, TP) <= SERVE_TP_ONLY_BUDGET)
    dp = data_axes_of(mesh_cfg)

    def rule(path, leaf):
        names = _path_names(path)
        shape = leaf.shape
        stacked = any(n in ("layers", "enc_layers") for n in names)
        body = shape[1:] if stacked else shape
        spec = _param_rule(cfg, mesh_cfg, names, body)
        if moe_ep_data and len(names) >= 2 and names[-2] == "moe":
            # serve-EP: experts over data, FFN over model, fully resident
            if names[-1] in ("w_gate", "w_up"):
                spec = pick(body, [P(dp, None, TP), P(dp, None, None)],
                            mesh_cfg)
            elif names[-1] == "w_down":
                spec = pick(body, [P(dp, TP, None), P(dp, None, None)],
                            mesh_cfg)
        elif tp_only:
            spec = _strip_dp(spec, dp)
        if stacked:
            spec = P(None, *spec)
        return spec

    return jax.tree_util.tree_map_with_path(rule, params_shapes)


def named_shardings(mesh, pspecs: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Lattice (LQCD) rules — T-axis sharding for the even-odd solver
# ---------------------------------------------------------------------------

def lattice_mesh(t_extent: int, n_devices: Optional[int] = None,
                 axis_name: str = TP):
    """1-D device mesh for lattice T-sharding.

    Picks the largest device count (≤ ``n_devices`` or all local devices)
    that divides ``t_extent`` — JAX rejects uneven shards, and the halo
    ring in ``repro.lqcd.multichip_eo`` assumes equal local T blocks.
    """
    avail = n_devices or jax.device_count()
    n = max(d for d in range(1, avail + 1) if t_extent % d == 0)
    # Auto axes, like repro.launch.mesh: plain jnp code on a solver's
    # T-sharded output then runs instead of raising ShardingTypeError
    return jax.make_mesh((n,), (axis_name,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def lattice_eo_specs(axis_name: str = TP) -> Tuple[P, P]:
    """(gauge-half, spinor-half) PartitionSpecs for the compact even-odd
    layout: gauge ``(4, X/2, Y, Z, T, 3, 3)`` and spinor
    ``(X/2, Y, Z, T, 4, 3)``, both sharded on the T axis."""
    return (P(None, None, None, None, axis_name, None, None),
            P(None, None, None, axis_name, None, None))


# ---------------------------------------------------------------------------
# Batch / cache rules
# ---------------------------------------------------------------------------

def batch_pspecs(cfg: ModelConfig, batch_shapes: Dict[str, Any],
                 mesh_cfg: MeshConfig) -> Dict[str, P]:
    dp = data_axes_of(mesh_cfg)
    out = {}
    for k, v in batch_shapes.items():
        cands = [P(dp, *([None] * (len(v.shape) - 1))), P()]
        out[k] = pick(v.shape, cands, mesh_cfg)
    return out


def cache_pspecs(cfg: ModelConfig, cache_shapes: Dict[str, Any],
                 mesh_cfg: MeshConfig) -> Dict[str, P]:
    """Decode-cache sharding: batch over data, sequence (or heads) over model.

    Sequence-sharding the KV cache over the model axis is the TPU-native
    analogue of paged/context-parallel decode: softmax reductions over the
    sharded axis lower to psums.
    """
    dp = data_axes_of(mesh_cfg)
    out: Dict[str, P] = {}
    for k, v in cache_shapes.items():
        if k == "pos":
            out[k] = P()
        elif k in ("k", "v", "xk", "xv"):          # (L, B, S, KVH, dh)
            kvh = v.shape[3]
            cands = [
                P(None, dp, TP, None, None),
                P(None, None, TP, None, None),
                P(None, dp, None, None, None),
            ]
            if kvh % _axis_size(mesh_cfg, TP) != 0:
                # heads don't shard: dynamic cache updates on a seq-sharded
                # dim force GSPMD rematerialization — shard head_dim instead
                cands.insert(0, P(None, dp, None, None, TP))
            out[k] = pick(v.shape, cands, mesh_cfg)
        elif k in ("ckv", "krope"):                # (L, B, S, r)
            out[k] = pick(v.shape, [
                P(None, dp, TP, None),
                P(None, None, TP, None),
            ], mesh_cfg)
        elif k == "ssm":                           # (L, B, H, P, N)
            out[k] = pick(v.shape, [
                P(None, dp, TP, None, None),
                P(None, dp, None, None, None),
                P(None, None, TP, None, None),
            ], mesh_cfg)
        elif k in ("k_s", "v_s"):                  # (L, B, S) per-token
            out[k] = pick(v.shape, [P(None, dp, None)], mesh_cfg)
        elif k == "conv":                          # (L, B, K-1, C)
            out[k] = pick(v.shape, [P(None, dp, None, None)], mesh_cfg)
        else:
            out[k] = P()
    return out
