"""Attention: GQA with blockwise (flash-style) softmax, sliding windows,
single-token decode against a KV cache, and DeepSeek-V2 MLA (multi-head
latent attention) with matrix absorption for decode.

The blockwise implementation is pure JAX (``lax.scan`` online softmax) so the
same code lowers for the CPU dry-run and for TPU.  Two schedules exist:

* rectangular (default): every (q-chunk, kv-chunk) block is computed and
  masked — simple, but computes ~2x the needed FLOPs for causal masks.
* triangular (``block_skip=True``): scans only the lower-triangle blocks —
  the §Perf hillclimb for compute-bound prefill cells.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.models.layers import apply_rope, param_dtype

Params = Dict[str, jnp.ndarray]

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# int8 KV-cache quantization (serving): per-(token, head) scales
# ---------------------------------------------------------------------------

def quantize_kv(x: jnp.ndarray, head_dims: int = 2):
    """x: (..., KVH, dh) -> (int8 values, f32 per-token scales).

    Scales are shared across the trailing ``head_dims`` axes (heads and
    head_dim): per-(token, head) scales do not shard on meshes where the
    head count is not divisible (qwen: 40 heads / 16), and at 32k x 128
    batch they alone cost GiBs/chip.  Accuracy is validated against the
    bf16 cache in tests."""
    ax = tuple(range(x.ndim - head_dims, x.ndim))
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=ax) / 127.0
    s = jnp.maximum(s, 1e-8)
    sb = s.reshape(s.shape + (1,) * head_dims)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / sb),
                 -127, 127).astype(jnp.int8)
    return q, s


def dequantize_kv(q: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    head_dims = q.ndim - s.ndim
    return q.astype(jnp.float32) * s.reshape(s.shape + (1,) * head_dims)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key) -> Params:
    dt = param_dtype(cfg)
    d, h, kvh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    s_in, s_out = 0.02, 0.02 / math.sqrt(2.0 * cfg.n_layers)
    ks = jax.random.split(key, 8)

    def mk(k, shape, s):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dt)

    if cfg.mla.enabled:
        m = cfg.mla
        qk = m.qk_nope_head_dim + m.qk_rope_head_dim
        p = {
            "wq_a": mk(ks[0], (d, m.q_lora_rank), s_in) if m.q_lora_rank else None,
            "wq_b": mk(ks[1], (m.q_lora_rank or d, h, qk), s_in),
            "wkv_a": mk(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), s_in),
            "wkv_b_nope": mk(ks[3], (m.kv_lora_rank, h, m.qk_nope_head_dim), s_in),
            "wkv_b_v": mk(ks[4], (m.kv_lora_rank, h, m.v_head_dim), s_in),
            "wo": mk(ks[5], (h, m.v_head_dim, d), s_out),
            "q_norm": jnp.ones((m.q_lora_rank,), dt) if m.q_lora_rank else None,
            "kv_norm": jnp.ones((m.kv_lora_rank,), dt),
        }
        return {k: v for k, v in p.items() if v is not None}

    p = {
        "wq": mk(ks[0], (d, h, dh), s_in),
        "wk": mk(ks[1], (d, kvh, dh), s_in),
        "wv": mk(ks[2], (d, kvh, dh), s_in),
        "wo": mk(ks[3], (h, dh, d), s_out),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, dh), dt)
        p["bk"] = jnp.zeros((kvh, dh), dt)
        p["bv"] = jnp.zeros((kvh, dh), dt)
    return p


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention — pure JAX
# ---------------------------------------------------------------------------

def _block_mask(qpos: jnp.ndarray, kpos: jnp.ndarray, causal: bool,
                window: int) -> jnp.ndarray:
    """(qc, kc) boolean mask: True = attend."""
    diff = qpos[:, None] - kpos[None, :]
    mask = jnp.ones(diff.shape, bool)
    if causal:
        mask &= diff >= 0
    if window > 0:
        mask &= diff < window
    return mask


def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, q_offset: int = 0,
                        window: int = 0, q_chunk: int = 512,
                        kv_chunk: int = 512,
                        block_skip: bool = False) -> jnp.ndarray:
    """q: (B, Sq, H, dh); k, v: (B, Sk, KVH, dh) -> (B, Sq, H, dh).

    Online-softmax over kv chunks; GQA via head grouping.  fp32 accumulation.
    """
    B, Sq, H, dh = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    scale = 1.0 / math.sqrt(dh)

    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Sk)
    # pad to multiples
    nq = -(-Sq // qc)
    nk = -(-Sk // kc)
    q_pad, k_pad = nq * qc - Sq, nk * kc - Sk
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0), (0, 0)))

    qg = q.reshape(B, nq, qc, KVH, G, dh)
    kg = k.reshape(B, nk, kc, KVH, dh)
    vg = v.reshape(B, nk, kc, KVH, dh)

    def block(qi_blk, kj_blk, i, j, m, l, acc):
        """One (qc x kc) attention block with online-softmax update."""
        qpos = q_offset + i * qc + jnp.arange(qc)
        kpos = j * kc + jnp.arange(kc)
        mask = _block_mask(qpos, kpos, causal, window)
        mask &= (kpos < Sk)[None, :]
        s = jnp.einsum("bqkgd,bckd->bqkgc", qi_blk.astype(jnp.float32),
                       kj_blk.astype(jnp.float32)) * scale
        s = jnp.where(mask[None, :, None, None, :], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqkgc,bckd->bqkgd", p, vg[:, j].astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return m_new, l_new, acc_new

    def init_stats():
        m = jnp.full((B, qc, KVH, G), _NEG_INF, jnp.float32)
        l = jnp.zeros((B, qc, KVH, G), jnp.float32)
        acc = jnp.zeros((B, qc, KVH, G, dh), jnp.float32)
        return m, l, acc

    if block_skip and causal and window == 0 and qc == kc and q_offset == 0:
        # Triangular schedule: flatten (i, j<=i) pairs; sequential scan keeps
        # the online-softmax state per-row valid because rows are contiguous.
        pairs = [(i, j) for i in range(nq) for j in range(i + 1)]
        ii = jnp.array([p[0] for p in pairs], jnp.int32)
        jj = jnp.array([p[1] for p in pairs], jnp.int32)
        row_done = jnp.array([j == i for i, j in pairs], bool)
        out = jnp.zeros((B, nq, qc, KVH, G, dh), jnp.float32)

        def step(carry, idx):
            m, l, acc, out = carry
            i, j, done = ii[idx], jj[idx], row_done[idx]
            qi = jax.lax.dynamic_index_in_dim(qg, i, 1, keepdims=False)
            kj = jax.lax.dynamic_index_in_dim(kg, j, 1, keepdims=False)
            vj = jax.lax.dynamic_index_in_dim(vg, j, 1, keepdims=False)
            qpos = i * qc + jnp.arange(qc)
            kpos = j * kc + jnp.arange(kc)
            mask = (qpos[:, None] >= kpos[None, :]) & (kpos < Sk)[None, :]
            s = jnp.einsum("bqkgd,bckd->bqkgc", qi.astype(jnp.float32),
                           kj.astype(jnp.float32)) * scale
            s = jnp.where(mask[None, :, None, None, :], s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqkgc,bckd->bqkgd", p, vj.astype(jnp.float32))
            row_out = acc_new / jnp.maximum(l_new, 1e-20)[..., None]
            out = jax.lax.cond(
                done,
                lambda o: jax.lax.dynamic_update_index_in_dim(o, row_out, i, 1),
                lambda o: o, out)
            m0, l0, acc0 = init_stats()
            m_next = jnp.where(done, m0, m_new)
            l_next = jnp.where(done, l0, l_new)
            acc_next = jnp.where(done, acc0, acc_new)
            return (m_next, l_next, acc_next, out), None

        m0, l0, acc0 = init_stats()
        (_, _, _, out), _ = jax.lax.scan(
            step, (m0, l0, acc0, out), jnp.arange(len(pairs)))
        o = out
    else:
        def q_row(qi_blk, i):
            def kv_step(carry, j):
                m, l, acc = carry
                kj = jax.lax.dynamic_index_in_dim(kg, j, 1, keepdims=False)
                m, l, acc = block(qi_blk, kj, i, j, m, l, acc)
                return (m, l, acc), None

            (m, l, acc), _ = jax.lax.scan(kv_step, init_stats(), jnp.arange(nk))
            return acc / jnp.maximum(l, 1e-20)[..., None]

        o = jax.lax.map(lambda args: q_row(*args),
                        (jnp.moveaxis(qg, 1, 0), jnp.arange(nq)))
        o = jnp.moveaxis(o, 0, 1)                    # (B, nq, qc, KVH, G, dh)

    o = o.reshape(B, nq * qc, H, dh)[:, :Sq]
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA forward (train / prefill) and decode
# ---------------------------------------------------------------------------

def _project_qkv(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                 positions: jnp.ndarray):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if positions is not None:
        q = apply_rope(q.swapaxes(1, 2), positions, cfg.rope_theta).swapaxes(1, 2)
        k = apply_rope(k.swapaxes(1, 2), positions, cfg.rope_theta).swapaxes(1, 2)
    return q, k, v


def _seq_sharded_attention(q, k, v, *, mesh, data_axes, causal, window,
                           model_axis="model"):
    """Sequence-parallel attention for head counts that do not divide the
    model axis (whisper 12H, qwen 40H, hymba 25H).

    Q is sharded over the model axis on the SEQUENCE dim; K/V are
    all-gathered inside the shard (one bf16 gather per layer), and the
    causal mask uses the shard's sequence offset.  Scores never materialize
    beyond (B_loc, S/tp, H, kc)."""
    dp = P(data_axes)

    def body(q_l, k_l, v_l):
        k_f = jax.lax.all_gather(k_l, model_axis, axis=1, tiled=True)
        v_f = jax.lax.all_gather(v_l, model_axis, axis=1, tiled=True)
        off = jax.lax.axis_index(model_axis) * q_l.shape[1]
        return blockwise_attention(q_l, k_f, v_f, causal=causal,
                                   q_offset=off, window=window)

    spec = P(data_axes, model_axis, None, None)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def gqa_forward(cfg: ModelConfig, p: Params, x: jnp.ndarray, *,
                positions: jnp.ndarray, causal: bool = True,
                block_skip: bool = False,
                kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                mesh=None, data_axes=("data",),
                ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Full-sequence attention.  Returns (out, (k, v)) for cache building.

    ``kv_override`` supplies external K/V (cross-attention)."""
    q, k, v = _project_qkv(cfg, p, x,
                           None if kv_override is not None else positions)
    if kv_override is not None:
        k, v = kv_override
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if cfg.qkv_bias:
            q = q + p["bq"]
    use_seq_shard = False
    if mesh is not None and "model" in getattr(mesh, "shape", {}):
        tp = mesh.shape["model"]
        seq_ok = (q.shape[1] % tp == 0 and k.shape[1] % tp == 0
                  and q.shape[1] == k.shape[1])
        use_seq_shard = (cfg.n_heads % tp != 0) and seq_ok and causal
    if use_seq_shard:
        o = _seq_sharded_attention(q, k, v, mesh=mesh, data_axes=data_axes,
                                   causal=causal, window=cfg.sliding_window)
    else:
        o = blockwise_attention(q, k, v, causal=causal,
                                window=cfg.sliding_window,
                                block_skip=block_skip)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, (k, v)


def cross_kv(cfg: ModelConfig, p: Params, enc: jnp.ndarray):
    """Precompute cross-attention K/V from encoder states."""
    k = jnp.einsum("bsd,dhk->bshk", enc, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc, p["wv"])
    if cfg.qkv_bias:
        k = k + p["bk"]
        v = v + p["bv"]
    return k, v


def gqa_decode(cfg: ModelConfig, p: Params, x: jnp.ndarray,
               cache_k: jnp.ndarray, cache_v: jnp.ndarray,
               position: jnp.ndarray, *, update_cache: bool = True,
               k_scale: Optional[jnp.ndarray] = None,
               v_scale: Optional[jnp.ndarray] = None):
    """Single-token decode.  x: (B, 1, d); cache: (B, S, KVH, dh).

    The cache sequence axis may be sharded (model axis) — the softmax
    reductions over it become psums under GSPMD.  With a sliding window the
    cache is a ring buffer of size ``window``.  int8 caches carry
    per-(token, head) ``k_scale``/``v_scale`` (B, S, KVH) and are
    dequantized inline (doubles effective decode bandwidth).

    Returns (out, cache_k, cache_v[, k_scale, v_scale])."""
    B, _, _ = x.shape
    S = cache_k.shape[1]
    H, KVH, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    G = H // KVH
    scale = 1.0 / math.sqrt(dh)
    quantized = k_scale is not None

    pos_vec = position.reshape(1)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k_new, v_new = q + p["bq"], k_new + p["bk"], v_new + p["bv"]
    q = apply_rope(q.swapaxes(1, 2), pos_vec, cfg.rope_theta).swapaxes(1, 2)
    k_new = apply_rope(k_new.swapaxes(1, 2), pos_vec,
                       cfg.rope_theta).swapaxes(1, 2)

    if update_cache:
        slot = position % S if cfg.sliding_window > 0 else position
        if quantized:
            kq, ks = quantize_kv(k_new)        # ks: (B, 1)
            vq, vs = quantize_kv(v_new)
            cache_k = jax.lax.dynamic_update_slice(cache_k, kq,
                                                   (0, slot, 0, 0))
            cache_v = jax.lax.dynamic_update_slice(cache_v, vq,
                                                   (0, slot, 0, 0))
            k_scale = jax.lax.dynamic_update_slice(k_scale, ks, (0, slot))
            v_scale = jax.lax.dynamic_update_slice(v_scale, vs, (0, slot))
        else:
            cache_k = jax.lax.dynamic_update_slice(
                cache_k, k_new.astype(cache_k.dtype), (0, slot, 0, 0))
            cache_v = jax.lax.dynamic_update_slice(
                cache_v, v_new.astype(cache_v.dtype), (0, slot, 0, 0))

    kpos = jnp.arange(S)
    if cfg.sliding_window > 0:
        # ring buffer: slot i holds the latest position p with p % S == i
        latest = position - ((position - kpos) % S)
        valid = (latest >= 0) & (latest >= position - cfg.sliding_window + 1)
        valid = valid | (kpos == (position % S))
    else:
        valid = kpos <= position

    qg = q.reshape(B, KVH, G, dh)
    if quantized:
        # dequantize on the fly: scores = (q·k_q) * s_k   (k_scale: (B, S))
        s_ = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                        cache_k.astype(jnp.float32))
        s_ = s_ * k_scale[:, None, None, :] * scale
    else:
        s_ = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                        cache_k.astype(jnp.float32)) * scale
    s_ = jnp.where(valid[None, None, None, :], s_, _NEG_INF)
    w = jax.nn.softmax(s_, axis=-1)
    if quantized:
        w_eff = w * v_scale[:, None, None, :]
        o = jnp.einsum("bkgs,bskd->bkgd", w_eff,
                       cache_v.astype(jnp.float32))
    else:
        o = jnp.einsum("bkgs,bskd->bkgd", w, cache_v.astype(jnp.float32))
    o = o.reshape(B, 1, H, dh).astype(x.dtype)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    if quantized:
        return out, cache_k, cache_v, k_scale, v_scale
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------

def _rms(x, scale):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6)
            * scale.astype(jnp.float32)).astype(x.dtype)


def _mla_q(cfg: ModelConfig, p: Params, x, positions):
    m = cfg.mla
    if m.q_lora_rank:
        ql = _rms(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_norm"])
    else:
        ql = x
    q = jnp.einsum("bsr,rhk->bshk", ql, p["wq_b"])
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:].swapaxes(1, 2),
                        positions, cfg.rope_theta).swapaxes(1, 2)
    return q_nope, q_rope


def _mla_latent(cfg: ModelConfig, p: Params, x, positions):
    m = cfg.mla
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    ckv = _rms(kv[..., :m.kv_lora_rank], p["kv_norm"])
    k_rope = kv[..., m.kv_lora_rank:]                       # (B, S, rope)
    k_rope = apply_rope(k_rope[:, None], positions,
                        cfg.rope_theta)[:, 0]
    return ckv, k_rope


def mla_forward(cfg: ModelConfig, p: Params, x: jnp.ndarray, *,
                positions: jnp.ndarray, block_skip: bool = False,
                ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Full-sequence MLA.  Returns (out, (ckv, k_rope)) latent cache."""
    m = cfg.mla
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    ckv, k_rope = _mla_latent(cfg, p, x, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["wkv_b_nope"])
    v = jnp.einsum("bsr,rhk->bshk", ckv, p["wkv_b_v"])
    H = cfg.n_heads
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :],
                                k_rope.shape[:2] + (H, m.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    # pad v head dim up to qk dim so the blockwise helper can be reused
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_dim - m.v_head_dim)))
    o = blockwise_attention(q, k, v_pad, causal=True, block_skip=block_skip)
    o = o[..., :m.v_head_dim]
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, (ckv, k_rope)


def mla_decode(cfg: ModelConfig, p: Params, x: jnp.ndarray,
               cache_ckv: jnp.ndarray, cache_krope: jnp.ndarray,
               position: jnp.ndarray,
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Matrix-absorbed MLA decode (DeepSeek-V2 inference optimization).

    Scores are computed directly in the latent space: the per-head nope
    projection is absorbed into the query, so the cache stays (B, S, r).
    """
    m = cfg.mla
    B = x.shape[0]
    S = cache_ckv.shape[1]
    pos_vec = position.reshape(1)

    q_nope, q_rope = _mla_q(cfg, p, x, pos_vec)             # (B,1,H,*)
    ckv_new, krope_new = _mla_latent(cfg, p, x, pos_vec)
    cache_ckv = jax.lax.dynamic_update_slice(
        cache_ckv, ckv_new.astype(cache_ckv.dtype), (0, position, 0))
    cache_krope = jax.lax.dynamic_update_slice(
        cache_krope, krope_new.astype(cache_krope.dtype), (0, position, 0))

    # absorb W_k_nope into q:  (B,1,H,nope) x (r,H,nope) -> (B,H,r)
    q_lat = jnp.einsum("bshk,rhk->bhr", q_nope.astype(jnp.float32),
                       p["wkv_b_nope"].astype(jnp.float32))
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat,
                    cache_ckv.astype(jnp.float32))
         + jnp.einsum("bshk,bSk->bhS", q_rope.astype(jnp.float32),
                      cache_krope.astype(jnp.float32))) * scale
    valid = jnp.arange(S) <= position
    s = jnp.where(valid[None, None, :], s, _NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", w, cache_ckv.astype(jnp.float32))
    o = jnp.einsum("bhr,rhk->bhk", o_lat,
                   p["wkv_b_v"].astype(jnp.float32))        # (B,H,v)
    out = jnp.einsum("bhk,hkd->bd", o.astype(x.dtype), p["wo"])[:, None]
    return out, cache_ckv, cache_krope
