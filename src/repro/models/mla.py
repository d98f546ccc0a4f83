"""Multi-head Latent Attention (DeepSeek-V2) — compressed-KV attention.

Prefill/train uses the *expanded* form (decompress c_kv to per-head K/V and
run standard attention).  Decode uses the *absorbed* form: queries are
projected into the compressed space so attention runs directly over the
(kv_lora_rank + rope_dim)-wide cache — the cache is ~(H·dh / r)× smaller
than GQA, which is the technique's serving payoff and makes the 32k decode
cell cheap.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.ctx import ShardCtx, constrain
from repro.models.layers import (
    apply_rope,
    chunked_attention,
    rms_norm,
    yarn_mscale,
)
from repro.models.param import FSDP, TP, ParamDef

__all__ = ["mla_defs", "mla_apply", "mla_decode", "init_mla_cache", "MLACache"]


def mla_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    dq = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq": ParamDef((D, H, dq), (FSDP, TP, None)),
        "wkv_a": ParamDef((D, m.kv_lora_rank + m.qk_rope_head_dim), (FSDP, None)),
        "kv_norm": ParamDef((m.kv_lora_rank,), (None,), init_value=1.0),
        "wk_b": ParamDef((m.kv_lora_rank, H, m.qk_nope_head_dim), (None, TP, None)),
        "wv_b": ParamDef((m.kv_lora_rank, H, m.v_head_dim), (None, TP, None)),
        "wo": ParamDef((H, m.v_head_dim, D), (TP, None, FSDP)),
    }


def _scale(cfg: ModelConfig) -> float:
    """Softmax scale: ``1/sqrt(dq)``, times ``mscale^2`` under YaRN with
    ``mscale_all_dim`` set (DeepSeek-V2)."""
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def mla_apply(
    p: Dict[str, jax.Array],
    x: jax.Array,  # (B, T, D)
    cfg: ModelConfig,
    *,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    collect_cache: bool = False,
    cache_len: Optional[int] = None,
    ctx: Optional[ShardCtx] = None,
):
    """Expanded-form MLA for training/prefill.

    With ``collect_cache`` also returns the compressed (c_kv, k_pe) cache
    consumed by the absorbed-form decode."""
    m = cfg.mla
    B, T, D = x.shape
    H = cfg.n_heads
    pos = jnp.arange(T)[None, :]
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    q_nope, q_pe = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim :]
    q_pe = apply_rope(q_pe, pos, cfg.rope_theta, scaling=cfg.rope_scaling)

    kv_a = x @ p["wkv_a"]  # (B, T, r + dr)
    c_kv = rms_norm(kv_a[..., : m.kv_lora_rank], p["kv_norm"])
    k_pe = kv_a[..., m.kv_lora_rank :][:, :, None, :]  # (B, T, 1, dr)
    k_pe = apply_rope(k_pe, pos, cfg.rope_theta, scaling=cfg.rope_scaling)

    k_nope = jnp.einsum("btr,rhk->bthk", c_kv, p["wk_b"])
    v = jnp.einsum("btr,rhv->bthv", c_kv, p["wv_b"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (B, T, H, m.qk_rope_head_dim))], axis=-1
    )
    q_full = jnp.concatenate([q_nope, q_pe], axis=-1)
    ent = ("b", None, "tp", None)
    q_full = constrain(q_full, ctx, *ent)
    k = constrain(k, ctx, *ent)
    v = constrain(v, ctx, *ent)
    o = chunked_attention(
        q_full, k, v,
        causal=cfg.causal,
        scale=_scale(cfg),
        q_chunk=q_chunk,
        kv_chunk=kv_chunk,
    )
    o = constrain(o, ctx, *ent)
    out = jnp.einsum("bthv,hvd->btd", o, p["wo"])
    if not collect_cache:
        return out
    L = cache_len or T
    pad = L - T
    ck = jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))) if pad else c_kv
    kp3 = k_pe[:, :, 0, :]
    kp = jnp.pad(kp3, ((0, 0), (0, pad), (0, 0))) if pad else kp3
    return out, MLACache(c_kv=ck, k_pe=kp)


class MLACache(NamedTuple):
    c_kv: jax.Array  # (B, S, r) compressed latents (normed)
    k_pe: jax.Array  # (B, S, dr) roped shared key


def init_mla_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype) -> MLACache:
    m = cfg.mla
    return MLACache(
        c_kv=jnp.zeros((batch, seq_len, m.kv_lora_rank), dtype),
        k_pe=jnp.zeros((batch, seq_len, m.qk_rope_head_dim), dtype),
    )


def mla_decode(
    p: Dict[str, jax.Array],
    x: jax.Array,  # (B, 1, D)
    cache: MLACache,
    t: jax.Array,  # scalar position
    cfg: ModelConfig,
    ctx: Optional[ShardCtx] = None,
) -> Tuple[jax.Array, MLACache]:
    """Absorbed-form decode: attention in the compressed space."""
    m = cfg.mla
    B = x.shape[0]
    pos = jnp.full((B, 1), t, jnp.int32)
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])[:, 0]  # (B, H, dq)
    q_nope, q_pe = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim :]
    q_pe = apply_rope(q_pe[:, None], pos, cfg.rope_theta,
                      scaling=cfg.rope_scaling)[:, 0]

    kv_a = (x @ p["wkv_a"])  # (B, 1, r + dr)
    c_kv_new = rms_norm(kv_a[..., : m.kv_lora_rank], p["kv_norm"])
    k_pe_new = apply_rope(
        kv_a[..., m.kv_lora_rank :][:, :, None, :], pos, cfg.rope_theta,
        scaling=cfg.rope_scaling,
    )[:, :, 0]
    c_kv = jax.lax.dynamic_update_slice_in_dim(cache.c_kv, c_kv_new, t, axis=1)
    k_pe = jax.lax.dynamic_update_slice_in_dim(cache.k_pe, k_pe_new, t, axis=1)
    c_kv = constrain(c_kv, ctx, "b", "tp", None)
    k_pe = constrain(k_pe, ctx, "b", "tp", None)

    # Absorb: q_c = q_nope @ wk_b  -> (B, H, r); scores over compressed cache.
    q_c = jnp.einsum("bhk,rhk->bhr", q_nope, p["wk_b"])
    s = (
        jnp.einsum("bhr,bsr->bhs", q_c.astype(jnp.float32),
                   c_kv.astype(jnp.float32))
        + jnp.einsum("bhk,bsk->bhs", q_pe.astype(jnp.float32),
                     k_pe.astype(jnp.float32))
    ) * _scale(cfg)
    valid = jnp.arange(c_kv.shape[1])[None, :] <= t
    s = jnp.where(valid[:, None, :], s, -1e30)
    attn = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", attn, c_kv.astype(jnp.float32))
    o = jnp.einsum("bhr,rhv->bhv", ctx, p["wv_b"].astype(jnp.float32))
    out = jnp.einsum("bhv,hvd->bd", o.astype(x.dtype), p["wo"])[:, None]
    return out, MLACache(c_kv=c_kv, k_pe=k_pe)
