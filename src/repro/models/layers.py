"""Shared neural layers: norms, RoPE, chunked (flash-style) attention,
decode attention over KV caches, gated MLPs, embeddings, chunked CE loss.

All functions are pure; parameters arrive as dicts built from the
:mod:`repro.models.param` definition trees.  Attention never materializes
the full (Tq, Tk) score matrix — it streams KV chunks with an online
softmax (the same algorithm as the Pallas flash kernel in
``repro/kernels/flash_attention.py``; this is its XLA-lowered twin, used
for CPU dry-runs and as the kernel oracle).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.param import FSDP, TP, ParamDef

__all__ = [
    "rms_norm",
    "layer_norm",
    "softcap",
    "apply_rope",
    "yarn_freqs",
    "yarn_mscale",
    "chunked_attention",
    "decode_attention",
    "mlp_defs",
    "mlp_apply",
    "chunked_ce_loss",
]

MASK_VALUE = -1e30


# -- norms ---------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
             plus_one: bool = False) -> jax.Array:
    """RMSNorm in fp32; ``plus_one`` uses the gemma ``(1 + scale)`` form."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    s = scale.astype(jnp.float32)
    if plus_one:
        s = 1.0 + s
    return (normed * s).astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    normed = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (normed * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    """Gemma-2 logit soft-capping: ``cap * tanh(x / cap)``."""
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# -- rotary embeddings -----------------------------------------------------

def rope_freqs(dh_rot: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dh_rot, 2, dtype=jnp.float32) / dh_rot))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(dh_rot: int, theta: float, yarn: Any) -> jax.Array:
    """YaRN inverse frequencies (DeepSeek-V2): each of the ``dh_rot / 2``
    frequencies blends the plain one and the one interpolated by
    ``factor``, by a linear ramp between the dims whose wavelength turns
    ``beta_fast`` and ``beta_slow`` times over ``original_max_position``;
    the same at every position."""

    def correction_dim(rotations: float) -> float:
        return (dh_rot * math.log(yarn.original_max_position
                                  / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dh_rot - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dh_rot // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = rope_freqs(dh_rot, theta)
    return extra / yarn.factor * ramp + extra * (1.0 - ramp)


def apply_rope(
    x: jax.Array,  # (..., T, H, Dh) or (..., H, Dh) with positions broadcast
    positions: jax.Array,  # (..., T) int32
    theta: float = 10000.0,
    dh_rot: Optional[int] = None,
    scaling: Any = None,
) -> jax.Array:
    """Rotary embedding on the first ``dh_rot`` head dims (rest pass through).
    ``scaling`` is a :class:`~repro.models.config.YarnConfig` or None."""
    dh = x.shape[-1]
    dh_rot = dh if dh_rot is None else dh_rot
    if scaling is None:
        freqs = rope_freqs(dh_rot, theta)  # (dh_rot/2,)
    else:
        freqs = yarn_freqs(dh_rot, theta, scaling)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, dh_rot/2)
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    if scaling is not None:
        mag = (yarn_mscale(scaling.factor, scaling.mscale)
               / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if mag != 1.0:
            cos, sin = cos * mag, sin * mag
    xr = x[..., :dh_rot].astype(jnp.float32)
    x1, x2 = xr[..., : dh_rot // 2], xr[..., dh_rot // 2 :]
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    out = jnp.concatenate([rotated.astype(x.dtype), x[..., dh_rot:]], axis=-1)
    return out


# -- attention ---------------------------------------------------------------

def _chunk_mask(
    q_pos: jax.Array,  # (Cq,)
    k_pos: jax.Array,  # (Ck,)
    causal: bool,
    window: Optional[int],
    k_len: Optional[jax.Array] = None,
) -> jax.Array:
    mask = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    if k_len is not None:
        mask &= k_pos[None, :] < k_len
    return mask


def chunked_attention(
    q: jax.Array,  # (B, Tq, H, Dh)
    k: jax.Array,  # (B, Tk, Kv, Dh)
    v: jax.Array,  # (B, Tk, Kv, Dhv)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    scale: Optional[float] = None,
    q_chunk: int = 512,
    kv_chunk: int = 512,
    q_offset: int = 0,
) -> jax.Array:
    """Streaming attention with online softmax; O(Cq·Ck) peak score memory.

    GQA: ``H = Kv * rep``.  Returns (B, Tq, H, Dhv).
    """
    B, Tq, H, Dh = q.shape
    _, Tk, Kv, _ = k.shape
    Dhv = v.shape[-1]
    rep = H // Kv
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)

    q_chunk = min(q_chunk, Tq)
    kv_chunk = min(kv_chunk, Tk)
    nq = -(-Tq // q_chunk)
    nk = -(-Tk // kv_chunk)
    pad_q = nq * q_chunk - Tq
    pad_k = nk * kv_chunk - Tk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))

    qc = q.reshape(B, nq, q_chunk, Kv, rep, Dh)
    kc = k.reshape(B, nk, kv_chunk, Kv, Dh)
    vc = v.reshape(B, nk, kv_chunk, Kv, Dhv)
    k_valid = Tk  # unpadded length

    def q_block(qi, q_blk):
        # q_blk: (B, Cq, Kv, rep, Dh)
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, inputs):
            acc, m, l = carry
            ki, k_blk, v_blk = inputs
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum(
                "bqkrd,bckd->bkrqc", q_blk, k_blk,
                preferred_element_type=jnp.float32,
            ) * scale
            s = softcap(s, attn_softcap)
            mask = _chunk_mask(q_pos, k_pos, causal, window, k_len=k_valid)
            s = jnp.where(mask[None, None, None], s, MASK_VALUE)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bkrqc,bckd->bkrqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32,
            )
            acc_new = acc * corr[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, Kv, rep, q_chunk, Dhv), jnp.float32)
        m0 = jnp.full((B, Kv, rep, q_chunk), MASK_VALUE, jnp.float32)
        l0 = jnp.zeros((B, Kv, rep, q_chunk), jnp.float32)
        kis = jnp.arange(nk)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (kis, jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)),
        )
        o = acc / jnp.maximum(l[..., None], 1e-30)
        # (B, Kv, rep, Cq, Dhv) -> (B, Cq, Kv, rep, Dhv)
        return jnp.transpose(o, (0, 3, 1, 2, 4))

    if causal and window is None and q_offset == 0 and nq > 1:
        # Block-causal skip: iterate only the lower-triangle (qi, ki) block
        # pairs — half the FLOPs of the dense sweep.  Accumulators for all
        # q blocks ride the scan carry; each step updates one q block.
        pairs = [(i, j) for i in range(nq) for j in range(nk)
                 if j * kv_chunk <= i * q_chunk + q_chunk - 1]
        pair_q = jnp.asarray([p_[0] for p_ in pairs])
        pair_k = jnp.asarray([p_[1] for p_ in pairs])

        def pair_step(carry, inputs):
            acc, m, l = carry  # (nq, B, Kv, rep, Cq, [Dhv])
            qi, ki = inputs
            q_blk = jax.lax.dynamic_index_in_dim(qc, qi, 1, keepdims=False)
            k_blk = jax.lax.dynamic_index_in_dim(kc, ki, 1, keepdims=False)
            v_blk = jax.lax.dynamic_index_in_dim(vc, ki, 1, keepdims=False)
            q_pos = qi * q_chunk + jnp.arange(q_chunk)
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum(
                "bqkrd,bckd->bkrqc", q_blk, k_blk,
                preferred_element_type=jnp.float32,
            ) * scale
            s = softcap(s, attn_softcap)
            mask = (q_pos[:, None] >= k_pos[None, :]) & (k_pos < Tk)[None, :]
            s = jnp.where(mask[None, None, None], s, MASK_VALUE)
            m_prev = jax.lax.dynamic_index_in_dim(m, qi, 0, keepdims=False)
            l_prev = jax.lax.dynamic_index_in_dim(l, qi, 0, keepdims=False)
            acc_prev = jax.lax.dynamic_index_in_dim(acc, qi, 0, keepdims=False)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.where(mask[None, None, None],
                          jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bkrqc,bckd->bkrqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32,
            )
            acc_new = acc_prev * corr[..., None] + pv
            return (
                jax.lax.dynamic_update_index_in_dim(acc, acc_new, qi, 0),
                jax.lax.dynamic_update_index_in_dim(m, m_new, qi, 0),
                jax.lax.dynamic_update_index_in_dim(l, l_new, qi, 0),
            ), None

        acc0 = jnp.zeros((nq, B, Kv, rep, q_chunk, Dhv), jnp.float32)
        m0 = jnp.full((nq, B, Kv, rep, q_chunk), MASK_VALUE, jnp.float32)
        l0 = jnp.zeros((nq, B, Kv, rep, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            pair_step, (acc0, m0, l0), (pair_q, pair_k)
        )
        o = acc / jnp.maximum(l[..., None], 1e-30)
        # (nq, B, Kv, rep, Cq, Dhv) -> (B, nq*Cq, H, Dhv)
        o = jnp.transpose(o, (1, 0, 4, 2, 3, 5)).reshape(
            B, nq * q_chunk, H, Dhv
        )
        return o[:, :Tq].astype(v.dtype)

    qis = jnp.arange(nq)
    o = jax.lax.map(lambda args: q_block(*args), (qis, jnp.moveaxis(qc, 1, 0)))
    # o: (nq, B, Cq, Kv, rep, Dhv)
    o = jnp.moveaxis(o, 0, 1).reshape(B, nq * q_chunk, H, Dhv)
    return o[:, :Tq].astype(v.dtype)


def decode_attention(
    q: jax.Array,  # (B, H, Dh) — one new token per sequence
    k_cache: jax.Array,  # (B, S, Kv, Dh)
    v_cache: jax.Array,  # (B, S, Kv, Dhv)
    length: jax.Array,  # (B,) valid cache entries (incl. current token)
    *,
    window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-token attention over a (possibly windowed) KV cache."""
    B, H, Dh = q.shape
    _, S, Kv, _ = k_cache.shape
    rep = H // Kv
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    qr = q.reshape(B, Kv, rep, Dh)
    s = jnp.einsum(
        "bkrd,bskd->bkrs", qr, k_cache, preferred_element_type=jnp.float32
    ) * scale
    s = softcap(s, attn_softcap)
    pos = jnp.arange(S)[None, :]  # (1, S)
    valid = pos < length[:, None]
    if window is not None:
        valid &= pos >= (length[:, None] - window)
    s = jnp.where(valid[:, None, None], s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bkrs,bskd->bkrd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return o.reshape(B, H, v_cache.shape[-1]).astype(v_cache.dtype)


# -- MLP ---------------------------------------------------------------

def mlp_defs(d_model: int, d_ff: int, gated: bool = True) -> Dict[str, ParamDef]:
    if gated:
        return {
            "wi_gate": ParamDef((d_model, d_ff), (FSDP, TP)),
            "wi_up": ParamDef((d_model, d_ff), (FSDP, TP)),
            "wo": ParamDef((d_ff, d_model), (TP, FSDP)),
        }
    return {
        "wi": ParamDef((d_model, d_ff), (FSDP, TP)),
        "wo": ParamDef((d_ff, d_model), (TP, FSDP)),
    }


def mlp_apply(p: Dict[str, jax.Array], x: jax.Array, act: str = "silu") -> jax.Array:
    act_fn = {
        "silu": jax.nn.silu,
        "gelu": lambda y: jax.nn.gelu(y, approximate=True),
        "gelu_exact": lambda y: jax.nn.gelu(y, approximate=False),
        "relu": jax.nn.relu,
    }[act]
    if "wi_gate" in p:
        h = act_fn(x @ p["wi_gate"]) * (x @ p["wi_up"])
    else:
        h = act_fn(x @ p["wi"])
    return h @ p["wo"]


# -- loss ---------------------------------------------------------------

def chunked_ce_loss(
    x: jax.Array,  # (B, T, D) final hidden states
    unembed: jax.Array,  # (D, V)
    labels: jax.Array,  # (B, T) int32; -100 = ignore
    *,
    t_chunk: int = 512,
    logit_softcap: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Mean CE over valid tokens, computed in T-chunks so the (.., V)
    logits tensor never exists at full sequence length.  Returns
    ``(loss, n_valid)``."""
    B, T, D = x.shape
    t_chunk = min(t_chunk, T)
    nt = -(-T // t_chunk)
    pad = nt * t_chunk - T
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-100)
    xc = jnp.moveaxis(x.reshape(B, nt, t_chunk, D), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, nt, t_chunk), 1, 0)

    def chunk_loss(args):
        xb, lb = args  # (B, C, D), (B, C)
        logits = (xb @ unembed).astype(jnp.float32)
        logits = softcap(logits, logit_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(
            logits, jnp.maximum(lb, 0)[..., None], axis=-1
        )[..., 0]
        valid = lb >= 0
        return jnp.sum(jnp.where(valid, lse - ll, 0.0)), jnp.sum(valid)

    losses, counts = jax.lax.map(chunk_loss, (xc, lc))
    n = jnp.maximum(jnp.sum(counts), 1)
    return jnp.sum(losses) / n, n
