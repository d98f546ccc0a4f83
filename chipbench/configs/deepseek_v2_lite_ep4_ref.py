"""Plain reference of DeepSeek-V2-Lite as one chip's expert-parallel
share, as the published config.json describes the model: pre-norm
RMSNorm blocks; latent attention (MLA) in its expanded form, the latent
``c_kv`` decompressed into per-head keys and values and one causal pass,
with YaRN rope on the 64 rope columns and the softmax scale times
``mscale^2``; layer 0 a SiLU-gated dense MLP; every later layer a
softmax router over all routed experts in float32, greedy top-k, the
weights kept as they are (``norm_topk_prob`` false), the held experts'
weighted outputs, zero for slots routed to experts held elsewhere, plus
the shared experts; final RMSNorm and an untied output head.

The share: the router keeps all its published outputs, and what experts
held on other chips would add is left out, as the program leaves it out.
Every held expert runs on every token, weighted by the token's routing
weight for it (zero where it was not routed): no capacity, no token
dropped, independent of how the program groups its tokens.

Float32 throughout with matmuls at ``HIGHEST`` precision; one full causal
forward pass over the prompt and the served tokens, no cache, no kernels,
no batching, layer by layer under ``lax.scan`` so that only one layer's
weights are widened to float32 at a time.  It reads the benchmark's own
weights by plain name and imports nothing of the system under test.
Rope rotates halves of the rope columns (``assumed`` in the configuration
file: a fixed permutation of DeepSeek's interleaved pairs).

``control`` puts the same reference in a lower precision: each bf16
weight matrix is rounded per output channel to ``"int8"`` or ``"fp8"``
(e4m3) and back before use; the float32 router is kept.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32

#: input (contracted) axes of each weight of one layer; the rest are the
#: output channels that a lower-precision copy scales by
_IN_AXES = {"wq": (0,), "wkv_a": (0,), "wk_b": (0,), "wv_b": (0,),
            "wo": (0, 1), "w_gate": (1,), "w_up": (1,), "w_down": (1,),
            "s_gate": (0,), "s_up": (0,), "s_down": (0,),
            "embed": (1,), "lm_head": (0,)}
_MLA = ("ln1", "wq", "wkv_a", "kv_norm", "wk_b", "wv_b", "wo", "ln2")
_MOE = ("router", "w_gate", "w_up", "w_down", "s_gate", "s_up", "s_down")


def _round_trip(x: jax.Array, in_axes: Tuple[int, ...],
                mode: Optional[str]) -> jax.Array:
    x = x.astype(F32)
    if mode is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=in_axes, keepdims=True)
    if mode == "int8":
        scale = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    if mode == "fp8":
        scale = jnp.maximum(amax, 1e-30) / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    raise ValueError(f"unknown control precision {mode!r}")


def _widen(lw: Dict[str, jax.Array], control: Optional[str]) -> Dict[str, jax.Array]:
    return {k: (_round_trip(v, _IN_AXES[k], control) if k in _IN_AXES
                else v.astype(F32)) for k, v in lw.items()}


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, rs: Dict) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies over ``dim`` rope columns."""
    base = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra, inter = 1.0 / base, 1.0 / (rs["factor"] * base)

    def corr(rot: float) -> float:
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def _rope(x: jax.Array, inv: jax.Array, mag: float) -> jax.Array:
    """Rotate-half rope on (T, heads, d) at positions 0..T-1."""
    t, _, d = x.shape
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = (mag * jnp.cos(ang))[:, None, :], (mag * jnp.sin(ang))[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=None)
def _logits_fn(dims: Tuple, eps: float, rope: Tuple, control: Optional[str]):
    (nope, rank, top_k, first_held, n_held) = dims
    inv, mag, scale = rope
    inv = np.asarray(inv, np.float32)

    def mla(x, w):
        t = x.shape[0]
        h = _rms(x, w["ln1"], eps)
        q = jnp.einsum("td,dhk->thk", h, w["wq"], precision=HI)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, mag)], -1)
        kv = jnp.einsum("td,dc->tc", h, w["wkv_a"], precision=HI)
        c = _rms(kv[:, :rank], w["kv_norm"], eps)
        k_pe = _rope(kv[:, None, rank:], inv, mag)
        k_nope = jnp.einsum("tr,rhk->thk", c, w["wk_b"], precision=HI)
        v = jnp.einsum("tr,rhv->thv", c, w["wv_b"], precision=HI)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:2] + k_pe.shape[2:])],
            -1)
        s = jnp.einsum("thk,shk->hts", q, k, precision=HI) * scale
        causal = jnp.tril(jnp.ones((t, t), bool))
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shv->thv", p, v, precision=HI)
        return x + jnp.einsum("thv,hvd->td", o, w["wo"], precision=HI)

    def mlp(h, g, u, dn):
        a = jnp.einsum("td,df->tf", h, g, precision=HI)
        b = jnp.einsum("td,df->tf", h, u, precision=HI)
        return jnp.einsum("tf,fd->td", jax.nn.silu(a) * b, dn, precision=HI)

    def moe_layer(x, lw):
        w = _widen(lw, control)
        x = mla(x, w)
        h = _rms(x, w["ln2"], eps)
        probs = jax.nn.softmax(
            jnp.einsum("td,de->te", h, w["router"], precision=HI), axis=-1)
        top_w, top_e = jax.lax.top_k(probs, top_k)
        local = top_e - first_held
        gate = jnp.sum(jnp.where(
            local[..., None] == jnp.arange(n_held), top_w[..., None], 0.0), 1)
        a = jnp.einsum("td,edf->tef", h, w["w_gate"], precision=HI)
        b = jnp.einsum("td,edf->tef", h, w["w_up"], precision=HI)
        y = jnp.einsum("tef,efd->ted", jax.nn.silu(a) * b, w["w_down"],
                       precision=HI)
        routed = jnp.einsum("ted,te->td", y, gate, precision=HI)
        shared = mlp(h, w["s_gate"], w["s_up"], w["s_down"])
        return x + routed + shared, None

    @jax.jit
    def logits_at(weights: Dict[str, jax.Array], tokens: jax.Array,
                  positions: jax.Array) -> jax.Array:
        emb = _round_trip(weights["embed"], _IN_AXES["embed"], control)
        x = emb[tokens]
        # layer 0's dense MLP under the shared experts' names: (in, out)
        l0 = _widen({**{k: weights["l0_" + k] for k in _MLA},
                     "s_gate": weights["l0_w_gate"], "s_up": weights["l0_w_up"],
                     "s_down": weights["l0_w_down"]}, control)
        x = mla(x, l0)
        x = x + mlp(_rms(x, l0["ln2"], eps), l0["s_gate"], l0["s_up"],
                    l0["s_down"])
        x, _ = jax.lax.scan(moe_layer, x, {k: weights[k] for k in _MLA + _MOE})
        x = _rms(x, weights["final_norm"].astype(F32), eps)
        head = _round_trip(weights["lm_head"], _IN_AXES["lm_head"], control)
        return jnp.einsum("pd,dv->pv", x[positions], head, precision=HI)

    return logits_at


def _consts(cfg: Dict) -> Tuple[Tuple, float, Tuple]:
    rs = cfg["rope_scaling"]
    theta = float(cfg["rope_theta"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    inv = tuple(float(f) for f in yarn_inv_freq(rope, theta, rs))
    mag = (yarn_mscale(rs["factor"], rs["mscale"])
           / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    scale = (nope + rope) ** -0.5 * yarn_mscale(
        rs["factor"], rs["mscale_all_dim"]) ** 2
    held = cfg["held_experts"]
    dims = (nope, int(cfg["kv_lora_rank"]), int(cfg["num_experts_per_tok"]),
            int(held["first"]), int(held["count"]))
    return dims, float(cfg["rms_norm_eps"]), (inv, mag, scale)


def logits(weights: Dict[str, jax.Array], cfg: Dict, tokens: np.ndarray,
           positions: np.ndarray, control: Optional[str] = None) -> jax.Array:
    """Float32 logits at ``positions`` of the causal pass over ``tokens``."""
    dims, eps, rope = _consts(cfg)
    fn = _logits_fn(dims, eps, rope, control)
    with jax.default_matmul_precision("highest"):
        return fn(weights, jnp.asarray(tokens, jnp.int32),
                  jnp.asarray(positions, jnp.int32))


def served_gaps(weights: Dict[str, jax.Array], cfg: Dict, prompt: np.ndarray,
                served: np.ndarray, pad_tokens: int, pad_served: int,
                controls: Tuple[str, ...] = ()) -> Dict[str, np.ndarray]:
    """For each served token, how far its reference logit lies below the
    reference's best at that position (``served``), and, for each
    precision in ``controls``, the same gap for the token that the
    lower-precision reference puts first (``control:<precision>``).

    The pass runs over the prompt and the served tokens but the last,
    padded to ``pad_tokens`` (causal, so padding changes nothing before
    it); the ``n`` scored positions are padded to ``pad_served``.
    """
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    n, plen = len(served), len(prompt)
    seq = np.zeros(pad_tokens, np.int32)
    seq[:plen] = prompt
    seq[plen:plen + n - 1] = served[:-1]
    pos = np.full(pad_served, plen - 1, np.int32)
    pos[:n] = plen - 1 + np.arange(n)
    ref = logits(weights, cfg, seq, pos)
    best = jnp.max(ref, -1)
    picks = jnp.zeros(pad_served, jnp.int32).at[:n].set(jnp.asarray(served))
    out = {"served": np.asarray(best - jnp.take_along_axis(
        ref, picks[:, None], -1)[:, 0])[:n]}
    for control in controls:
        top = jnp.argmax(logits(weights, cfg, seq, pos, control), -1)
        out[f"control:{control}"] = np.asarray(best - jnp.take_along_axis(
            ref, top[:, None], -1)[:, 0])[:n]
    return out
