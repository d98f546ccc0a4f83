"""Plain reference of a Qwen2-family decoder, as the published config.json
describes it: pre-norm RMSNorm blocks, GQA attention with q/k/v biases
and rotate-half RoPE, SiLU-gated MLP, final RMSNorm, output head tied to
the embedding.

Float32 throughout with matmuls at ``HIGHEST`` precision; one full causal
forward pass over the prompt and the served tokens, no cache, no kernels,
no batching, layer by layer under ``lax.scan`` so that only one layer's
weights are widened to float32 at a time.  It reads the benchmark's own
weights by plain name and imports nothing of the system under test.

``control`` puts the same reference in a lower precision: each weight
matrix is rounded per output channel to ``"int8"`` or ``"fp8"`` (e4m3)
and back before use.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32

#: input (contracted) axes of each weight, per layer; the rest are the
#: output channels that a lower-precision copy scales by
_IN_AXES = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
            "w_gate": (0,), "w_up": (0,), "w_down": (0,), "embed": (1,)}


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


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotate-half RoPE on (T, heads, dh) at positions 0..T-1."""
    t, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=None)
def _logits_fn(n_heads: int, n_kv: int, eps: float, theta: float,
               control: Optional[str]):
    def layer(x, lw):
        t = x.shape[0]
        w = {k: (_round_trip(v, _IN_AXES[k], control) if k in _IN_AXES
                 else v.astype(F32)) for k, v in lw.items()}
        h = _rms(x, w["ln1"], eps)
        q = jnp.einsum("td,dhk->thk", h, w["wq"], precision=HI) + w["bq"]
        k = jnp.einsum("td,dhk->thk", h, w["wk"], precision=HI) + w["bk"]
        v = jnp.einsum("td,dhk->thk", h, w["wv"], precision=HI) + w["bv"]
        q, k = _rope(q, theta), _rope(k, theta)
        rep = n_heads // n_kv
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("thk,shk->hts", q, k, precision=HI)
        s = s / jnp.sqrt(jnp.asarray(q.shape[-1], F32))
        causal = jnp.tril(jnp.ones((t, t), bool))
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shk->thk", p, v, precision=HI)
        x = x + jnp.einsum("thk,hkd->td", o, w["wo"], precision=HI)
        h = _rms(x, w["ln2"], eps)
        g = jnp.einsum("td,df->tf", h, w["w_gate"], precision=HI)
        u = jnp.einsum("td,df->tf", h, w["w_up"], precision=HI)
        x = x + jnp.einsum("tf,fd->td", jax.nn.silu(g) * u, w["w_down"],
                           precision=HI)
        return x, None

    @jax.jit
    def logits_at(weights: Dict[str, jax.Array], tokens: jax.Array,
                  positions: jax.Array) -> jax.Array:
        emb = _round_trip(weights["embed"], _IN_AXES["embed"], control)
        x = emb[tokens]
        stacked = {k: weights[k] for k in (
            "ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "ln2",
            "w_gate", "w_up", "w_down")}
        x, _ = jax.lax.scan(layer, x, stacked)
        x = _rms(x, weights["final_norm"].astype(F32), eps)
        return jnp.einsum("pd,vd->pv", x[positions], emb, precision=HI)

    return logits_at


def logits(weights: Dict[str, jax.Array], cfg: Dict, tokens: np.ndarray,
           positions: np.ndarray, control: Optional[str] = None) -> jax.Array:
    """Float32 logits at ``positions`` of the causal pass over ``tokens``."""
    fn = _logits_fn(int(cfg["num_attention_heads"]),
                    int(cfg["num_key_value_heads"]),
                    float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
                    control)
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
