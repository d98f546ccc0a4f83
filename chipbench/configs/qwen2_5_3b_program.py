"""How the benchmark hands a Qwen2-family configuration to the program.

``program_config`` builds the program's ``ModelConfig`` from the sizes in
the configuration file; ``draw_weights`` makes the weights on the device
in one jitted call from the seed, in bf16, under plain names that the
reference reads; ``program_params`` arranges those same arrays (no copy)
into the program's parameter tree.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: the program normalizes with this epsilon and takes no other
_PROGRAM_RMS_EPS = 1e-6


def program_config(cfg: Dict) -> Any:
    from repro.models import BlockSpec, ModelConfig

    if float(cfg["rms_norm_eps"]) != _PROGRAM_RMS_EPS:
        raise ValueError(f"the program normalizes with eps {_PROGRAM_RMS_EPS}, "
                         f"the configuration states {cfg['rms_norm_eps']}")
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return ModelConfig(
        name=cfg["name"],
        d_model=d,
        n_heads=h,
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg.get("head_dim") or d // h),
        d_ff=int(cfg["intermediate_size"]),
        vocab=int(cfg["vocab_size"]),
        pattern=(BlockSpec(mixer="attn", ffn="dense"),),
        n_periods=int(cfg["num_hidden_layers"]),
        act=cfg["hidden_act"],
        qkv_bias=True,
        rope_theta=float(cfg["rope_theta"]),
    )


def _leaf_specs(cfg: Dict) -> Tuple[Tuple[str, Tuple[int, ...], str, int], ...]:
    """(name, shape, kind, fan_in) of every weight, layers stacked."""
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    kv, f = int(cfg["num_key_value_heads"]), int(cfg["intermediate_size"])
    dh = int(cfg.get("head_dim") or d // h)
    n, v = int(cfg["num_hidden_layers"]), int(cfg["vocab_size"])
    return (
        ("embed", (v, d), "embed", d),
        ("ln1", (n, d), "norm", 0),
        ("wq", (n, d, h, dh), "proj", d),
        ("bq", (n, h, dh), "bias", 0),
        ("wk", (n, d, kv, dh), "proj", d),
        ("bk", (n, kv, dh), "bias", 0),
        ("wv", (n, d, kv, dh), "proj", d),
        ("bv", (n, kv, dh), "bias", 0),
        ("wo", (n, h, dh, d), "proj", h * dh),
        ("ln2", (n, d), "norm", 0),
        ("w_gate", (n, d, f), "proj", d),
        ("w_up", (n, d, f), "proj", d),
        ("w_down", (n, f, d), "proj", f),
        ("final_norm", (d,), "norm", 0),
    )


@functools.lru_cache(maxsize=None)
def _drawer(specs: Tuple, dtype: str):
    out_dtype = jnp.dtype(dtype)

    @jax.jit
    def draw_weights(seed_words: jax.Array) -> Dict[str, jax.Array]:
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(0), seed_words[0]), seed_words[1]
        )
        out = {}
        for i, (name, shape, kind, fan_in) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if kind == "norm":
                x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif kind == "bias":
                x = 0.1 * jax.random.normal(k, shape, jnp.float32)
            else:
                scale = 0.02 if kind == "embed" else 1.0 / math.sqrt(fan_in)
                x = scale * jax.random.truncated_normal(
                    k, -2.0, 2.0, shape, jnp.float32)
            out[name] = x.astype(out_dtype)
        out["unembed"] = out["embed"].T  # tied output head
        return out

    return draw_weights


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two uint32 words."""
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def draw_weights(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    if not cfg.get("tie_word_embeddings"):
        raise ValueError("only tied embeddings are drawn here")
    return _drawer(_leaf_specs(cfg), cfg["torch_dtype"])(seed_words(seed))


def program_params(w: Dict[str, jax.Array]) -> Dict[str, Any]:
    """The program's parameter tree over the same arrays."""
    return {
        "embed": w["embed"],
        "prelude": [],
        "body": [{
            "norm1": {"scale": w["ln1"]},
            "mixer": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                      "wo": w["wo"], "bq": w["bq"], "bk": w["bk"],
                      "bv": w["bv"]},
            "norm2": {"scale": w["ln2"]},
            "ffn": {"wi_gate": w["w_gate"], "wi_up": w["w_up"],
                    "wo": w["w_down"]},
        }],
        "postlude": [],
        "final_norm": {"scale": w["final_norm"]},
        "unembed": w["unembed"],
    }
