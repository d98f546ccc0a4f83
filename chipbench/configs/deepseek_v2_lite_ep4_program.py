"""How the benchmark hands DeepSeek-V2-Lite, as one chip's expert-parallel
share, to the program.

``program_config`` builds the program's ``ModelConfig`` from the
configuration file: latent attention with YaRN rope, layer 0 dense, then
MoE layers whose router keeps the published ``n_routed_experts`` outputs
and top-k while the layer holds only the experts named under
``held_experts``.  ``draw_weights`` makes the weights on the device from
the seed, in bf16 (the router in float32), under plain names that the
reference reads; each stacked weight is drawn one layer at a time, so
that no float32 copy of a whole stack is ever held.  ``program_params``
arranges those same arrays (no copy) into the program's parameter tree.
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

#: published settings the program implements and no other
_FIXED = {"scoring_func": "softmax", "topk_method": "greedy",
          "routed_scaling_factor": 1, "moe_layer_freq": 1, "n_group": 1,
          "topk_group": 1, "q_lora_rank": None, "attention_bias": False,
          "hidden_act": "silu"}


def _check(cfg: Dict) -> None:
    if float(cfg["rms_norm_eps"]) != _PROGRAM_RMS_EPS:
        raise ValueError(f"the program normalizes with eps {_PROGRAM_RMS_EPS}, "
                         f"the configuration states {cfg['rms_norm_eps']}")
    for key, want in _FIXED.items():
        if cfg.get(key) != want:
            raise ValueError(f"the program implements {key}={want!r}, the "
                             f"configuration states {cfg.get(key)!r}")
    if cfg["rope_scaling"]["type"] != "yarn":
        raise ValueError("only YaRN rope scaling is implemented")


def routed_experts(cfg: Dict) -> int:
    """The router's width: the published count of routed experts."""
    return int(cfg["published"]["n_routed_experts"])


def program_config(cfg: Dict) -> Any:
    from repro.models import (
        BlockSpec,
        MLAConfig,
        ModelConfig,
        MoEConfig,
        YarnConfig,
    )

    _check(cfg)
    dense = int(cfg["first_k_dense_replace"])
    rs = cfg["rope_scaling"]
    held = cfg["held_experts"]
    if int(held["count"]) != int(cfg["n_routed_experts"]):
        raise ValueError("n_routed_experts counts the experts held here")
    return ModelConfig(
        name=cfg["name"],
        d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]),
        d_ff=int(cfg["intermediate_size"]),
        vocab=int(cfg["vocab_size"]),
        prelude=(BlockSpec(mixer="mla", ffn="dense"),) * dense,
        pattern=(BlockSpec(mixer="mla", ffn="moe"),),
        n_periods=int(cfg["num_hidden_layers"]) - dense,
        act=cfg["hidden_act"],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YarnConfig(
            factor=float(rs["factor"]),
            original_max_position=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"]),
        ),
        mla=MLAConfig(
            kv_lora_rank=int(cfg["kv_lora_rank"]),
            qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
            qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
            v_head_dim=int(cfg["v_head_dim"]),
        ),
        moe=MoEConfig(
            n_experts=routed_experts(cfg),
            top_k=int(cfg["num_experts_per_tok"]),
            d_expert=int(cfg["moe_intermediate_size"]),
            n_shared=int(cfg["n_shared_experts"]),
            normalize_top_k=bool(cfg["norm_topk_prob"]),
            first_held=int(held["first"]),
            n_held=int(held["count"]),
        ),
    )


def _leaf_specs(cfg: Dict) -> Tuple[Tuple[str, int, Tuple[int, ...], str, int], ...]:
    """(name, layers, shape of one layer, kind, fan_in) of every weight;
    ``layers`` 0 for an unstacked weight."""
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    r, dr = int(cfg["kv_lora_rank"]), int(cfg["qk_rope_head_dim"])
    dn, dv = int(cfg["qk_nope_head_dim"]), int(cfg["v_head_dim"])
    f, fe = int(cfg["intermediate_size"]), int(cfg["moe_intermediate_size"])
    fs = int(cfg["n_shared_experts"]) * fe
    e, v = int(cfg["n_routed_experts"]), int(cfg["vocab_size"])
    n = int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])
    if int(cfg["first_k_dense_replace"]) != 1:
        raise ValueError("the weights are drawn for one leading dense layer")

    def mla(prefix: str, layers: int):
        return (
            (prefix + "ln1", layers, (d,), "norm", 0),
            (prefix + "wq", layers, (d, h, dn + dr), "proj", d),
            (prefix + "wkv_a", layers, (d, r + dr), "proj", d),
            (prefix + "kv_norm", layers, (r,), "norm", 0),
            (prefix + "wk_b", layers, (r, h, dn), "proj", r),
            (prefix + "wv_b", layers, (r, h, dv), "proj", r),
            (prefix + "wo", layers, (h, dv, d), "proj", h * dv),
            (prefix + "ln2", layers, (d,), "norm", 0),
        )

    return (
        ("embed", 0, (v, d), "embed", d),
        *mla("l0_", 0),
        ("l0_w_gate", 0, (d, f), "proj", d),
        ("l0_w_up", 0, (d, f), "proj", d),
        ("l0_w_down", 0, (f, d), "proj", f),
        *mla("", n),
        ("router", n, (d, routed_experts(cfg)), "router", d),
        ("w_gate", n, (e, d, fe), "proj", d),
        ("w_up", n, (e, d, fe), "proj", d),
        ("w_down", n, (e, fe, d), "proj", fe),
        ("s_gate", n, (d, fs), "proj", d),
        ("s_up", n, (d, fs), "proj", d),
        ("s_down", n, (fs, d), "proj", fs),
        ("final_norm", 0, (d,), "norm", 0),
        ("lm_head", 0, (d, v), "embed", d),
    )


def _draw_one(key: jax.Array, shape: Tuple[int, ...], kind: str, fan_in: int,
              dtype: Any) -> jax.Array:
    if kind == "norm":
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        scale = 0.02 if kind == "embed" else 1.0 / math.sqrt(fan_in)
        x = scale * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                                jnp.float32)
    return x.astype(jnp.float32 if kind == "router" else dtype)


@functools.lru_cache(maxsize=None)
def _drawer(specs: Tuple, dtype: str):
    out_dtype = jnp.dtype(dtype)

    @jax.jit
    def draw_weights(seed_words: jax.Array) -> Dict[str, jax.Array]:
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(0), seed_words[0]), seed_words[1]
        )
        out = {}
        for i, (name, layers, shape, kind, fan_in) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if layers:
                out[name] = jax.lax.map(
                    lambda j, k=k, shape=shape, kind=kind, fan_in=fan_in:
                    _draw_one(jax.random.fold_in(k, j), shape, kind, fan_in,
                              out_dtype),
                    jnp.arange(layers))
            else:
                out[name] = _draw_one(k, shape, kind, fan_in, out_dtype)
        return out

    return draw_weights


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two uint32 words."""
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def draw_weights(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    if cfg.get("tie_word_embeddings"):
        raise ValueError("only an untied output head is drawn here")
    program_config(cfg)  # a program that cannot run it fails before the draw
    return _drawer(_leaf_specs(cfg), cfg["torch_dtype"])(seed_words(seed))


def _mla(w: Dict[str, jax.Array], prefix: str) -> Dict[str, jax.Array]:
    return {k: w[prefix + k] for k in
            ("wq", "wkv_a", "kv_norm", "wk_b", "wv_b", "wo")}


def program_params(w: Dict[str, jax.Array]) -> Dict[str, Any]:
    """The program's parameter tree over the same arrays."""
    return {
        "embed": w["embed"],
        "prelude": [{
            "norm1": {"scale": w["l0_ln1"]},
            "mixer": _mla(w, "l0_"),
            "norm2": {"scale": w["l0_ln2"]},
            "ffn": {"wi_gate": w["l0_w_gate"], "wi_up": w["l0_w_up"],
                    "wo": w["l0_w_down"]},
        }],
        "body": [{
            "norm1": {"scale": w["ln1"]},
            "mixer": _mla(w, ""),
            "norm2": {"scale": w["ln2"]},
            "ffn": {"router": w["router"], "w_gate": w["w_gate"],
                    "w_up": w["w_up"], "w_down": w["w_down"],
                    "shared": {"wi_gate": w["s_gate"], "wi_up": w["s_up"],
                               "wo": w["s_down"]}},
        }],
        "postlude": [],
        "final_norm": {"scale": w["final_norm"]},
        "unembed": w["lm_head"],
    }
