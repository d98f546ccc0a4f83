"""Operations and bytes that a latent-attention (MLA) decoder with routed
and shared experts needs, as one chip's expert-parallel share, from its
shapes (DeepSeek-V2 ``config.json`` keys; ``n_routed_experts`` counts the
experts held here, ``published.n_routed_experts`` the router's width).

The same rule as ``counts.py``: what the algorithm needs, not what the
program happens to do.  A batch-1 decode step reads every weight but the
input embedding (a gather of one row) and the routed experts, and of the
routed experts only the held ones its token was routed to
(``held_routes``, counted by the program); it reads the latent cache over
the ``n`` positions it attends to, in the absorbed form (queries projected
into the latent space).  A prefill of ``t`` tokens reads every held
expert once and multiplies ``t * top_k * held / routed`` routed slots
through them: the expected count under the router's softmax, which the
program does not count for a prefill.
"""

from __future__ import annotations

from typing import Dict, Tuple

from chipbench.counts import _DTYPE_BYTES

_ROUTER_BYTES = 4  # the router is float32, as published


def _i(cfg: Dict, key: str) -> int:
    return int(cfg[key])


def _layers(cfg: Dict) -> Tuple[int, int]:
    """(dense layers, MoE layers)."""
    dense = _i(cfg, "first_k_dense_replace")
    return dense, _i(cfg, "num_hidden_layers") - dense


def attn_params(cfg: Dict) -> int:
    """Matmul weights of one MLA layer: q, the joint latent and rope key
    projection, the key and value up-projections, the output."""
    d, h = _i(cfg, "hidden_size"), _i(cfg, "num_attention_heads")
    r, dr = _i(cfg, "kv_lora_rank"), _i(cfg, "qk_rope_head_dim")
    dn, dv = _i(cfg, "qk_nope_head_dim"), _i(cfg, "v_head_dim")
    return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def expert_params(cfg: Dict) -> int:
    """Weights of one routed expert (gated)."""
    return 3 * _i(cfg, "hidden_size") * _i(cfg, "moe_intermediate_size")


def _fixed(cfg: Dict) -> Tuple[int, int]:
    """(bf16 weights, router weights) that every step reads whatever its
    routing: attention and norms of every layer, the dense layers, the
    shared experts, the final norm and the head."""
    d, v, r = _i(cfg, "hidden_size"), _i(cfg, "vocab_size"), _i(cfg, "kv_lora_rank")
    dense, moe = _layers(cfg)
    per_layer = attn_params(cfg) + r + 2 * d
    bf16 = (dense + moe) * per_layer
    bf16 += dense * 3 * d * _i(cfg, "intermediate_size")
    bf16 += moe * _i(cfg, "n_shared_experts") * expert_params(cfg)
    bf16 += d + d * v
    router = moe * d * int(cfg["published"]["n_routed_experts"])
    return bf16, router


def latent_bytes_per_token(cfg: Dict) -> int:
    """Bytes of one position of the latent cache, over all layers."""
    width = _i(cfg, "kv_lora_rank") + _i(cfg, "qk_rope_head_dim")
    return _i(cfg, "num_hidden_layers") * width * _DTYPE_BYTES[cfg["torch_dtype"]]


def _matmul_flops(cfg: Dict, held_slots: float) -> Tuple[float, float]:
    """Operations of one token: (projections, router and FFNs with
    ``held_slots`` routed slots on held experts over all layers; the
    head)."""
    d, v = _i(cfg, "hidden_size"), _i(cfg, "vocab_size")
    dense, moe = _layers(cfg)
    flops = (dense + moe) * attn_params(cfg)
    flops += dense * 3 * d * _i(cfg, "intermediate_size")
    flops += moe * (_i(cfg, "n_shared_experts") * expert_params(cfg)
                    + d * int(cfg["published"]["n_routed_experts"]))
    flops += held_slots * expert_params(cfg)
    return 2.0 * flops, 2.0 * d * v


def decode_cost(cfg: Dict, n: int, held_routes: int) -> Tuple[float, float]:
    """(flops, bytes) of one batch-1 decode step that attends over ``n``
    positions (itself included) and whose token was routed to
    ``held_routes`` held experts over all layers.  Absorbed attention: per
    head, the query's no-rope part is projected into the latent (``r``),
    scored against ``n`` latents and rope keys, and the weighted latent
    projected out through the value up-projection."""
    h = _i(cfg, "num_attention_heads")
    r, dr = _i(cfg, "kv_lora_rank"), _i(cfg, "qk_rope_head_dim")
    layers = _i(cfg, "num_hidden_layers")
    body, head = _matmul_flops(cfg, held_routes)
    # the attention's matmuls are in attn_params, once a token; over n
    # positions: scores (r + dr) and the weighted latent (r), per head
    flops = body + head + layers * 2.0 * h * n * (2 * r + dr)
    bf16, router = _fixed(cfg)
    wbytes = _DTYPE_BYTES[cfg["torch_dtype"]]
    nbytes = ((bf16 + held_routes * expert_params(cfg)) * wbytes
              + router * _ROUTER_BYTES + latent_bytes_per_token(cfg) * n)
    return float(flops), float(nbytes)


def prefill_cost(cfg: Dict, t: int) -> Tuple[float, float]:
    """(flops, bytes) of the prefill of one ``t``-token prompt that ends in
    the logits of its last position: expanded attention over ``t (t + 1)
    / 2`` causal pairs (keys of ``dn + dr``, values of ``dv`` per head),
    the expected held slots, one read of every weight but the embedding,
    the write of ``t`` latent positions."""
    h = _i(cfg, "num_attention_heads")
    dn, dr, dv = (_i(cfg, "qk_nope_head_dim"), _i(cfg, "qk_rope_head_dim"),
                  _i(cfg, "v_head_dim"))
    dense, moe = _layers(cfg)
    held = _i(cfg, "n_routed_experts")
    share = held / int(cfg["published"]["n_routed_experts"])
    slots = t * moe * _i(cfg, "num_experts_per_tok") * share
    body, head = _matmul_flops(cfg, 0)
    flops = t * body + slots * 2.0 * expert_params(cfg) + head
    flops += (dense + moe) * 2.0 * h * (dn + dr + dv) * t * (t + 1) // 2
    bf16, router = _fixed(cfg)
    wbytes = _DTYPE_BYTES[cfg["torch_dtype"]]
    nbytes = ((bf16 + moe * held * expert_params(cfg)) * wbytes
              + router * _ROUTER_BYTES + latent_bytes_per_token(cfg) * t)
    return float(flops), float(nbytes)


def weight_bytes(cfg: Dict) -> int:
    """Bytes of every weight held on the chip, the embedding included."""
    bf16, router = _fixed(cfg)
    d, v = _i(cfg, "hidden_size"), _i(cfg, "vocab_size")
    _, moe = _layers(cfg)
    held = moe * _i(cfg, "n_routed_experts") * expert_params(cfg)
    return ((bf16 + held + d * v) * _DTYPE_BYTES[cfg["torch_dtype"]]
            + router * _ROUTER_BYTES)
