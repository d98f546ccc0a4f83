"""Operations and bytes that each measured program needs, from its shapes.

These are what the algorithm needs, not what the program happens to do:
a decode step attends over the ``n`` positions that hold keys, not over
the whole reserved cache, and a prefill writes the keys of its ``T``
prompt positions.  A roofline share is then
``max(flops / peak_flops, bytes / peak_bytes_per_s) / device_time``.

The decoder functions take a Hugging Face style ``config.json`` dict of a
dense GQA decoder (``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``intermediate_size``, ``num_hidden_layers``,
``vocab_size``, ``torch_dtype``, optional ``head_dim``).
"""

from __future__ import annotations

from typing import Dict, Tuple

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(cfg: Dict) -> Tuple[int, int, int, int, int, int, int]:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    kv = int(cfg["num_key_value_heads"])
    dh = int(cfg.get("head_dim") or d // h)
    f = int(cfg["intermediate_size"])
    layers = int(cfg["num_hidden_layers"])
    v = int(cfg["vocab_size"])
    return d, h, kv, dh, f, layers, v


def layer_matmul_params(cfg: Dict) -> int:
    """Weights one token multiplies by in one decoder layer: q, k, v, o
    projections and the gated MLP."""
    d, h, kv, dh, f, _, _ = _dims(cfg)
    return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f


def param_count(cfg: Dict) -> int:
    """Parameters of the published model (tied embeddings counted once)."""
    d, h, kv, dh, _, layers, v = _dims(cfg)
    per_layer = layer_matmul_params(cfg) + (h + 2 * kv) * dh + 2 * d
    embeds = v * d * (1 if cfg.get("tie_word_embeddings") else 2)
    return layers * per_layer + embeds + d


def _weight_bytes(cfg: Dict) -> int:
    """Bytes of weights a forward pass reads: every layer, the final norm
    and the output head (the input embedding is a gather of one row per
    token, counted with the activations and left out)."""
    d, h, kv, dh, _, layers, v = _dims(cfg)
    per_layer = layer_matmul_params(cfg) + (h + 2 * kv) * dh + 2 * d
    return (layers * per_layer + v * d + d) * _DTYPE_BYTES[cfg["torch_dtype"]]


def kv_bytes_per_token(cfg: Dict) -> int:
    d, h, kv, dh, _, layers, _ = _dims(cfg)
    return layers * 2 * kv * dh * _DTYPE_BYTES[cfg["torch_dtype"]]


def decode_cost(cfg: Dict, n: int) -> Tuple[float, float]:
    """(flops, bytes) of one batch-1 decode step whose token attends over
    ``n`` positions (itself included): the weight read, the read of the
    ``n - 1`` cached keys and values and the write of the new ones."""
    d, h, kv, dh, _, layers, v = _dims(cfg)
    flops = 2 * (layers * layer_matmul_params(cfg) + d * v)
    flops += layers * 4 * h * dh * n
    nbytes = _weight_bytes(cfg) + kv_bytes_per_token(cfg) * n
    return float(flops), float(nbytes)


def prefill_cost(cfg: Dict, t: int) -> Tuple[float, float]:
    """(flops, bytes) of the prefill of one ``t``-token prompt that ends
    in the logits of its last position: causal attention over
    ``t (t + 1) / 2`` pairs, one weight read, the write of ``t`` positions
    of keys and values."""
    d, h, kv, dh, _, layers, v = _dims(cfg)
    flops = 2 * t * layers * layer_matmul_params(cfg) + 2 * d * v
    flops += layers * 4 * h * dh * t * (t + 1) // 2
    nbytes = _weight_bytes(cfg) + kv_bytes_per_token(cfg) * t
    return float(flops), float(nbytes)


def bucket_histogram_cost(n_keys: int, n_buckets: int) -> Tuple[float, float]:
    """(ops, bytes) of counting ``n_keys`` int32 keys into ``n_buckets``
    int32 counts: one add per key, the keys read once, the counts
    written once."""
    return float(n_keys), float(4 * n_keys + 4 * n_buckets)


def roofline_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The least time the chip could take: compute or memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
