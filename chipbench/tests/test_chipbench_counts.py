"""Operation and byte counts, and the table of peaks."""

import math

import jax
import pytest

from chipbench import counts, harness
from chipbench.peaks import peaks

QWEN = harness.load_json(harness.HERE / "configs" / "qwen2_5_3b.json")
TINY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 4, "intermediate_size": 16, "num_hidden_layers": 1,
        "vocab_size": 10, "torch_dtype": "bfloat16", "tie_word_embeddings": True}


def test_parameter_count_matches_the_program_shapes():
    from repro.models import model_defs
    from repro.models.param import ParamDef

    from chipbench.configs.qwen2_5_3b_program import program_config

    defs = model_defs(program_config(QWEN))
    leaves = jax.tree_util.tree_leaves(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    total = sum(math.prod(pd.shape) for pd in leaves)
    tied_head = QWEN["vocab_size"] * QWEN["hidden_size"]
    assert counts.param_count(QWEN) == total - tied_head
    assert 3.0e9 < counts.param_count(QWEN) < 3.2e9  # "3B"


def test_decode_and_prefill_costs_match_a_hand_worked_case():
    # one layer: q 64 + k 32 + v 32 + o 64 + mlp 384 = 576 matmul weights;
    # + biases 16 + norms 16; head 10 x 8; final norm 8 -> 696 x 2 bytes
    assert counts.layer_matmul_params(TINY) == 576
    assert counts.kv_bytes_per_token(TINY) == 16
    flops, nbytes = counts.decode_cost(TINY, 3)
    assert nbytes == 1392 + 3 * 16
    assert flops == 2 * (576 + 80) + 4 * 2 * 4 * 3
    flops, nbytes = counts.prefill_cost(TINY, 3)
    assert nbytes == 1392 + 3 * 16
    assert flops == 2 * 3 * 576 + 2 * 80 + 4 * 2 * 4 * 6


def test_published_decode_is_bound_by_its_weight_read():
    flops, nbytes = counts.decode_cost(QWEN, 1024)
    v5e = peaks("TPU v5 lite")
    assert counts.roofline_seconds(flops, nbytes, v5e) == nbytes / 819e9
    assert 6.1e9 < nbytes < 6.3e9


def test_bucket_histogram_cost():
    assert counts.bucket_histogram_cost(1000, 4) == (1000.0, 4016.0)


def test_unknown_device_is_an_error():
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks("cpu")
