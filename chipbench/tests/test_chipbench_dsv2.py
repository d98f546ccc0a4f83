"""DeepSeek-V2-Lite as one chip's expert-parallel share, at smoke size on
the CPU: the served path agrees with the plain reference on logits, the
cell proves correct and its fp8 control does not, and the counts match
a hand-worked case."""

from __future__ import annotations

import numpy as np
import pytest

from chipbench import counts_mla_moe, harness
from chipbench.tests import smoke

CELL = "deepseek_v2_lite_ep4.chat_steady_dsv2"
CONFIG = harness.load_json(harness.HERE / "configs" / "deepseek_v2_lite_ep4.json")


def dsv2_cell() -> harness.Cell:
    """The real cell with every width cut down: 2 of 8 routed experts held
    (experts 2-3), top-2, one shared expert, three layers."""
    cell = harness.load_cell(CELL)
    cell.config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=2, num_experts_per_tok=2, n_shared_experts=1,
        num_hidden_layers=3, vocab_size=256)
    cell.config["published"] = {"n_routed_experts": 8}
    cell.config["held_experts"] = {"first": 2, "count": 2}
    cell.config["deployment"].update(prompt_len=16, max_tokens=8)
    mix = cell.traffic
    mix["prompt_tokens"] = {"lengths": [8, 16], "weights": [0.5, 0.5]}
    mix["output_tokens"] = {"median": 4, "sigma": 0.5, "min": 2, "max": 8}
    mix["rate"] = 3.0
    cell.workload["check"].update(served_tokens=24, max_logit_gap=0.01)
    return cell


def _pool(cfg, seed, prompt_len=16, max_tokens=8):
    from repro.api import ClusterConfig, MarvelClient, ServingConfig, TierSpec

    from chipbench.configs import deepseek_v2_lite_ep4_program as prog

    weights = prog.draw_weights(cfg, seed)
    client = MarvelClient(ClusterConfig(
        name="dsv2", tiers=(TierSpec("dram"),), invokers=1,
        serving=ServingConfig(lossless=True, block_tokens=4)))
    pool = client.serving(prog.program_params(weights), prog.program_config(cfg),
                          prompt_len=prompt_len, max_tokens=max_tokens)
    return weights, client, pool


def test_served_logits_match_the_reference():
    """Prefill, then decode through ServingPool and the pager: the
    prefill's pick and every decode step's logits against the reference's
    full forward pass over the same tokens."""
    from chipbench.configs import deepseek_v2_lite_ep4_ref as ref

    cfg = dsv2_cell().config
    weights, client, pool = _pool(cfg, 7)
    dec = pool.decoder
    picks, logits = [], []
    prefill, decode = dec._prefill, dec._decode

    def capture_prefill(*args):
        out = prefill(*args)
        picks.append(int(np.asarray(out[0])[0, 0]))
        return out

    def capture_decode(*args):
        out = decode(*args)
        logits.append(np.asarray(out[0], np.float32)[0])
        return out

    dec._prefill, dec._decode = capture_prefill, capture_decode
    plen = 12
    try:
        prompt = np.random.default_rng(3).integers(0, 256, (1, plen), np.int32)
        served = [int(np.asarray(pool.start("c", prompt).result())[0, 0])]
        for _ in range(6):
            served.append(int(np.asarray(pool.step("c").result())[0, 0]))
    finally:
        client.close()
    seq = np.concatenate([prompt[0], picks, served[:-1]]).astype(np.int32)
    want = np.asarray(ref.logits(weights, cfg, seq,
                                 np.arange(plen - 1, plen + len(served))))
    got = np.stack(logits)
    assert picks[0] == int(np.argmax(want[0]))
    gap = np.abs(got - want[1:]).max()
    assert gap < 0.05, gap
    assert np.array_equal(got.argmax(-1), np.asarray(served))


@pytest.mark.parametrize("seed", [11, 12])
def test_cell_is_correct_and_its_fp8_control_is_not(seed):
    # At this size sound runs read gaps of 0-0.0014 and the fp8 control
    # 0.044-0.060 (seeds 11-13, every served token compared)
    cell = dsv2_cell()
    cell.workload["check"]["served_tokens"] = 96
    r = smoke.run(cell, seconds=6.0, seed=seed, control="fp8")
    assert r["correct"], r["checks"]
    assert r["control_correct"] == {"control:fp8": False}, r["readings"]
    assert r["readings"]["control:fp8"] >= 3 * r["readings"]["program"]


def test_traced_run_reads_the_latent_write_and_the_held_routes():
    cell = dsv2_cell()
    r = smoke.run(cell, trace=True)
    assert r["correct"], r["checks"]
    got = r["metrics"]
    # three layers in two pager layers (layer 0, the stacked MoE layers):
    # a token writes one block of each, of 16 slots or the 8-slot tail of
    # the 24; the whole latent would be 3 x 24 slots
    width = (cell.config["kv_lora_rank"] + cell.config["qk_rope_head_dim"]) * 2
    assert 3 * 8 * width / 1e3 < got["pager_write_kb_per_token.serve"]["value"] \
        < 3 * 16 * width / 1e3 + 1.0 < 3 * 24 * width / 1e3
    # 2 MoE layers x top-2, on 2 of 8 experts: a token's slots on held ones
    assert 0 <= got["moe_held_routes_per_token.decode"]["value"] <= 4
    # no chip: no device plane and no peaks
    assert "mla_moe_decode_step_roofline" not in got


def test_readers_give_nothing_without_held_routes():
    from types import SimpleNamespace

    from chipbench import decode_attrs

    ctx = SimpleNamespace(spans=SimpleNamespace(intervals={}))
    assert decode_attrs.decode_steps(ctx) is None


TINY = {"hidden_size": 8, "num_attention_heads": 2, "kv_lora_rank": 4,
        "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 2,
        "intermediate_size": 16, "moe_intermediate_size": 4,
        "n_routed_experts": 2, "published": {"n_routed_experts": 8},
        "num_experts_per_tok": 2, "n_shared_experts": 1,
        "first_k_dense_replace": 1, "num_hidden_layers": 2, "vocab_size": 10,
        "torch_dtype": "bfloat16"}


def test_counts_match_a_hand_worked_case():
    c = counts_mla_moe
    # q 8x2x4 + latent and rope key 8x6 + up-projections 4x2x4 + out 2x2x8
    assert c.attn_params(TINY) == 176
    assert c.expert_params(TINY) == 96
    assert c.latent_bytes_per_token(TINY) == 24
    # fixed bf16 weights: 2 x (176 + kv_norm 4 + norms 16) + dense 384 +
    # shared 96 + final norm 8 + head 80 = 960; router 8 x 8 in float32
    flops, nbytes = c.decode_cost(TINY, 3, 1)
    assert nbytes == (960 + 96) * 2 + 64 * 4 + 24 * 3
    assert flops == 2 * (2 * 176 + 384 + 96 + 64 + 96) + 2 * 80 + 2 * 2 * 2 * 3 * 10
    flops, nbytes = c.prefill_cost(TINY, 3)
    assert nbytes == (960 + 2 * 96) * 2 + 64 * 4 + 24 * 3
    # 1.5 expected held slots; causal pairs 6 x (2 + 2 + 2) per head
    assert flops == 3 * 2 * (2 * 176 + 384 + 96 + 64) + 1.5 * 2 * 96 + 160 + 288
    assert c.weight_bytes(TINY) == (960 + 192 + 80) * 2 + 256


def test_weight_bytes_match_the_drawn_weights():
    import jax

    from chipbench.configs import deepseek_v2_lite_ep4_program as prog

    drawer = prog._drawer(prog._leaf_specs(CONFIG), CONFIG["torch_dtype"])
    shapes = jax.eval_shape(drawer, jax.ShapeDtypeStruct((2,), np.uint32))
    drawn = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                for s in jax.tree_util.tree_leaves(shapes))
    assert counts_mla_moe.weight_bytes(CONFIG) == drawn == 9_827_507_200
