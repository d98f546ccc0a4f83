"""DeepSeek-V2-Lite's mechanisms at a small size: YaRN rope and its
softmax scale against the published formula worked by hand, the dropless
held-expert MoE layer against a per-token oracle, and one chip's share
of the experts tied to the uncut layer."""

import math
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import init_params, reduced_for_smoke
from repro.models.config import BlockSpec, YarnConfig
from repro.models.layers import apply_rope, mlp_apply, yarn_freqs, yarn_mscale
from repro.models.mla import _scale
from repro.models.moe import moe_apply_dense, moe_apply_held
from repro.models.transformer import _ffn_defs

DSV2 = get_config("deepseek-v2-lite-16b")


def test_deepseek_config_routes_as_published():
    m = DSV2.moe
    assert (m.n_experts, m.top_k, m.n_shared, m.d_expert) == (64, 6, 2, 1408)
    assert m.normalize_top_k is False
    assert m.held == 64
    y = DSV2.rope_scaling
    assert (y.factor, y.original_max_position, y.beta_fast, y.beta_slow) == (
        40.0, 4096, 32.0, 1.0)
    assert y.mscale == y.mscale_all_dim == 0.707


def test_yarn_frequencies_match_the_published_formula():
    """dim 64, base 1e4, factor 40 over 4096 positions, betas 32 and 1:
    the correction range is floor(10.47) = 10 to ceil(22.51) = 23, so the
    first 10 frequencies are the plain ones, the last 9 the plain ones
    over 40, and the 13 between blend them on a linear ramp."""
    dim, theta = 64, 10000.0
    y = DSV2.rope_scaling
    got = np.asarray(yarn_freqs(dim, theta, y), np.float64)
    plain = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    low = dim * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(theta))
    high = dim * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(theta))
    assert (math.floor(low), math.ceil(high)) == (10, 23)
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    ramp = (np.arange(10, 23) - 10) / 13
    np.testing.assert_allclose(
        got[10:23], plain[10:23] * (1 - ramp) + plain[10:23] / 40 * ramp,
        rtol=1e-6)


def test_yarn_softmax_scale_and_rope_magnitude():
    # 0.1 * 0.707 * ln 40 + 1 = 1.26080378; squared 1.58963
    assert yarn_mscale(40, 0.707) == pytest.approx(1.26080378, rel=1e-6)
    assert _scale(DSV2) == pytest.approx(192 ** -0.5 * 1.26080378 ** 2, rel=1e-6)
    assert _scale(replace(DSV2, rope_scaling=None)) == pytest.approx(192 ** -0.5)
    # mscale equal to mscale_all_dim: cos and sin unscaled, so rope keeps norms
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 64))
    pos = jnp.arange(5)[None] * 1000
    r = apply_rope(x, pos, 1e4, scaling=DSV2.rope_scaling)
    np.testing.assert_allclose(np.linalg.norm(r, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    # with no scaling the plain rope is taken
    y = YarnConfig(factor=1.0, original_max_position=4096)
    np.testing.assert_allclose(apply_rope(x, pos, 1e4, scaling=y),
                               apply_rope(x, pos, 1e4), rtol=1e-6)


def _moe_cfg(n_held=None, first_held=0, capacity=1.25):
    cfg = reduced_for_smoke(DSV2)
    return replace(cfg, moe=replace(cfg.moe, first_held=first_held,
                                    n_held=n_held, capacity_factor=capacity))


def _params(cfg, seed=0):
    defs = _ffn_defs(BlockSpec(ffn="moe"), cfg)
    p = init_params(defs, jax.random.PRNGKey(seed))
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)


def _oracle(p, x, cfg, shared=True):
    """Per token: softmax over all experts, top-k, each held expert's
    output times its weight (no renormalization), plus the shared MLP."""
    m = cfg.moe
    xf = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = xf @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        for e in np.argsort(-probs[t])[:m.top_k]:
            local = e - m.first_held
            if not 0 <= local < m.held:
                continue
            g = xf[t] @ np.asarray(p["w_gate"][local], np.float64)
            u = xf[t] @ np.asarray(p["w_up"][local], np.float64)
            h = g / (1 + np.exp(-g)) * u
            out[t] += probs[t, e] * (h @ np.asarray(p["w_down"][local], np.float64))
    if shared and m.n_shared:
        out += np.asarray(mlp_apply(p["shared"], jnp.asarray(xf, jnp.float32),
                                    cfg.act), np.float64)
    return out.reshape(x.shape)


def test_held_layer_drops_no_token_where_capacity_packing_would():
    """A 96-token prompt routes 192 slots over 8 experts: capacity 1.25
    packs at most 30 a expert and drops the overflow; the held path
    computes every slot."""
    cfg = _moe_cfg()
    p = _params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 96, cfg.d_model))
    # skew the router so that one expert is over capacity
    p["router"] = p["router"].at[:, 3].add(0.5)
    want = _oracle(p, x, cfg)
    got, _, routes = moe_apply_held(p, x, cfg)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-4)
    assert int(routes) == 96 * cfg.moe.top_k
    packed, _ = moe_apply_dense(p, x, cfg)
    assert np.abs(np.asarray(packed) - want).max() > 1e-2  # it dropped


def test_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four chips of 2 experts each: the routed parts of every share,
    summed, plus the shared experts counted once, equal the uncut layer;
    each share's held routes add up to every routed slot."""
    full = _moe_cfg()
    p = _params(full, seed=2)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, full.d_model))
    uncut, _, _ = moe_apply_held(p, x, full)
    shared = mlp_apply(p["shared"], x, full.act)
    total, routes = shared, 0
    for chip in range(4):
        cfg = _moe_cfg(n_held=2, first_held=2 * chip)
        share = dict(p, **{k: p[k][2 * chip:2 * chip + 2]
                           for k in ("w_gate", "w_up", "w_down")})
        out, _, r = moe_apply_held(share, x, cfg)
        part = out - shared  # every chip computes the shared experts alike
        np.testing.assert_allclose(np.asarray(part), _oracle(share, x, cfg, False),
                                   atol=1e-4, rtol=1e-4)
        total, routes = total + part, routes + int(r)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=1e-5, rtol=1e-5)
    assert routes == 2 * 20 * full.moe.top_k


def test_routing_keeps_the_softmax_weights_without_renormalizing():
    cfg = _moe_cfg()
    p = _params(cfg, seed=4)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 4, cfg.d_model))
    got, _, _ = moe_apply_held(p, x, cfg)
    np.testing.assert_allclose(np.asarray(got), _oracle(p, x, cfg),
                               atol=1e-5, rtol=1e-5)
    renorm = replace(cfg, moe=replace(cfg.moe, normalize_top_k=True))
    other, _, _ = moe_apply_held(p, x, renorm)
    assert np.abs(np.asarray(other) - np.asarray(got)).max() > 1e-3


def test_a_share_of_the_experts_runs_on_a_one_device_expert_axis():
    from repro.models.moe import moe_apply

    cfg = _moe_cfg(n_held=2)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    # one device on the expert axis: the held path, no exchange
    out, _, _ = moe_apply(_params(cfg), jnp.ones((1, 2, cfg.d_model)), cfg, mesh)
    assert out.shape == (1, 2, cfg.d_model)


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "deepseek-v2-lite-16b"])
def test_decode_step_counts_held_routes_only_for_a_held_share(arch):
    """A dense model's decode step counts no routes (None); a layer that
    holds every expert counts all top-k slots of every MoE layer, and a
    share of them no more than that."""
    from repro.models import decode_step, init_cache, model_defs

    def routes(cfg):
        params = init_params(model_defs(cfg), jax.random.PRNGKey(6))
        return decode_step(params, cfg, jnp.ones((1, 1), jnp.int32),
                           init_cache(cfg, 1, 8), jnp.int32(0))[2]

    cfg = reduced_for_smoke(get_config(arch))
    if cfg.moe is None:
        assert routes(cfg) is None
        return
    every = sum(b.ffn == "moe" for b in cfg.all_blocks()) * cfg.moe.top_k
    assert int(routes(cfg)) == every > 0
    half = replace(cfg, moe=replace(cfg.moe, n_held=cfg.moe.n_experts // 2))
    assert 0 <= int(routes(half)) <= every
