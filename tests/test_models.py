"""Model layers + per-arch smoke + decode/forward equivalence."""

import math
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.models import (
    ShapeConfig,
    decode_step,
    forward,
    init_params,
    logits_fn,
    model_defs,
    reduced_for_smoke,
)
from repro.models.layers import (
    apply_rope,
    chunked_attention,
    chunked_ce_loss,
    decode_attention,
    rms_norm,
)

SMOKE_SHAPE = ShapeConfig(
    name="smoke", kind="train", seq_len=32, global_batch=2,
    q_chunk=16, kv_chunk=16, loss_chunk=16, remat="none",
)

_f32 = lambda t: jax.tree_util.tree_map(
    lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x, t
)


def _np_attn(q, k, v, causal=True, window=None, cap=None, scale=None):
    B, Tq, H, Dh = q.shape
    Tk, Kv = k.shape[1], k.shape[2]
    rep = H // Kv
    scale = scale or 1.0 / math.sqrt(Dh)
    kk = np.repeat(np.asarray(k, np.float32), rep, axis=2)
    vv = np.repeat(np.asarray(v, np.float32), rep, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q, np.float32), kk) * scale
    if cap:
        s = cap * np.tanh(s / cap)
    qp = np.arange(Tq)[:, None]
    kp = np.arange(Tk)[None, :]
    mask = np.ones((Tq, Tk), bool)
    if causal:
        mask &= qp >= kp
    if window:
        mask &= qp - kp < window
    s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, vv)


# -- layer oracles ---------------------------------------------------------

@pytest.mark.parametrize("causal,window,cap", [
    (True, None, None), (True, 64, None), (False, None, None),
    (True, None, 30.0),
])
def test_chunked_attention_oracle(rng, causal, window, cap):
    B, T, H, Kv, D = 2, 200, 8, 2, 32
    q = rng.standard_normal((B, T, H, D)).astype(np.float32)
    k = rng.standard_normal((B, T, Kv, D)).astype(np.float32)
    v = rng.standard_normal((B, T, Kv, D)).astype(np.float32)
    got = chunked_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        causal=causal, window=window, attn_softcap=cap,
        q_chunk=64, kv_chunk=48,
    )
    want = _np_attn(q, k, v, causal, window, cap)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


def test_decode_attention_is_last_row_of_full(rng):
    B, S, H, Kv, D = 2, 96, 8, 2, 32
    kc = rng.standard_normal((B, S, Kv, D)).astype(np.float32)
    vc = rng.standard_normal((B, S, Kv, D)).astype(np.float32)
    qd = rng.standard_normal((B, H, D)).astype(np.float32)
    L = np.array([50, 96], np.int32)
    got = decode_attention(
        jnp.asarray(qd), jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(L)
    )
    for b in range(B):
        w = _np_attn(
            qd[b].reshape(1, 1, H, D), kc[b : b + 1, : L[b]],
            vc[b : b + 1, : L[b]], causal=False,
        )
        np.testing.assert_allclose(np.asarray(got[b]), w[0, 0], atol=2e-5,
                                   rtol=2e-5)


def test_rope_relative_property(rng):
    x = rng.standard_normal((1, 5, 1, 16)).astype(np.float32)
    pos = jnp.arange(5)[None]
    r1 = apply_rope(jnp.asarray(x), pos)
    r2 = apply_rope(jnp.asarray(x), pos + 7)
    s1 = np.einsum("bthd,bshd->ts", np.asarray(r1), np.asarray(r1))
    s2 = np.einsum("bthd,bshd->ts", np.asarray(r2), np.asarray(r2))
    np.testing.assert_allclose(s1, s2, atol=1e-4)


def test_partial_rope_passthrough(rng):
    x = rng.standard_normal((1, 4, 2, 16)).astype(np.float32)
    out = apply_rope(jnp.asarray(x), jnp.arange(4)[None], dh_rot=8)
    np.testing.assert_array_equal(np.asarray(out)[..., 8:], x[..., 8:])


def test_chunked_ce_loss_oracle(rng):
    B, T, D, V = 2, 37, 16, 50
    x = rng.standard_normal((B, T, D)).astype(np.float32)
    U = rng.standard_normal((D, V)).astype(np.float32) * 0.1
    lbl = rng.integers(0, V, (B, T)).astype(np.int32)
    lbl[0, :5] = -100
    loss, n = chunked_ce_loss(
        jnp.asarray(x), jnp.asarray(U), jnp.asarray(lbl), t_chunk=16
    )
    logits = x @ U
    lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) + \
        logits.max(-1)
    ll = np.take_along_axis(logits, np.maximum(lbl, 0)[..., None], -1)[..., 0]
    valid = lbl >= 0
    np.testing.assert_allclose(
        float(loss), ((lse - ll) * valid).sum() / valid.sum(), rtol=1e-5
    )
    assert int(n) == valid.sum()


def test_rms_norm_scale_invariance(rng):
    x = rng.standard_normal((2, 8)).astype(np.float32)
    y1 = rms_norm(jnp.asarray(x), jnp.ones(8))
    y2 = rms_norm(jnp.asarray(x * 100.0), jnp.ones(8))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)


# -- per-arch smoke (reduced config, REAL forward + train grad) -------------

def _inputs_for(cfg, key, B, T):
    if cfg.frontend == "tokens":
        return {"tokens": jax.random.randint(key, (B, T), 0, cfg.vocab)}
    if cfg.frontend == "frames":
        return {"frames": jax.random.normal(key, (B, T, cfg.frame_dim),
                                            jnp.bfloat16)}
    return {
        "tokens": jax.random.randint(key, (B, T - cfg.n_patches), 0,
                                     cfg.vocab),
        "patches": jax.random.normal(key, (B, cfg.n_patches, cfg.d_model),
                                     jnp.bfloat16),
    }


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward_and_grad(arch):
    """One forward + one grad step per assigned architecture (reduced)."""
    cfg = reduced_for_smoke(get_config(arch))
    key = jax.random.PRNGKey(0)
    params = _f32(init_params(model_defs(cfg), key))
    B, T = 2, 32
    inputs = _inputs_for(cfg, key, B, T)
    h, aux = forward(params, cfg, inputs, SMOKE_SHAPE)
    assert h.shape == (B, T, cfg.d_model)
    logits = logits_fn(params, cfg, h)
    assert not bool(jnp.isnan(logits).any())
    labels = jax.random.randint(key, (B, T), 0, cfg.vocab)

    def loss_fn(p):
        hh, a = forward(p, cfg, inputs, SMOKE_SHAPE)
        loss, _ = chunked_ce_loss(hh, p["unembed"], labels, t_chunk=16)
        return loss + 0.01 * a

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    gnorm = sum(
        float(jnp.sum(jnp.square(g.astype(jnp.float32))))
        for g in jax.tree_util.tree_leaves(grads)
    )
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", [
    "qwen2.5-3b", "gemma2-9b", "deepseek-v2-lite-16b", "mamba2-2.7b",
    "recurrentgemma-9b", "gemma-2b",
])
def test_arch_decode_matches_forward(arch):
    """Token-by-token decode reproduces teacher-forced logits."""
    cfg = reduced_for_smoke(get_config(arch))
    if cfg.moe:
        cfg = replace(cfg, moe=replace(cfg.moe, capacity_factor=8.0))
    key = jax.random.PRNGKey(1)
    params = _f32(init_params(model_defs(cfg), key))
    B, T = 2, 24
    shape = replace(SMOKE_SHAPE, q_chunk=8, kv_chunk=8)
    toks = jax.random.randint(key, (B, T), 0, cfg.vocab)
    h, _ = forward(params, cfg, {"tokens": toks}, shape)
    full_logits = np.asarray(logits_fn(params, cfg, h))
    from repro.models import init_cache

    cache = _f32(init_cache(cfg, B, T, jnp.float32))
    step = jax.jit(lambda p, tok, c, t: decode_step(p, cfg, tok, c, t))
    errs = []
    for t in range(T):
        lg, cache, _ = step(params, toks[:, t : t + 1], cache, jnp.int32(t))
        errs.append(np.abs(np.asarray(lg) - full_logits[:, t]).max())
    assert max(errs) < 2e-2, f"{arch}: max decode divergence {max(errs)}"


def test_prefill_cache_matches_decode_path():
    """prefill(T) then decode == decode from scratch for T+k tokens."""
    cfg = reduced_for_smoke(get_config("gemma2-9b"))
    key = jax.random.PRNGKey(2)
    params = _f32(init_params(model_defs(cfg), key))
    B, Tp, Tg = 2, 16, 4
    total = Tp + Tg
    shape = replace(SMOKE_SHAPE, q_chunk=8, kv_chunk=8)
    toks = jax.random.randint(key, (B, total), 0, cfg.vocab)
    # path A: full decode from scratch
    from repro.models import init_cache

    cache = _f32(init_cache(cfg, B, total, jnp.float32))
    step = jax.jit(lambda p, tok, c, t: decode_step(p, cfg, tok, c, t))
    logits_a = None
    for t in range(total):
        logits_a, cache, _ = step(params, toks[:, t : t + 1], cache,
                               jnp.int32(t))
    # path B: prefill Tp, decode the rest
    h, _aux, cache_b = forward(
        params, cfg, {"tokens": toks[:, :Tp]}, shape,
        collect_cache=True, cache_len=total,
    )
    cache_b = _f32(cache_b)
    logits_b = logits_fn(params, cfg, h[:, -1])
    for i in range(Tg):
        logits_b, cache_b, _ = step(
            params, toks[:, Tp + i : Tp + i + 1], cache_b,
            jnp.int32(Tp + i),
        )
    np.testing.assert_allclose(
        np.asarray(logits_a), np.asarray(logits_b), atol=2e-2, rtol=1e-2
    )


def test_param_counts_match_public_sizes():
    """approx_params within ~25% of each model's nominal size."""
    expected = {
        "deepseek-v2-lite-16b": 15.7e9,
        "dbrx-132b": 132e9,
        "mamba2-2.7b": 2.7e9,
        "qwen2.5-3b": 3.1e9,
        "gemma-2b": 2.5e9,
        "gemma2-9b": 9.2e9,
        "qwen1.5-32b": 32e9,
        "recurrentgemma-9b": 9e9,
    }
    for arch, want in expected.items():
        got = get_config(arch).approx_params()
        assert abs(got - want) / want < 0.30, (arch, got, want)


def test_moe_dense_path_routes_topk(rng):
    from repro.models.moe import moe_apply_dense

    cfg = reduced_for_smoke(get_config("dbrx-132b"))
    cfg = replace(cfg, moe=replace(cfg.moe, capacity_factor=8.0))
    from repro.models.transformer import _ffn_defs
    from repro.models.config import BlockSpec

    defs = _ffn_defs(BlockSpec(ffn="moe"), cfg)
    params = _f32(init_params(defs, jax.random.PRNGKey(0)))
    x = jnp.asarray(rng.standard_normal((2, 8, cfg.d_model)).astype(np.float32))
    out, aux = moe_apply_dense(params, x, cfg)
    assert out.shape == x.shape
    assert float(aux) > 0  # load-balance loss computed
    assert not bool(jnp.isnan(out).any())


def test_int8_kv_cache_decode_close_to_bf16():
    """int8 KV cache (quant_cache.py): small logit perturbation, same
    argmax path on a tiny model — the compression tier for decode state."""
    cfg = reduced_for_smoke(get_config("qwen1.5-32b"))
    key = jax.random.PRNGKey(0)
    params = _f32(init_params(model_defs(cfg), key))
    from repro.models import init_cache

    B, T = 2, 12
    toks = jax.random.randint(key, (B, T), 0, cfg.vocab)
    c_ref = _f32(init_cache(cfg, B, T, jnp.float32))
    c_q = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x,
        init_cache(cfg, B, T, jnp.float32, quant_attn=True),
    )
    errs, agree = [], 0
    for t in range(T):
        l1, c_ref, _ = decode_step(params, cfg, toks[:, t : t + 1], c_ref,
                                jnp.int32(t))
        l2, c_q, _ = decode_step(params, cfg, toks[:, t : t + 1], c_q,
                              jnp.int32(t))
        errs.append(np.abs(np.asarray(l1) - np.asarray(l2)).max())
        agree += int(
            np.array_equal(np.argmax(l1, -1), np.argmax(l2, -1))
        )
    assert max(errs) < 0.5, max(errs)  # bounded quantization noise
    assert agree >= T - 2  # argmax path essentially unchanged


def test_quant_cache_roundtrip_accuracy(rng):
    from repro.models.quant_cache import quantize_kv

    x = jnp.asarray(rng.standard_normal((2, 7, 3, 32)).astype(np.float32))
    q, s = quantize_kv(x)
    deq = q.astype(np.float32) * np.asarray(s, np.float32)[..., None]
    rel = np.abs(deq - np.asarray(x)).max() / np.abs(np.asarray(x)).max()
    assert rel < 0.01  # 1/127 per-head relative error bound
