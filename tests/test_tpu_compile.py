"""Compile the main path's kernels and the full-width decode step for a
described TPU v5e chip — no chip attached, nothing runs.

What interpret mode cannot show, the chip's compiler does: Mosaic refuses
layouts the interpreter happily executes (the old ``bucket_histogram``
one-hot panel needed a lane-to-sublane relayout and never compiled), and
XLA refuses a program that does not fit the device.  The topology is
described inside a fixture — never at import — so every pytest worker
collects the same tests and only the one running this file loads the
TPU library.  The persistent compilation cache is off around these
compiles: an entry written here cannot be read back without a chip.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.bucket_histogram import bucket_histogram
from repro.models import abstract_params, decode_step, init_cache, model_defs

#: HBM of one TPU v5e chip
V5E_HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n_buckets", [4, 8, 128, 256])
@pytest.mark.parametrize("n", [5, 12345, 1 << 20])
def test_bucket_histogram_compiles_for_v5e(one_chip, n, n_buckets):
    keys = _shape((n,), jnp.int32, one_chip)
    compiled = jax.jit(lambda k: bucket_histogram(k, n_buckets)).lower(
        keys
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_qwen_widths(one_chip):
    cfg = get_config("qwen2.5-3b")
    B, T = 1, 2048
    q = _shape((B, T, cfg.n_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _shape((B, T, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    compiled = jax.jit(
        lambda q, k, v: ops.flash_attention(q, k, v, interpret=False)
    ).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_compiles_at_qwen_widths(one_chip):
    cfg = get_config("qwen2.5-3b")
    B, S = 4, 4096
    q = _shape((B, cfg.n_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _shape((B, S, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    lengths = _shape((B,), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda q, k, v, n: ops.decode_attention(q, k, v, n, interpret=False)
    ).lower(q, kv, kv, lengths).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen_decode_step_fits_one_v5e(one_chip):
    # The serving path's per-token program at published widths: 36 layers,
    # d_model 2048, vocab 151936, bf16 weights, a 160-slot cache.
    cfg = get_config("qwen2.5-3b")
    on_chip = lambda s: _shape(s.shape, s.dtype, one_chip)  # noqa: E731
    params = jax.tree.map(on_chip, abstract_params(model_defs(cfg)))
    cache = jax.tree.map(on_chip, jax.eval_shape(lambda: init_cache(cfg, 1, 160)))
    tok = _shape((1, 1), jnp.int32, one_chip)
    t = _shape((), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda p, tok, c, t: decode_step(p, cfg, tok, c, t)
    ).lower(params, tok, cache, t).compile()
    args = compiled.memory_analysis().argument_size_in_bytes
    assert 6 << 30 < args < V5E_HBM_BYTES


def test_deepseek_expert_share_fits_one_v5e(one_chip):
    # DeepSeek-V2-Lite as one chip's EP=4 share (16 of 64 routed experts a
    # layer) at published widths: the paged decode step, with its held
    # routes, and a prefill whose dropless expert layer is a ragged dot
    from dataclasses import replace

    from repro.models import ShapeConfig, forward

    full = get_config("deepseek-v2-lite-16b")
    cfg = replace(full, moe=replace(full.moe, n_held=16))
    on_chip = lambda s: _shape(s.shape, s.dtype, one_chip)  # noqa: E731
    params = jax.tree.map(on_chip, abstract_params(model_defs(cfg)))
    cache = jax.tree.map(on_chip, jax.eval_shape(lambda: init_cache(cfg, 1, 160)))
    tok = _shape((1, 1), jnp.int32, one_chip)
    t = _shape((), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda p, tok, c, t: decode_step(p, cfg, tok, c, t)
    ).lower(params, tok, cache, t).compile()
    args = compiled.memory_analysis().argument_size_in_bytes
    assert 9 << 30 < args < V5E_HBM_BYTES
    shape = ShapeConfig(name="serve", kind="prefill", seq_len=256,
                        global_batch=1, q_chunk=8, kv_chunk=8, remat="none")
    prompt = _shape((1, 256), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda p, x: forward(p, cfg, {"tokens": x}, shape, collect_cache=True,
                             cache_len=288)
    ).lower(params, prompt).compile()
    assert "ragged-dot" in compiled.as_text()
