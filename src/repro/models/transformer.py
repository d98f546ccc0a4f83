"""Model assembly: frontend → prelude → scanned pattern body → postlude →
final norm → unembed.  Covers all 10 assigned architectures via
:class:`ModelConfig` (see configs/).

Scan-over-layers: the repeating block pattern is stacked along a leading
``n_periods`` dim and driven by ``lax.scan`` — HLO size stays O(pattern),
which is what makes 512-device dry-run compiles fast.  Remat wraps the
scanned period body.  Decode threads per-layer caches through the same scan.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.models import attention, mla, moe, rglru, ssm
from repro.models.config import BlockSpec, ModelConfig, ShapeConfig
from repro.models.ctx import ShardCtx
from repro.models.layers import layer_norm, mlp_apply, mlp_defs, rms_norm, softcap
from repro.models.param import FSDP, TP, ParamDef, stack_defs

__all__ = ["ShardCtx", "model_defs", "forward", "decode_step", "init_cache"]


# -- defs ---------------------------------------------------------------

def _norm_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    if cfg.norm == "ln":
        return {
            "scale": ParamDef((cfg.d_model,), (None,), init_value=1.0),
            "bias": ParamDef((cfg.d_model,), (None,), init_scale=0.0),
        }
    init = 0.0 if cfg.rms_plus_one else 1.0
    return {"scale": ParamDef((cfg.d_model,), (None,), init_value=init)}


def _norm_apply(p, x, cfg: ModelConfig):
    if cfg.norm == "ln":
        return layer_norm(x, p["scale"], p["bias"])
    return rms_norm(x, p["scale"], plus_one=cfg.rms_plus_one)


def _mixer_defs(blk: BlockSpec, cfg: ModelConfig) -> Dict[str, ParamDef]:
    if blk.mixer in ("attn", "local"):
        return attention.attn_defs(cfg)
    if blk.mixer == "mla":
        return mla.mla_defs(cfg)
    if blk.mixer == "ssm":
        return ssm.ssm_defs(cfg)
    if blk.mixer == "rglru":
        return rglru.rglru_defs(cfg)
    raise ValueError(blk.mixer)


def _ffn_defs(blk: BlockSpec, cfg: ModelConfig) -> Optional[Dict[str, ParamDef]]:
    if blk.ffn == "dense":
        # encoder-style plain MLP when act endswith _plain
        if cfg.act == "gelu_plain":
            return mlp_defs(cfg.d_model, cfg.d_ff, gated=False)
        return mlp_defs(cfg.d_model, cfg.d_ff, gated=True)
    if blk.ffn == "moe":
        return moe.moe_defs(cfg)
    return None


def _block_defs(blk: BlockSpec, cfg: ModelConfig) -> Dict[str, Any]:
    defs: Dict[str, Any] = {
        "norm1": _norm_defs(cfg),
        "mixer": _mixer_defs(blk, cfg),
    }
    if blk.ffn != "none":
        defs["norm2"] = _norm_defs(cfg)
        defs["ffn"] = _ffn_defs(blk, cfg)
    if cfg.post_block_norm:
        defs["post1"] = _norm_defs(cfg)
        if blk.ffn != "none":
            defs["post2"] = _norm_defs(cfg)
    return defs


def model_defs(cfg: ModelConfig) -> Dict[str, Any]:
    D, V = cfg.d_model, cfg.vocab
    defs: Dict[str, Any] = {}
    if cfg.frontend in ("tokens", "tokens+patches"):
        # D sharded over FSDP: the token gather stays local per D-shard and
        # GSPMD reshards (B,T,D/16)->(B/16,T,D) with an all-to-all, 16x
        # cheaper than the psum a vocab-sharded table would need.
        defs["embed"] = ParamDef((V, D), (None, FSDP), init_scale=0.02)
    if cfg.frontend == "frames":
        fd = cfg.frame_dim or D
        defs["frame_proj"] = {
            "w": ParamDef((fd, D), (None, FSDP)),
            "b": ParamDef((D,), (None,), init_scale=0.0),
        }
    defs["prelude"] = [ _block_defs(b, cfg) for b in cfg.prelude ]
    defs["body"] = [
        stack_defs(_block_defs(b, cfg), cfg.n_periods) for b in cfg.pattern
    ]
    defs["postlude"] = [ _block_defs(b, cfg) for b in cfg.postlude ]
    defs["final_norm"] = _norm_defs(cfg)
    # V over TP: logits shard the vocab dim with no sharded contraction;
    # logsumexp cross-shard reductions are (B,T)-sized, not (B,T,V).
    defs["unembed"] = ParamDef((D, V), (None, TP))
    return defs


# -- apply ---------------------------------------------------------------

def _mixer_apply(p, x, blk: BlockSpec, cfg: ModelConfig, shape: ShapeConfig,
                 ctx: ShardCtx, collect_cache: bool = False, cache_len=None):
    if blk.mixer in ("attn", "local"):
        out = attention.attn_apply(
            p, x, cfg,
            window=blk.window if blk.mixer == "local" else None,
            q_chunk=shape.q_chunk, kv_chunk=shape.kv_chunk,
            collect_cache=collect_cache, cache_len=cache_len, ctx=ctx,
        )
    elif blk.mixer == "mla":
        out = mla.mla_apply(p, x, cfg, q_chunk=shape.q_chunk,
                            kv_chunk=shape.kv_chunk,
                            collect_cache=collect_cache, cache_len=cache_len,
                            ctx=ctx)
    elif blk.mixer == "ssm":
        out = ssm.ssm_apply(p, x, cfg, collect_cache=collect_cache, ctx=ctx)
    elif blk.mixer == "rglru":
        out = rglru.rglru_apply(p, x, cfg, collect_cache=collect_cache, ctx=ctx)
    else:
        raise ValueError(blk.mixer)
    return out if collect_cache else (out, None)


def _ffn_apply(p, x, blk: BlockSpec, cfg: ModelConfig, ctx: ShardCtx):
    """(output, aux loss, routed slots on held experts or None)."""
    if blk.ffn == "dense":
        act = "gelu" if cfg.act == "gelu_plain" else cfg.act
        return mlp_apply(p, x, act), jnp.zeros((), jnp.float32), None
    if blk.ffn == "moe":
        return moe.moe_apply(p, x, cfg, ctx.mesh, ctx.dp_axes, ctx.tp_axis,
                             zero1=getattr(ctx, 'zero1', False))
    raise ValueError(blk.ffn)


def _block_apply(p, x, blk: BlockSpec, cfg: ModelConfig, shape: ShapeConfig,
                 ctx: ShardCtx, collect_cache: bool = False, cache_len=None):
    h, cache = _mixer_apply(
        p["mixer"], _norm_apply(p["norm1"], x, cfg), blk, cfg, shape, ctx,
        collect_cache, cache_len,
    )
    h = jax.ad_checkpoint.checkpoint_name(h, "block_out")
    if cfg.post_block_norm:
        h = _norm_apply(p["post1"], h, cfg)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if blk.ffn != "none":
        h, aux, _ = _ffn_apply(p["ffn"], _norm_apply(p["norm2"], x, cfg), blk,
                               cfg, ctx)
        h = jax.ad_checkpoint.checkpoint_name(h, "block_out")
        if cfg.post_block_norm:
            h = _norm_apply(p["post2"], h, cfg)
        x = x + h
    return x, aux, cache


def _frontend(params, cfg: ModelConfig, inputs: Dict[str, jax.Array]):
    if cfg.frontend == "tokens":
        x = jnp.take(params["embed"], inputs["tokens"], axis=0)
    elif cfg.frontend == "frames":
        fp = params["frame_proj"]
        x = inputs["frames"] @ fp["w"] + fp["b"]
    elif cfg.frontend == "tokens+patches":
        tok = jnp.take(params["embed"], inputs["tokens"], axis=0)
        x = jnp.concatenate([inputs["patches"].astype(tok.dtype), tok], axis=1)
    else:
        raise ValueError(cfg.frontend)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    if policy == "save_block_out":
        # keep the post-collective mixer/FFN outputs: the backward pass
        # then reuses them instead of re-running the forward psums
        # (remat recompute was ~1/3 of train collective bytes — §Perf)
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.save_only_these_names("block_out"),
        )
    return jax.checkpoint(fn)


def forward(
    params: Dict[str, Any],
    cfg: ModelConfig,
    inputs: Dict[str, jax.Array],
    shape: ShapeConfig,
    ctx: Optional[ShardCtx] = None,
    collect_cache: bool = False,
    cache_len: Optional[int] = None,
):
    """Full-sequence forward. Returns (hidden (B,T,D), moe aux loss) or,
    with ``collect_cache`` (prefill), (hidden, aux, cache pytree).
    ``cache_len`` reserves decode headroom in the collected caches."""
    ctx = ctx or ShardCtx()
    x = _frontend(params, cfg, inputs)
    aux = jnp.zeros((), jnp.float32)
    caches = {"prelude": [], "body": [], "postlude": []}

    for p, blk in zip(params["prelude"], cfg.prelude):
        x, a, c = _block_apply(p, x, blk, cfg, shape, ctx, collect_cache,
                               cache_len)
        aux = aux + a
        caches["prelude"].append(c)

    if cfg.n_periods > 0:
        def period(carry, slot_params):
            xx, acc = carry
            slot_caches = []
            for sp, blk in zip(slot_params, cfg.pattern):
                xx, a, c = _block_apply(sp, xx, blk, cfg, shape, ctx,
                                        collect_cache, cache_len)
                acc = acc + a
                slot_caches.append(c)
            ys = tuple(slot_caches) if collect_cache else None
            return (xx, acc), ys

        period_fn = _remat(period, shape.remat)
        (x, aux), body_caches = jax.lax.scan(
            period_fn, (x, aux), tuple(params["body"])
        )
        if collect_cache:
            caches["body"] = list(body_caches)

    for p, blk in zip(params["postlude"], cfg.postlude):
        x, a, c = _block_apply(p, x, blk, cfg, shape, ctx, collect_cache,
                               cache_len)
        aux = aux + a
        caches["postlude"].append(c)

    x = _norm_apply(params["final_norm"], x, cfg)
    if collect_cache:
        return x, aux, caches
    return x, aux


def logits_fn(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Final logits (fp32, softcapped). x: (..., D)."""
    logits = (x @ params["unembed"]).astype(jnp.float32)
    return softcap(logits, cfg.final_softcap)


# -- decode ---------------------------------------------------------------

def _mixer_cache(blk: BlockSpec, cfg: ModelConfig, batch: int, seq_len: int,
                 dtype, quant_attn: bool = False):
    if blk.mixer in ("attn", "local"):
        window = blk.window if blk.mixer == "local" else None
        if quant_attn:
            from repro.models.quant_cache import init_quant_cache
            return init_quant_cache(cfg, batch, seq_len, window)
        return attention.init_attn_cache(cfg, batch, seq_len, window, dtype)
    if blk.mixer == "mla":
        return mla.init_mla_cache(cfg, batch, seq_len, dtype)
    if blk.mixer == "ssm":
        return ssm.init_ssm_cache(cfg, batch, dtype)
    if blk.mixer == "rglru":
        return rglru.init_rglru_cache(cfg, batch, dtype)
    raise ValueError(blk.mixer)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=jnp.bfloat16,
               quant_attn: bool = False):
    """Decode cache pytree; ``quant_attn`` uses int8 attention caches."""
    stack = lambda c: jax.tree_util.tree_map(
        lambda leaf: jnp.broadcast_to(
            leaf[None], (cfg.n_periods,) + leaf.shape
        ).copy() if cfg.n_periods else leaf,
        c,
    )
    mk = lambda b: _mixer_cache(b, cfg, batch, seq_len, dtype, quant_attn)
    return {
        "prelude": [mk(b) for b in cfg.prelude],
        "body": [stack(mk(b)) for b in cfg.pattern],
        "postlude": [mk(b) for b in cfg.postlude],
    }


def _block_decode(p, x, cache, t, blk: BlockSpec, cfg: ModelConfig,
                  ctx: ShardCtx):
    xn = _norm_apply(p["norm1"], x, cfg)
    if blk.mixer in ("attn", "local"):
        h, new_cache = attention.attn_decode(
            p["mixer"], xn, cache, t, cfg,
            window=blk.window if blk.mixer == "local" else None, ctx=ctx,
        )
    elif blk.mixer == "mla":
        h, new_cache = mla.mla_decode(p["mixer"], xn, cache, t, cfg, ctx=ctx)
    elif blk.mixer == "ssm":
        h, new_cache = ssm.ssm_decode(p["mixer"], xn, cache, cfg, ctx=ctx)
    elif blk.mixer == "rglru":
        h, new_cache = rglru.rglru_decode(p["mixer"], xn, cache, cfg, ctx=ctx)
    else:
        raise ValueError(blk.mixer)
    if cfg.post_block_norm:
        h = _norm_apply(p["post1"], h, cfg)
    x = x + h
    routes = None
    if blk.ffn != "none":
        h, _, routes = _ffn_apply(p["ffn"], _norm_apply(p["norm2"], x, cfg),
                                  blk, cfg, ctx)
        if cfg.post_block_norm:
            h = _norm_apply(p["post2"], h, cfg)
        x = x + h
    return x, new_cache, routes


def decode_step(
    params: Dict[str, Any],
    cfg: ModelConfig,
    tokens: jax.Array,  # (B, 1) int32 current token ids
    cache: Dict[str, Any],
    t: jax.Array,  # scalar int32 position of `tokens`
    ctx: Optional[ShardCtx] = None,
):
    """One-token decode. Returns (logits (B, V) fp32, new cache, held
    routes): the number of the token's routed slots, summed over layers,
    that fell on held experts, or None where no layer routes to a share of
    its experts on one device (a dense model, or the sharded expert
    paths)."""
    ctx = ctx or ShardCtx()
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    routes = None

    def add(acc, r):
        return r if acc is None else acc if r is None else acc + r

    new_prelude = []
    for p, c, blk in zip(params["prelude"], cache["prelude"], cfg.prelude):
        x, nc, r = _block_decode(p, x, c, t, blk, cfg, ctx)
        routes = add(routes, r)
        new_prelude.append(nc)

    new_body = cache["body"]
    if cfg.n_periods > 0:
        def period(xx, scanned):
            slot_params, slot_caches = scanned
            new_caches, acc = [], None
            for sp, sc, blk in zip(slot_params, slot_caches, cfg.pattern):
                xx, nc, r = _block_decode(sp, xx, sc, t, blk, cfg, ctx)
                acc = add(acc, r)
                new_caches.append(nc)
            return xx, (tuple(new_caches), acc)

        # each period's count is a scanned output (None adds none), so the
        # carry holds x alone
        x, (new_body, body_routes) = jax.lax.scan(
            period, x, (tuple(params["body"]), tuple(cache["body"]))
        )
        new_body = list(new_body)
        if body_routes is not None:
            routes = add(routes, jnp.sum(body_routes))

    new_postlude = []
    for p, c, blk in zip(params["postlude"], cache["postlude"], cfg.postlude):
        x, nc, r = _block_decode(p, x, c, t, blk, cfg, ctx)
        routes = add(routes, r)
        new_postlude.append(nc)

    x = _norm_apply(params["final_norm"], x, cfg)
    logits = logits_fn(params, cfg, x[:, 0])
    return logits, {
        "prelude": new_prelude,
        "body": new_body,
        "postlude": new_postlude,
    }, routes
