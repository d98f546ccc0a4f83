"""Step builders: train / prefill / decode, with full sharding metadata.

Each ``make_*_step`` returns a :class:`StepBundle`: the pure step function,
its in/out shardings, and ShapeDtypeStruct argument stand-ins — exactly
what the dry-run needs to ``jit(...).lower(...).compile()`` and what the
real launchers feed with live arrays.

Training step layout (DESIGN.md §4):
  * params fp32 masters, 2D-sharded (FSDP×TP); cast to bf16 inside the step,
  * grad accumulation over ``shape.microbatches`` via ``lax.scan`` (this is
    also the compute/comm overlap point: per-microbatch reduce-scatters
    can overlap the next microbatch's compute under XLA latency hiding),
  * optional int8 gradient compression with error feedback on the DP axis,
  * AdamW with ZeRO-sharded fp32 moments.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import (
    ModelConfig,
    ShapeConfig,
    ShardCtx,
    abstract_params,
    decode_step,
    forward,
    init_cache,
    model_defs,
    param_specs,
)
from repro.models.layers import chunked_ce_loss
from repro.models.param import tree_map_defs
from repro.optim.adamw import AdamWConfig, OptState, adamw_update
from repro.optim.compression import EFState, compress_decompress
from repro.parallel.sharding import (
    batch_entry,
    cache_pspecs,
    input_shardings,
    input_specs,
    mesh_axes,
    named,
)

__all__ = ["StepBundle", "make_train_step", "make_prefill_step",
           "make_decode_step", "make_ctx"]


@dataclass
class StepBundle:
    name: str
    fn: Callable
    args: Tuple[Any, ...]  # ShapeDtypeStruct trees
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...] = ()

    def jitted(self, mesh: Mesh):
        return jax.jit(
            self.fn,
            in_shardings=named(mesh, self.in_shardings),
            out_shardings=named(mesh, self.out_shardings),
            donate_argnums=self.donate_argnums,
        )

    def lower(self, mesh: Mesh):
        return self.jitted(mesh).lower(*self.args)


def make_ctx(mesh: Optional[Mesh]) -> ShardCtx:
    if mesh is None:
        return ShardCtx()
    dp, _, tp = mesh_axes(mesh)
    return ShardCtx(mesh=mesh, dp_axes=dp or ("data",), tp_axis=tp or "model")


def _abstract_f32(defs):
    return tree_map_defs(
        lambda pd: jax.ShapeDtypeStruct(
            pd.shape, jnp.float32 if pd.dtype == jnp.bfloat16 else pd.dtype
        ),
        defs,
    )


def _cast_tree(tree, dtype):
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype)
        if isinstance(x, jax.Array) and x.dtype == jnp.float32 and x.ndim > 0
        else x,
        tree,
    )


def _pspec_tree(defs, mesh: Mesh, fsdp_override=Ellipsis):
    _, fsdp, tp = mesh_axes(mesh)
    if fsdp_override is not Ellipsis:
        fsdp = fsdp_override
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return param_specs(defs, tp_axis=tp, fsdp_axis=fsdp, axis_sizes=sizes)


# -- train ---------------------------------------------------------------

def make_train_step(
    cfg: ModelConfig,
    shape: ShapeConfig,
    mesh: Mesh,
    opt_cfg: AdamWConfig = AdamWConfig(),
    aux_coef: float = 0.01,
    compress_grads: bool = False,
    zero1: bool = False,
) -> StepBundle:
    """``zero1=True`` keeps optimizer state FSDP-sharded but gathers the
    bf16 weights ONCE per step (TP-only layout) instead of per layer per
    microbatch — trades ~``2·P/tp`` resident bytes for eliminating the
    per-microbatch ZeRO-3 re-gathers (measured 5-10x collective-bytes win;
    see EXPERIMENTS.md §Perf).  Valid when bf16 params fit HBM at TP-only
    sharding (every assigned arch except dbrx-132b)."""
    defs = model_defs(cfg)
    ctx = make_ctx(mesh)
    if zero1:
        import dataclasses
        ctx = dataclasses.replace(ctx, zero1=True)
    n_mb = shape.microbatches
    B = shape.global_batch
    assert B % n_mb == 0
    pspecs = _pspec_tree(defs, mesh)
    pspecs_nofsdp = _pspec_tree(defs, mesh, fsdp_override=None)

    def loss_fn(params_bf16, mb):
        h, aux = forward(params_bf16, cfg, {k: v for k, v in mb.items()
                                            if k != "labels"}, shape, ctx)
        loss, n = chunked_ce_loss(
            h, params_bf16["unembed"], mb["labels"],
            t_chunk=shape.loss_chunk, logit_softcap=cfg.final_softcap,
        )
        return loss + aux_coef * aux, (loss, n)

    def train_step(params, opt_state, batch, ef_state=None):
        mbs = jax.tree_util.tree_map(
            lambda x: x.reshape((n_mb, B // n_mb) + x.shape[1:]), batch
        )
        params_c = _cast_tree(params, jnp.bfloat16)
        if zero1:
            # gather once per step: compute weights live TP-only sharded
            params_c = jax.tree_util.tree_map(
                lambda x, s: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, s)
                ),
                params_c, pspecs_nofsdp,
                is_leaf=lambda x: isinstance(x, jax.Array),
            )
        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )

        def mb_step(acc, mb):
            (tot, (loss, n)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params_c, mb)
            # accumulate at the FSDP (ZeRO) layout: under zero1 this is the
            # per-microbatch reduce-scatter of bf16 grads
            acc = jax.tree_util.tree_map(
                lambda a, g, s: jax.lax.with_sharding_constraint(
                    a + g.astype(jnp.float32), NamedSharding(mesh, s)
                ),
                acc, grads, pspecs,
            )
            return acc, (loss, n)

        grads, (losses, ns) = jax.lax.scan(mb_step, zeros, mbs)
        grads = jax.tree_util.tree_map(lambda g: g / n_mb, grads)
        metrics = {}
        new_ef = ef_state
        if compress_grads and ef_state is not None:
            grads, new_ef, qerr = compress_decompress(grads, ef_state)
            metrics["compression_err"] = qerr
        new_params, new_opt, gnorm = adamw_update(params, grads, opt_state, opt_cfg)
        metrics.update(
            loss=jnp.mean(losses),
            tokens=jnp.sum(ns),
            grad_norm=gnorm,
            step=new_opt.step,
        )
        out = (new_params, new_opt, metrics)
        return out + ((new_ef,) if compress_grads else ())

    params_sds = _abstract_f32(defs)
    opt_sds = OptState(
        mu=jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params_sds
        ),
        nu=jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params_sds
        ),
        step=jax.ShapeDtypeStruct((), jnp.int32),
    )
    opt_specs = OptState(mu=pspecs, nu=pspecs, step=P())
    batch_sds = input_specs(cfg, shape)
    batch_specs = input_shardings(cfg, shape, mesh)
    metric_specs = {"loss": P(), "tokens": P(), "grad_norm": P(), "step": P()}

    args = (params_sds, opt_sds, batch_sds)
    in_sh = (pspecs, opt_specs, batch_specs)
    out_sh = (pspecs, opt_specs, metric_specs)
    if compress_grads:
        ef_sds = EFState(residual=jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params_sds
        ))
        args = args + (ef_sds,)
        in_sh = in_sh + (EFState(residual=pspecs),)
        out_sh = out_sh + (EFState(residual=pspecs),)
        metric_specs["compression_err"] = P()
    return StepBundle(
        name=f"train:{cfg.name}:{shape.name}",
        fn=train_step,
        args=args,
        in_shardings=in_sh,
        out_shardings=out_sh,
        donate_argnums=(0, 1),
    )


# -- prefill ---------------------------------------------------------------

def make_prefill_step(
    cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
    param_fsdp: bool = True,
) -> StepBundle:
    defs = model_defs(cfg)
    ctx = make_ctx(mesh)
    if not param_fsdp:  # weights arrive TP-only: MoE skips FSDP gathers
        import dataclasses
        ctx = dataclasses.replace(ctx, zero1=True)

    def prefill_step(params, batch):
        h, _aux, caches = forward(
            params, cfg, batch, shape, ctx, collect_cache=True
        )
        last = h[:, -1]
        logits = (last @ params["unembed"]).astype(jnp.float32)
        from repro.models.layers import softcap
        return softcap(logits, cfg.final_softcap), caches

    pspecs = _pspec_tree(defs, mesh,
                         fsdp_override=Ellipsis if param_fsdp else None)
    params_sds = abstract_params(defs)
    batch_sds = input_specs(cfg, shape)
    batch_specs = input_shardings(cfg, shape, mesh)
    b = batch_entry(mesh, shape.global_batch)
    cache_specs = cache_pspecs(cfg, shape, mesh)
    return StepBundle(
        name=f"prefill:{cfg.name}:{shape.name}",
        fn=prefill_step,
        args=(params_sds, batch_sds),
        in_shardings=(pspecs, batch_specs),
        out_shardings=(P(b, None), cache_specs),
    )


# -- decode ---------------------------------------------------------------

def make_decode_step(
    cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, greedy: bool = True,
    param_fsdp: bool = True, quant_cache: bool = False,
) -> StepBundle:
    defs = model_defs(cfg)
    ctx = make_ctx(mesh)
    if not param_fsdp:  # weights arrive TP-only: MoE skips FSDP gathers
        import dataclasses
        ctx = dataclasses.replace(ctx, zero1=True)
    B, S = shape.global_batch, shape.seq_len

    def serve_step(params, tokens, cache, t):
        logits, new_cache, _ = decode_step(params, cfg, tokens, cache, t, ctx)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_tok, new_cache

    pspecs = _pspec_tree(defs, mesh,
                         fsdp_override=Ellipsis if param_fsdp else None)
    params_sds = abstract_params(defs)
    cache_sds = jax.eval_shape(
        lambda: init_cache(cfg, B, S, jnp.bfloat16, quant_attn=quant_cache)
    )
    cache_specs = cache_pspecs(cfg, shape, mesh, quant_attn=quant_cache)
    b = batch_entry(mesh, B)
    tok_sds = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    t_sds = jax.ShapeDtypeStruct((), jnp.int32)
    return StepBundle(
        name=f"decode:{cfg.name}:{shape.name}",
        fn=serve_step,
        args=(params_sds, tok_sds, cache_sds, t_sds),
        in_shardings=(pspecs, P(b, None), cache_specs, P()),
        out_shardings=(P(b, None), cache_specs),
        donate_argnums=(2,),
    )


def make_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, **kw) -> StepBundle:
    """Dispatch on the shape kind (the dry-run entry point)."""
    if shape.kind == "train":
        return make_train_step(cfg, shape, mesh, **kw)
    if shape.kind == "prefill":
        return make_prefill_step(cfg, shape, mesh, **kw)
    return make_decode_step(cfg, shape, mesh, **kw)
