"""Mixture-of-Experts FFN with expert parallelism over the TP axis.

EP dispatch *is* the paper's shuffle: tokens are intermediate data routed
to their owner (expert) through the fast tier (ICI ``all_to_all``), exactly
like Marvel keeps MapReduce's mapper→reducer traffic in Ignite instead of
S3.  The dispatch machinery mirrors ``core/device_shuffle.pack_buckets``
(sort → capacity-pack → all_to_all → local compute → reverse path).

Four apply paths:

  * ``moe_apply_held``    — one device: the layer holds the routed experts
    ``[first_held, first_held + n_held)`` (all by default), routes over all
    of them, and computes, with no token dropped, only what its own
    experts give (tokens sorted by held expert, ``lax.ragged_dot``); on one
    chip of an expert-parallel deployment it runs without the exchange.
  * ``moe_apply_dense``   — reference: every token through its top-k experts
    via per-expert capacity gather; no mesh needed (oracle of the two
    sharded paths, which pack by capacity too).
  * ``moe_apply_a2a``     — shard_map EP: tokens sequence-sharded over TP,
    two all_to_alls (dispatch + return).  Used for train/prefill.
  * ``moe_apply_gather``  — shard_map EP for tiny T (decode): tokens
    replicated over TP, each column computes its owned experts, psum
    combine.  One psum, no all_to_all.

Expert weights are 2D-sharded ``(TP on experts, FSDP on d_model)`` and
all-gathered over FSDP inside the shard_map (manual ZeRO-3 gather).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

from repro.models.config import ModelConfig, MoEConfig
from repro.models.layers import mlp_apply, mlp_defs
from repro.models.param import FSDP, TP, ParamDef

__all__ = ["moe_defs", "moe_apply", "moe_apply_held"]


def moe_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    m = cfg.moe
    D, E, F = cfg.d_model, m.held, m.d_expert
    defs = {
        "router": ParamDef((D, m.n_experts), (FSDP, None), dtype=jnp.float32),
        "w_gate": ParamDef((E, D, F), (TP, FSDP, None)),
        "w_up": ParamDef((E, D, F), (TP, FSDP, None)),
        "w_down": ParamDef((E, F, D), (TP, None, FSDP)),
    }
    if m.n_shared:
        defs["shared"] = mlp_defs(D, m.n_shared * F, gated=True)
    return defs


def _route(xf: jax.Array, router: jax.Array, m: MoEConfig):
    """Top-k routing. Returns (weights (N,k) f32, experts (N,k) i32, aux)."""
    logits = jnp.dot(xf.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    if m.normalize_top_k:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # load-balance aux (Switch-style): E * sum_e f_e * p_e
    E = probs.shape[-1]
    f = jnp.mean(
        jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(axis=1), axis=0
    )
    p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * p)
    return w, idx, aux


def _expert_ffn(gx: jax.Array, wg, wu, wd, act: str) -> jax.Array:
    """gx: (E_loc, C, D) -> (E_loc, C, D); batched gated FFN."""
    act_fn = jax.nn.silu if act == "silu" else jax.nn.gelu
    h = act_fn(jnp.einsum("ecd,edf->ecf", gx, wg)) * jnp.einsum(
        "ecd,edf->ecf", gx, wu
    )
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _pack_by_group(
    groups: jax.Array,  # (M,) int32 group id, or big sentinel for invalid
    n_groups: int,
    capacity: int,
):
    """Sort-based capacity packing. Returns (order, grp_sorted, pos, keep)."""
    order = jnp.argsort(groups, stable=True)
    gs = groups[order]
    starts = jnp.searchsorted(gs, jnp.arange(n_groups + 1))
    pos = jnp.arange(groups.shape[0]) - starts[jnp.minimum(gs, n_groups)]
    keep = (pos < capacity) & (gs < n_groups)
    return order, gs, pos, keep


# -- one device: the held experts, dropless ----------------------------------

def moe_apply_held(
    p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The held experts' weighted outputs for every routed slot, no token
    dropped, plus the shared experts.  Returns (output, aux, how many
    routed slots fell on held experts)."""
    m = cfg.moe
    B, T, D = x.shape
    N = B * T
    xf = x.reshape(N, D)
    w, idx, aux = _route(xf, p["router"], m)
    M = N * m.top_k
    local = idx.reshape(M) - m.first_held
    held = (local >= 0) & (local < m.held)
    # slots on experts held elsewhere sort last, outside every group
    order = jnp.argsort(jnp.where(held, local, m.held), stable=True)
    sizes = jnp.zeros((m.held,), jnp.int32).at[local].add(
        held.astype(jnp.int32), mode="drop")
    tok = order // m.top_k
    xs = xf[tok]

    def gmm(a, b):
        return jax.lax.ragged_dot(a, b, sizes,
                                  preferred_element_type=jnp.float32)

    act_fn = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = (act_fn(gmm(xs, p["w_gate"])) * gmm(xs, p["w_up"])).astype(x.dtype)
    y = gmm(h, p["w_down"])
    # rows outside every group are not defined by ragged_dot: select
    contrib = jnp.where(held[order][:, None],
                        y * w.reshape(M)[order][:, None], 0.0)
    out = jnp.zeros((N, D), jnp.float32).at[tok].add(contrib)
    out = out.reshape(B, T, D).astype(x.dtype)
    if m.n_shared:
        out = out + mlp_apply(p["shared"], x, cfg.act)
    return out, aux, jnp.sum(held, dtype=jnp.int32)


# -- reference path ---------------------------------------------------------

def moe_apply_dense(
    p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array]:
    """Oracle: capacity-packed per-expert compute on one device."""
    m = cfg.moe
    B, T, D = x.shape
    N = B * T
    xf = x.reshape(N, D)
    w, idx, aux = _route(xf, p["router"], m)
    M = N * m.top_k
    e_flat = idx.reshape(M)
    w_flat = w.reshape(M)
    tok = jnp.repeat(jnp.arange(N), m.top_k)
    cap = max(1, int(math.ceil(M / m.n_experts * m.capacity_factor)))
    order, gs, pos, keep = _pack_by_group(e_flat, m.n_experts, cap)
    ge = jnp.minimum(gs, m.n_experts - 1)
    gp = jnp.minimum(pos, cap - 1)
    gx = jnp.zeros((m.n_experts, cap, D), x.dtype)
    gx = gx.at[jnp.where(keep, gs, m.n_experts), jnp.where(keep, pos, cap)].set(
        xf[tok[order]], mode="drop"
    )
    y = _expert_ffn(gx, p["w_gate"], p["w_up"], p["w_down"], cfg.act)
    vals = jnp.where(keep[:, None], y[ge, gp], 0.0)  # (M, D) sorted order
    contrib = jnp.zeros((N, D), y.dtype)
    contrib = contrib.at[tok[order]].add(vals * w_flat[order][:, None].astype(y.dtype))
    out = contrib.reshape(B, T, D).astype(x.dtype)
    if m.n_shared:
        out = out + mlp_apply(p["shared"], x, cfg.act)
    return out, aux


# -- sharded paths ---------------------------------------------------------

def _gather_experts(p, fsdp_axes):
    """Manual ZeRO gather of expert weights over the FSDP axis/axes."""
    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    for ax in fsdp_axes:
        wg = jax.lax.all_gather(wg, ax, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, ax, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, ax, axis=2, tiled=True)
    return wg, wu, wd


def moe_apply_a2a(
    p: Dict[str, jax.Array],
    x: jax.Array,
    cfg: ModelConfig,
    mesh: Mesh,
    dp_axes: Tuple[str, ...],
    tp_axis: str,
    zero1: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """EP via two all_to_alls; tokens sequence-sharded along TP."""
    m = cfg.moe
    B, T, D = x.shape
    tp = mesh.shape[tp_axis]
    E_loc = m.n_experts // tp
    assert T % tp == 0, "a2a MoE path needs seq divisible by TP"

    # zero1: weights arrive pre-gathered -> no in-shard_map FSDP gathers
    fsdp_axes = () if zero1 else dp_axes[-1:]

    def shard_fn(xl, router, wg_l, wu_l, wd_l):
        wg, wu, wd = _gather_experts(
            {"w_gate": wg_l, "w_up": wu_l, "w_down": wd_l}, fsdp_axes
        )
        router_full = router
        for ax in fsdp_axes:
            router_full = jax.lax.all_gather(router_full, ax, axis=0, tiled=True)
        Bl, Tl, _ = xl.shape
        N = Bl * Tl
        xf = xl.reshape(N, D)
        w, idx, aux = _route(xf, router_full, m)
        M = N * m.top_k
        e_flat = idx.reshape(M)
        tok = jnp.repeat(jnp.arange(N), m.top_k)
        owner = e_flat // E_loc
        cap_s = max(1, int(math.ceil(M / tp * m.capacity_factor)))
        cap_e = max(1, int(math.ceil(M * tp / m.n_experts * m.capacity_factor)))

        # ---- dispatch pack (by owner column) ----
        order, gs, pos, keep = _pack_by_group(owner, tp, cap_s)
        row = jnp.where(keep, gs, tp)
        col = jnp.where(keep, pos, cap_s)
        send_x = jnp.zeros((tp, cap_s, D), xl.dtype)
        send_x = send_x.at[row, col].set(xf[tok[order]], mode="drop")
        send_e = jnp.full((tp, cap_s), -1, jnp.int32)
        send_e = send_e.at[row, col].set(e_flat[order].astype(jnp.int32), mode="drop")

        recv_x = jax.lax.all_to_all(send_x, tp_axis, 0, 0, tiled=True)
        recv_e = jax.lax.all_to_all(send_e, tp_axis, 0, 0, tiled=True)

        # ---- local expert grouping ----
        my_col = jax.lax.axis_index(tp_axis)
        le = jnp.where(recv_e >= 0, recv_e - my_col * E_loc, E_loc).reshape(-1)
        rxf = recv_x.reshape(tp * cap_s, D)
        order2, gs2, pos2, keep2 = _pack_by_group(le, E_loc, cap_e)
        gx = jnp.zeros((E_loc, cap_e, D), xl.dtype)
        gx = gx.at[
            jnp.where(keep2, gs2, E_loc), jnp.where(keep2, pos2, cap_e)
        ].set(rxf[order2], mode="drop")
        y = _expert_ffn(gx, wg, wu, wd, cfg.act)
        ge2 = jnp.minimum(gs2, E_loc - 1)
        gp2 = jnp.minimum(pos2, cap_e - 1)
        vals2 = jnp.where(keep2[:, None], y[ge2, gp2], 0.0).astype(xl.dtype)
        ret = jnp.zeros((tp * cap_s, D), xl.dtype).at[order2].set(vals2)

        back = jax.lax.all_to_all(
            ret.reshape(tp, cap_s, D), tp_axis, 0, 0, tiled=True
        )

        # ---- combine at source ----
        inv = jnp.argsort(order)  # entry -> sorted slot
        pos_of = pos[inv]
        keep_of = keep[inv]
        got = back[
            jnp.minimum(owner, tp - 1), jnp.minimum(pos_of, cap_s - 1)
        ]  # (M, D)
        got = jnp.where(keep_of[:, None], got, 0.0)
        wf = w.reshape(M).astype(got.dtype)
        contrib = jnp.zeros((N, D), got.dtype).at[tok].add(got * wf[:, None])
        out = contrib.reshape(Bl, Tl, D)
        aux = jax.lax.pmean(jax.lax.pmean(aux, tp_axis), dp_axes[0])
        for ax in dp_axes[1:]:
            aux = jax.lax.pmean(aux, ax)
        return out, aux

    w_fsdp = None if zero1 else dp_axes[-1]
    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(dp_axes, tp_axis, None),  # x: sequence-sharded over TP
            P(w_fsdp, None),  # router
            P(tp_axis, w_fsdp, None),
            P(tp_axis, w_fsdp, None),
            P(tp_axis, None, w_fsdp),
        ),
        out_specs=(P(dp_axes, tp_axis, None), P()),
        check_vma=False,
    )
    out, aux = fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    if m.n_shared:
        out = out + mlp_apply(p["shared"], x, cfg.act)
    return out, aux


def moe_apply_gather(
    p: Dict[str, jax.Array],
    x: jax.Array,
    cfg: ModelConfig,
    mesh: Mesh,
    dp_axes: Tuple[str, ...],
    tp_axis: str,
    zero1: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """EP for decode-size T: tokens replicated over TP, psum combine."""
    m = cfg.moe
    B, T, D = x.shape
    tp = mesh.shape[tp_axis]
    E_loc = m.n_experts // tp

    fsdp_axes = () if zero1 else dp_axes[-1:]

    def shard_fn(xl, router, wg_l, wu_l, wd_l):
        wg, wu, wd = _gather_experts(
            {"w_gate": wg_l, "w_up": wu_l, "w_down": wd_l}, fsdp_axes
        )
        router_full = router
        for ax in fsdp_axes:
            router_full = jax.lax.all_gather(router_full, ax, axis=0, tiled=True)
        Bl, Tl, _ = xl.shape
        N = Bl * Tl
        xf = xl.reshape(N, D)
        w, idx, aux = _route(xf, router_full, m)
        M = N * m.top_k
        e_flat = idx.reshape(M)
        tok = jnp.repeat(jnp.arange(N), m.top_k)
        my_col = jax.lax.axis_index(tp_axis)
        le_all = e_flat - my_col * E_loc
        le = jnp.where((le_all >= 0) & (le_all < E_loc), le_all, E_loc)
        cap_e = max(1, int(math.ceil(M / m.n_experts * m.capacity_factor)))
        order, gs, pos, keep = _pack_by_group(le, E_loc, cap_e)
        gx = jnp.zeros((E_loc, cap_e, D), xl.dtype)
        gx = gx.at[
            jnp.where(keep, gs, E_loc), jnp.where(keep, pos, cap_e)
        ].set(xf[tok[order]], mode="drop")
        y = _expert_ffn(gx, wg, wu, wd, cfg.act)
        ge = jnp.minimum(gs, E_loc - 1)
        gp = jnp.minimum(pos, cap_e - 1)
        vals = jnp.where(keep[:, None], y[ge, gp], 0.0)
        wf = w.reshape(M).astype(vals.dtype)[order]
        contrib = jnp.zeros((N, D), vals.dtype).at[tok[order]].add(vals * wf[:, None])
        out = jax.lax.psum(contrib, tp_axis).reshape(Bl, Tl, D).astype(xl.dtype)
        aux = jax.lax.pmean(aux, dp_axes[0])
        for ax in dp_axes[1:]:
            aux = jax.lax.pmean(aux, ax)
        return out, aux

    w_fsdp = None if zero1 else dp_axes[-1]
    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(dp_axes, None, None),
            P(w_fsdp, None),
            P(tp_axis, w_fsdp, None),
            P(tp_axis, w_fsdp, None),
            P(tp_axis, None, w_fsdp),
        ),
        out_specs=(P(dp_axes, None, None), P()),
        check_vma=False,
    )
    out, aux = fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    if m.n_shared:
        out = out + mlp_apply(p["shared"], x, cfg.act)
    return out, aux


def moe_apply(
    p: Dict[str, jax.Array],
    x: jax.Array,
    cfg: ModelConfig,
    mesh: Optional[Mesh] = None,
    dp_axes: Tuple[str, ...] = ("data",),
    tp_axis: str = "model",
    zero1: bool = False,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Dispatching wrapper: picks the held / dense / a2a / gather path.
    Returns (output, aux, routed slots on held experts); the sharded paths
    hold every expert and count none (None)."""
    if mesh is None or tp_axis not in mesh.axis_names or mesh.shape[tp_axis] == 1:
        return moe_apply_held(p, x, cfg)
    if cfg.moe.held != cfg.moe.n_experts:
        raise ValueError("a layer that holds a share of the experts runs on "
                         "one device")
    tp = mesh.shape[tp_axis]
    if cfg.moe.n_experts % tp != 0:
        out, aux = moe_apply_dense(p, x, cfg)
    elif x.shape[1] % tp == 0:
        out, aux = moe_apply_a2a(p, x, cfg, mesh, dp_axes, tp_axis, zero1)
    else:
        out, aux = moe_apply_gather(p, x, cfg, mesh, dp_axes, tp_axis, zero1)
    return out, aux, None
