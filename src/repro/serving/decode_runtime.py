"""A ``StatefulFunction``-compatible decode wrapper over the KV pager.

The seed ``serve_lm`` kept whole KV caches inside the function state blob
— opaque to the tier hierarchy, so a warm-pool eviction round-tripped the
entire cache and DRAM held every conversation ever admitted.  Here the
function state shrinks to ``{session, t, tok}`` (a few hundred bytes,
cheap to journal every commit) while the cache itself lives in the pager
as per-(layer, block) tier keys.  Which leaves of the model's cache
pytree are one pager layer is the pager's layout table's call
(:func:`~repro.serving.kvpager.is_cache_layer`); this module names no
cache type.

Each step reads the session's layer list through :meth:`KVPager.load`
(the resident handle when hot — no tier I/O), runs the stock
``decode_step``, and writes back only the dirty blocks.  Dispatch to the
int8 path is structural: a session that was demoted quantized comes back
as :class:`QuantAttnCache` leaves, which ``attn_decode`` routes to
``quant_decode_attention``; raw sessions keep the float ``decode_step``
path.  Both shapes get their own jitted trace, keyed by the leaf types.
"""

from __future__ import annotations

from typing import Any, List, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.stateful import StatefulFunction
from repro.models import (
    ShapeConfig,
    decode_step,
    forward,
    init_cache,
    logits_fn,
)
from repro.serving.kvpager import KVPager, is_cache_layer

__all__ = ["PagedDecoder", "flatten_cache", "unflatten_cache"]


def flatten_cache(cache: Any) -> Tuple[List[Any], Any]:
    """Cache pytree → flat list of per-layer caches + treedef.  Every
    cache type of the pager's layout table stays whole (one pager layer
    each — the stacked body caches ride as single leaves with a leading
    period axis); anything else (ssm/rglru conv state) flattens to opaque
    array leaves the pager stores whole."""
    return jax.tree_util.tree_flatten(cache, is_leaf=is_cache_layer)


def unflatten_cache(treedef: Any, layers: List[Any]) -> Any:
    return jax.tree_util.tree_unflatten(treedef, layers)


class PagedDecoder:
    """Builds the paged decode :class:`StatefulFunction`.

    ``fn`` is registered with ``jit=False`` — the step does pager/tier
    I/O — while the pure model math inside (prefill forward, decode
    step) is jitted once per (batch shape, cache leaf types).
    """

    def __init__(
        self,
        params: Any,
        cfg: Any,
        pager: KVPager,
        *,
        prompt_len: int,
        max_tokens: int,
        name: str = "decode",
    ) -> None:
        self.params = params
        self.cfg = cfg
        self.pager = pager
        self.prompt_len = prompt_len
        self.total_len = prompt_len + max_tokens
        # Structure constant: the cache treedef does not depend on batch
        # size or values, so a throwaway template recovers it even when
        # this process never ran the prefill (post-restart resume).
        _, self._treedef = flatten_cache(init_cache(cfg, 1, 2))
        self._prefill = jax.jit(self._prefill_fn)

        # named, so that its XLA module is ``jit_paged_decode_step`` and a
        # trace tells it from any other jitted function
        def paged_decode_step(p, tok, cache, t):
            return decode_step(p, cfg, tok, cache, t)

        self._decode = jax.jit(paged_decode_step)
        self.fn = StatefulFunction(name, self._step, init=self._init,
                                   jit=False)

    # -- prefill ------------------------------------------------------------
    def _prefill_fn(self, params: Any, prompt: jnp.ndarray):
        """Prompt → (first greedy token (B, 1), decode cache)."""
        B, plen = prompt.shape
        shape = ShapeConfig(
            name="serve", kind="prefill", seq_len=plen, global_batch=B,
            q_chunk=min(8, plen), kv_chunk=min(8, plen), remat="none",
        )
        h, _aux, kv = forward(
            params, self.cfg, {"tokens": prompt}, shape,
            collect_cache=True, cache_len=self.total_len,
        )
        tok = jnp.argmax(logits_fn(params, self.cfg, h[:, -1]), -1)
        return tok.astype(jnp.int32)[:, None], kv

    def _init(self, session: str, prompt: jnp.ndarray) -> dict:
        with obs.span("serve.prefill", tokens=int(prompt.shape[1])):
            tok, kv = self._prefill(self.params, prompt)
            layers, _ = flatten_cache(kv)
            self.pager.create(session, layers, int(prompt.shape[1]) - 1)
        return {"session": session,
                "t": jnp.int32(int(prompt.shape[1]) - 1),
                "tok": tok}

    # -- one decode token ---------------------------------------------------
    def _step(self, state: dict) -> Tuple[dict, jnp.ndarray]:
        sid = state["session"]
        with obs.span("serve.decode") as attrs:
            pos = attrs["position"] = int(state["t"]) + 1
            t = jnp.int32(pos)
            layers, _t_meta = self.pager.load(sid)
            cache = unflatten_cache(self._treedef, layers)
            logits, new_cache, routes = self._decode(
                self.params, state["tok"], cache, t)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            # the write-back copies the new cache to the host, which waits
            # for the step anyway: the wait is timed on its own here
            with obs.span("serve.device_wait"):
                tok.block_until_ready()
            if routes is not None:  # computed by the step waited for
                attrs["held_routes"] = int(routes)
            new_layers, _ = flatten_cache(new_cache)
            self.pager.write(sid, new_layers, pos)
        return {"session": sid, "t": t, "tok": tok}, tok
