"""Property test: paged decode is observationally lossless.

Whatever schedule of {decode, evict (demote), resume, crash+recover} a
set of conversations goes through, a ``lossless=True`` pager over a
journaled tier stack must behave exactly like a never-evicted in-memory
decode: every emitted token matches the oracle's token at that position
(no lost acked steps — the store journals every block write), and the
final per-layer cache bytes are identical to the oracle's cache.  Runs
under real hypothesis when installed, else the deterministic fallback
sampler (tests/hypothesis_compat.py).
"""

import numpy as np

import jax
import jax.numpy as jnp
from hypothesis_compat import given, nightly_examples, settings, st

from repro.configs import get_config
from repro.models import init_params, model_defs, reduced_for_smoke
from repro.serving import (
    KVPager,
    PagedDecoder,
    flatten_cache,
    unflatten_cache,
)
from repro.storage import (
    DramTier,
    PlacementPolicy,
    StateCache,
    TieredStore,
    TierLevel,
)

PROMPT_LEN, MAX_TOKENS = 8, 24
_SIDS = ["s0", "s1", "s2"]

_MODEL = None


def _model():
    """Module-cached tiny model (shared across property examples)."""
    global _MODEL
    if _MODEL is None:
        cfg = reduced_for_smoke(get_config("qwen2.5-3b"))
        params = init_params(model_defs(cfg), jax.random.PRNGKey(0))
        _MODEL = (cfg, params)
    return _MODEL


class _DurableDram(DramTier):
    name = "fakepmem"
    persistent = True


def _fresh_store():
    """Capped write-back DRAM over a durable home, durable journal —
    acked puts must survive a crash at any point."""
    return TieredStore(
        [TierLevel("dram", DramTier(), 1 << 20),
         TierLevel("home", _DurableDram())],
        policy=PlacementPolicy(write_back=True, promote_after=1,
                               flush_interval=0.002),
        journal=StateCache(memory=_DurableDram()),
        name="serve-prop",
    )


class _Oracle:
    """Never-evicted reference: same jitted decode, plain in-memory
    cache."""

    def __init__(self, decoder):
        self.decoder = decoder
        self.cache = {}   # sid -> layer list
        self.state = {}   # sid -> (t, tok)
        self.tokens = {}  # sid -> [token arrays]

    def start(self, sid, layers, state, tok):
        self.cache[sid] = list(layers)
        self.state[sid] = (int(state["t"]), state["tok"])
        self.tokens[sid] = [np.asarray(tok)]

    def step(self, sid):
        t, tok = self.state[sid]
        cache = unflatten_cache(self.decoder._treedef, self.cache[sid])
        t = t + 1
        logits, new_cache, _ = self.decoder._decode(
            self.decoder.params, tok, cache, jnp.int32(t))
        new_tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        self.cache[sid], _ = flatten_cache(new_cache)
        self.state[sid] = (t, new_tok)
        self.tokens[sid].append(np.asarray(new_tok))
        return np.asarray(new_tok)


_OPS = st.lists(
    st.tuples(
        st.sampled_from(["decode", "decode", "evict", "resume", "crash"]),
        st.integers(0, len(_SIDS) - 1),
    ),
    min_size=4,
    max_size=22,
)


@settings(max_examples=nightly_examples(3), deadline=None)
@given(st.integers(0, 2**31 - 1), _OPS)
def test_paged_decode_lossless_under_interleavings(seed, ops):
    cfg, params = _model()
    store = _fresh_store()
    try:
        pager = KVPager(store, block_tokens=4, lossless=True)
        decoder = PagedDecoder(params, cfg, pager,
                               prompt_len=PROMPT_LEN, max_tokens=MAX_TOKENS)
        oracle = _Oracle(decoder)
        states = {}  # sid -> paged function state (journaled in the
        # real system at commit_every=1; held by the test harness here)
        steps = {sid: 0 for sid in _SIDS}

        for op, si in ops:
            sid = _SIDS[si]
            if op == "decode":
                if steps[sid] >= MAX_TOKENS - 1:
                    continue  # cache ring would wrap past total_len
                if sid not in states:
                    prompt = jax.random.randint(
                        jax.random.fold_in(jax.random.PRNGKey(seed), si),
                        (1, PROMPT_LEN), 0, cfg.vocab)
                    states[sid] = decoder._init(sid, prompt)
                    layers, _t = pager.load(sid)
                    oracle.start(sid, layers, states[sid],
                                 states[sid]["tok"])
                else:
                    states[sid], tok = decoder._step(states[sid])
                    want = oracle.step(sid)
                    assert np.array_equal(np.asarray(tok), want), (
                        f"token diverged for {sid} at step {steps[sid]}")
                steps[sid] += 1
            elif op == "evict":
                if sid in states:
                    pager.demote(sid)
            elif op == "resume":
                if sid in states:
                    pager.resume(sid, prefetch=bool(si % 2))
            elif op == "crash":
                # lose the serving process and every volatile tier —
                # acked puts ride the journal; nothing was flushed
                # explicitly before the crash
                pager.crash()
                store.crash()
                store.recover()
                assert pager.recover() == len(states)

        # final byte identity: every session's paged cache equals the
        # never-evicted oracle's, leaf for leaf
        for sid in states:
            layers, t = pager.load(sid)
            assert t == oracle.state[sid][0], (
                f"{sid}: acked step lost (t={t} != {oracle.state[sid][0]})")
            for li, (got, want) in enumerate(zip(layers, oracle.cache[sid])):
                for gf, wf in zip(got, want):
                    ga, wa = np.asarray(gf), np.asarray(wf)
                    assert ga.dtype == wa.dtype
                    assert np.array_equal(ga, wa), (
                        f"{sid} layer {li}: cache bytes diverged")
    finally:
        store.close(flush=False)
