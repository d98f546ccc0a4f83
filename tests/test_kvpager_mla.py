"""The pager on MLA latent caches (DESIGN.md §14): a latent is paged in
blocks along its sequence axis (-2), a decode token writes back the one
block it touched, and every placement transition restores the latent
byte for byte, quantized mode included (latents are never quantized)."""

import json

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.models.mla import MLACache
from repro.serving import KVPager
from repro.storage import DramTier, PlacementPolicy, StateCache, TieredStore, TierLevel
from repro.storage import serde


class _DurableDram(DramTier):
    name = "fakepmem"
    persistent = True


def _store():
    home = _DurableDram()
    store = TieredStore(
        [TierLevel("dram", DramTier(), 1 << 22), TierLevel("pmem", home)],
        policy=PlacementPolicy(write_back=False, promote_after=1),
        journal=StateCache(memory=_DurableDram()), name="mla-test")
    return store


def _latents(seed=0, S=12, r=8, dr=4, periods=3):
    """Layer 0's latent (B, S, r) and a stacked body latent (P, B, S, r),
    in bf16 as the program keeps them."""
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf = jnp.bfloat16
    return [
        MLACache(c_kv=jax.random.normal(k[0], (1, S, r)).astype(bf),
                 k_pe=jax.random.normal(k[1], (1, S, dr)).astype(bf)),
        MLACache(c_kv=jax.random.normal(k[2], (periods, 1, S, r)).astype(bf),
                 k_pe=jax.random.normal(k[3], (periods, 1, S, dr)).astype(bf)),
    ]


def _same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert isinstance(g, MLACache)
        for ga, wa in zip(g, w):
            ga, wa = np.asarray(ga), np.asarray(wa)
            assert ga.dtype == wa.dtype and ga.shape == wa.shape
            assert ga.tobytes() == wa.tobytes()


def _block(layer, lo, hi):
    return {"c_kv": layer.c_kv[..., lo:hi, :], "k_pe": layer.k_pe[..., lo:hi, :]}


def test_latents_page_along_the_sequence_axis():
    store = _store()
    pager = KVPager(store, block_tokens=4, lossless=True)
    pager.create("s", _latents(), t=5)
    # 12 slots in blocks of 4: 3 blocks a layer
    assert sorted(store.keys("kv/s/")) == [
        f"kv/s/L{li:03d}/B{b:05d}" for li in range(2) for b in range(3)
    ] + ["kv/s/meta"]
    meta = json.loads(store.get("kv/s/meta"))
    assert [(m["kind"], m["S"], m["blocks"]) for m in meta["layers"]] == [
        ("mla", 12, 3), ("mla", 12, 3)]
    assert pager.stats.latent_blocks == 6


def test_a_token_writes_the_one_block_it_touched():
    store = _store()
    pager = KVPager(store, block_tokens=4, lossless=True)
    pager.create("s", _latents(), t=5)
    new = _latents(seed=1)
    since = obs.spans()[-1].sid if obs.spans() else 0
    blocks, nbytes = pager.stats.latent_blocks, pager.stats.latent_bytes
    pager.write("s", new, t=6)
    write = [s for s in obs.spans() if s.name == "pager.write" and s.sid > since][-1]
    # one block a layer: slots 4-7 hold position 6
    one = sum(len(serde.dumps(_block(layer, 4, 8))) for layer in new)
    assert write.attrs["blocks"] == 2
    assert write.attrs["kind"] == "mla"
    assert write.attrs["bytes"] == one
    assert pager.stats.latent_blocks - blocks == 2
    assert pager.stats.latent_bytes - nbytes == one
    # the blocks a token did not touch keep the prefill's bytes
    assert serde.loads(store.get("kv/s/L001/B00000"))["c_kv"].tobytes() == \
        np.asarray(_latents()[1].c_kv[..., 0:4, :]).tobytes()
    got, t = pager.load("s")
    assert t == 6
    _same(got, new)


def test_demote_resume_restores_the_latent_byte_for_byte():
    for lossless in (True, False):  # quantized mode leaves latents raw
        store = _store()
        pager = KVPager(store, block_tokens=4, lossless=lossless)
        layers = _latents(seed=2)
        pager.create("s", layers, t=9)
        assert pager.demote("s")
        assert all(store.level_of(k) == "pmem" for k in store.keys("kv/s/"))
        assert pager.stats.quantized_blocks == 0
        assert pager.resume("s")
        got, t = pager.load("s")
        assert t == 9
        _same(got, layers)
        meta = json.loads(store.get("kv/s/meta"))
        assert not meta["quantized"]
        assert {m["kind"] for m in meta["layers"]} == {"mla"}


def test_crash_and_adopt_restore_the_latent_byte_for_byte():
    store = _store()
    pager = KVPager(store, block_tokens=4, lossless=True)
    layers = _latents(seed=3)
    pager.create("s", layers, t=2)
    new = _latents(seed=4)
    pager.write("s", new, t=3)
    pager.sync()
    pager.crash()
    store.crash()
    store.recover()
    fresh = KVPager(store, block_tokens=4, lossless=True)
    assert fresh.recover() == 1
    got, t = fresh.load("s")
    assert t == 3
    # the write-back touched block 0 only: it holds the new latent there
    want = [MLACache(
        c_kv=jnp.concatenate([n.c_kv[..., :4, :], o.c_kv[..., 4:, :]], -2),
        k_pe=jnp.concatenate([n.k_pe[..., :4, :], o.k_pe[..., 4:, :]], -2))
        for n, o in zip(new, layers)]
    _same(got, want)
