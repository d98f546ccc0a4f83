"""Marvel-Serve unit + integration tests (DESIGN.md §14).

Covers the pager's placement transitions (create / per-step write-back /
demote / resume / drop / recover) against a hand-built tier stack, the
prefix-filtered ``keys()`` delegation fix (listing one namespace must not
touch unrelated keys' accounting or placement), and the gateway-facing
``ServingPool`` built through the :class:`~repro.api.MarvelClient`
façade — warm-pool eviction routing to demotion, KV-pressure load
snapshots, and admission shedding against the DRAM block budget.
"""

import hashlib
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import (
    ClusterConfig,
    ConfigError,
    MarvelClient,
    ServingConfig,
    TierSpec,
)
from repro.configs import get_config
from repro.core.gateway import AdmissionError
from repro.models import init_params, model_defs, reduced_for_smoke
from repro.models.attention import AttnCache
from repro.models.mla import MLACache
from repro.models.quant_cache import QuantAttnCache
from repro.serving import KVPager
from repro.storage import (
    DramTier,
    PlacementPolicy,
    StateCache,
    TieredStore,
    TierLevel,
)


class _DurableDram(DramTier):
    """In-memory PMEM stand-in: survives `crash()`."""

    name = "fakepmem"
    persistent = True


def _store(cap=1 << 20, write_back=False):
    """Two-level stack: capped DRAM over an unbounded durable home."""
    home = _DurableDram()
    journal = StateCache(memory=_DurableDram())
    store = TieredStore(
        [TierLevel("dram", DramTier(), cap), TierLevel("pmem", home)],
        policy=PlacementPolicy(write_back=write_back, promote_after=1,
                               flush_interval=0.002),
        journal=journal,
        name="serve-test",
    )
    return store, home


def _layers(seed=0, n=2, B=1, S=8, Kv=2, dh=4):
    """A hand-built per-layer cache list (no model needed)."""
    key = jax.random.PRNGKey(seed)
    out = []
    for i in range(n):
        k1, k2, key = jax.random.split(jax.random.fold_in(key, i), 3)
        out.append(AttnCache(
            k=jax.random.normal(k1, (B, S, Kv, dh), jnp.float32),
            v=jax.random.normal(k2, (B, S, Kv, dh), jnp.float32),
        ))
    return out


def _assert_layers_equal(got, want, exact=True):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for gf, wf in zip(g, w):
            ga, wa = np.asarray(gf), np.asarray(wf)
            if exact:
                assert ga.dtype == wa.dtype
                assert np.array_equal(ga, wa)
            else:
                np.testing.assert_allclose(ga, wa, atol=5e-2)


# -- pager placement transitions ----------------------------------------


class TestKVPager:
    def test_create_write_load_roundtrip(self):
        store, _ = _store()
        pager = KVPager(store, block_tokens=4, lossless=True)
        layers = _layers()
        pager.create("s0", layers, t=3)
        got, t = pager.load("s0")
        assert t == 3
        _assert_layers_equal(got, layers)
        # per-step write-back: only the dirty block is rewritten
        before = pager.stats.blocks_written
        new_layers = _layers(seed=1)
        pager.write("s0", new_layers, t=4)
        # 2 layers x 1 dirty block each (+ meta, not counted)
        assert pager.stats.blocks_written == before + 2
        got, t = pager.load("s0")
        assert t == 4
        _assert_layers_equal(got, new_layers)

    def test_blocks_are_per_session_layer_block_keys(self):
        store, _ = _store()
        pager = KVPager(store, block_tokens=4)
        pager.create("s0", _layers(S=8), t=0)
        keys = sorted(store.keys("kv/s0/"))
        # 2 layers x (8/4 = 2 blocks) + meta
        assert keys == [
            "kv/s0/L000/B00000", "kv/s0/L000/B00001",
            "kv/s0/L001/B00000", "kv/s0/L001/B00001",
            "kv/s0/meta",
        ]
        assert all(store.level_of(k) == "dram" for k in keys)
        assert pager.session_prefix("s0") in store.pinned_prefixes

    def test_lossless_demote_resume_byte_identity(self):
        store, _ = _store()
        pager = KVPager(store, block_tokens=4, lossless=True)
        layers = _layers()
        pager.create("s0", layers, t=5)
        assert pager.demote("s0")
        # all blocks left the fast level; pin released
        for k in store.keys("kv/s0/"):
            assert store.level_of(k) == "pmem"
        assert pager.session_prefix("s0") not in store.pinned_prefixes
        assert not pager.is_hot("s0")
        got, t = pager.load("s0")  # demand-fault resume
        assert t == 5
        _assert_layers_equal(got, layers, exact=True)
        assert pager.stats.demand_faults == 1
        assert pager.is_hot("s0")

    def test_quantized_demote_shrinks_and_still_decodes(self):
        store, _ = _store()
        pager = KVPager(store, block_tokens=4, lossless=False)
        layers = _layers(S=8, dh=16)
        pager.create("s0", layers, t=5)
        hot_bytes = sum(store.size_of(k) for k in store.keys("kv/s0/"))
        assert pager.demote("s0")
        cold_bytes = sum(store.size_of(k) for k in store.keys("kv/s0/"))
        # int8 + bf16 scales vs float32: well under half the bytes
        assert cold_bytes < hot_bytes * 0.6
        assert pager.stats.quantized_blocks > 0
        got, _t = pager.load("s0")
        assert all(isinstance(l, QuantAttnCache) for l in got)
        # dequantized content close to the original
        for g, w in zip(got, layers):
            deq = np.asarray(g.k_q, np.float32) * np.asarray(
                g.k_s, np.float32)[..., None]
            np.testing.assert_allclose(deq, np.asarray(w.k), atol=5e-2)

    def test_double_demote_is_noop(self):
        store, _ = _store()
        pager = KVPager(store, lossless=True)
        pager.create("s0", _layers(), t=0)
        assert pager.demote("s0")
        assert not pager.demote("s0")
        assert not pager.demote("missing")
        assert pager.stats.demotions == 1

    def test_resume_prefetch_promotes_in_background(self):
        store, _ = _store()
        pager = KVPager(store, block_tokens=4, lossless=True)
        layers = _layers()
        pager.create("s0", layers, t=2)
        pager.demote("s0")
        assert pager.resume("s0", prefetch=True)
        # background promotion: poll until the worker pulls all blocks up
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if all(store.level_of(k) == "dram"
                   for k in store.keys("kv/s0/")):
                break
            time.sleep(0.005)
        for k in store.keys("kv/s0/"):
            assert store.level_of(k) == "dram"
        # the subsequent load is a hot-path assembly, not a demand fault
        got, _ = pager.load("s0")
        _assert_layers_equal(got, layers)
        assert pager.stats.demand_faults == 0
        assert pager.stats.resumes == 1

    def test_crash_recover_adopts_sessions(self):
        store, _ = _store()
        pager = KVPager(store, block_tokens=4, lossless=True)
        layers = _layers()
        pager.create("s0", layers, t=7)
        pager.create("s1", _layers(seed=3), t=1)
        pager.sync()
        # lose the process + volatile tiers
        pager.crash()
        store.crash()
        store.recover()
        assert pager.sessions == []
        assert pager.recover() == 2
        assert sorted(pager.sessions) == ["s0", "s1"]
        assert pager.paged_sessions == 2  # adopted cold
        got, t = pager.load("s0")
        assert t == 7
        _assert_layers_equal(got, layers)

    def test_drop_removes_all_tiers(self):
        store, _ = _store()
        pager = KVPager(store, lossless=True)
        pager.create("s0", _layers(), t=0)
        pager.demote("s0")
        pager.drop("s0")
        assert list(store.keys("kv/s0/")) == []
        assert pager.sessions == []

    def test_admission_accounting(self):
        store, _ = _store()
        pager = KVPager(store, block_tokens=4, lossless=True,
                        dram_budget_bytes=None)
        assert pager.can_admit()  # no budget admits everything
        pager.create("a", _layers(), t=0)
        one = pager.dram_bytes()
        assert one > 0
        pager.create("b", _layers(seed=1), t=0)
        assert pager.dram_bytes() == 2 * one
        pager.dram_budget_bytes = int(2.5 * one)
        assert not pager.can_admit()  # a third session would not fit
        pager.demote(pager.lru_hot()[0])  # LRU victim = "a"
        assert pager.dram_bytes() == one
        assert pager.can_admit()
        assert pager.lru_hot() == ["b"]


# -- keys(prefix) delegation fix ----------------------------------------


class _LegacyTier(DramTier):
    """A tier predating the prefix parameter on ``keys()``."""

    name = "legacy"

    def keys(self):  # noqa: D102 - old signature on purpose
        return iter(list(self._data))


class TestPrefixListing:
    def test_statecache_keys_delegates_prefix(self):
        cache = StateCache(memory=DramTier())
        for i in range(4):
            cache.put(f"ns1/k{i}", b"x" * 8)
            cache.put(f"ns2/k{i}", b"y" * 8)
        tier = cache.memory
        before = (tier.stats.bytes_read, tier.stats.read_ops)
        assert sorted(cache.keys("ns1/")) == [f"ns1/k{i}" for i in range(4)]
        # listing is metadata-only: no value reads charged to the tier
        assert (tier.stats.bytes_read, tier.stats.read_ops) == before

    def test_statecache_keys_legacy_tier_fallback(self):
        cache = StateCache(memory=_LegacyTier())
        cache.put("a/1", b"x")
        cache.put("b/1", b"y")
        assert sorted(cache.keys("a/")) == ["a/1"]
        assert sorted(cache.keys()) == ["a/1", "b/1"]

    def test_tiered_keys_prefix_leaves_placement_alone(self):
        store, _ = _store()
        for i in range(4):
            store.put(f"ns1/k{i}", b"x" * 16)
            store.put(f"ns2/k{i}", b"y" * 16)
        store.demote("ns2/k0")
        placement = {k: store.level_of(k) for k in store.keys()}
        stats = {
            lv: (s.bytes_read, s.read_ops)
            for lv, s in store.stats_by_level().items()
        }
        assert sorted(store.keys("ns1/")) == [f"ns1/k{i}" for i in range(4)]
        # unrelated keys: placement, LRU recency, and read accounting
        # untouched by the namespaced listing
        assert {k: store.level_of(k) for k in store.keys()} == placement
        assert {
            lv: (s.bytes_read, s.read_ops)
            for lv, s in store.stats_by_level().items()
        } == stats

    def test_pmem_tier_prefix_walks_subtree_only(self, tmp_path):
        from repro.storage import PmemTier

        tier = PmemTier(str(tmp_path))
        tier.put("kv/s0/L000/B00000", b"a")
        tier.put("kv/s1/L000/B00000", b"b")
        tier.put("other/x", b"c")
        assert sorted(tier.keys("kv/s0/")) == ["kv/s0/L000/B00000"]
        assert sorted(tier.keys("kv/")) == [
            "kv/s0/L000/B00000", "kv/s1/L000/B00000"
        ]
        assert list(tier.keys("missing/")) == []


# -- the façade-built serving pool --------------------------------------


def _model():
    cfg = reduced_for_smoke(get_config("qwen2.5-3b"))
    params = init_params(model_defs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _prompt(cfg, seed=0, B=1, plen=8):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, plen), 0,
                              cfg.vocab)


class TestServingPool:
    def test_serving_config_validation(self):
        with pytest.raises(ConfigError):
            ServingConfig(block_tokens=0).validate()
        with pytest.raises(ConfigError):
            ServingConfig(dram_budget_bytes=-1).validate()
        ClusterConfig(serving=ServingConfig(block_tokens=8)).validate()
        with pytest.raises(ConfigError):
            ClusterConfig(serving=ServingConfig(block_tokens=0)).validate()

    def test_pool_end_to_end(self, tmp_path):
        cfg, params = _model()
        cluster = ClusterConfig(
            name="serve-test",
            tiers=(TierSpec("dram", capacity_bytes=8 << 20), "pmem"),
            invokers=2, warm_pool=3, commit_every=1,
            journal="pmem", journal_path=str(tmp_path),
            serving=ServingConfig(block_tokens=8, lossless=True),
        )
        with MarvelClient(cluster) as client:
            pool = client.serving(params, cfg, prompt_len=8, max_tokens=8)
            prompt = _prompt(cfg)
            toks = {}
            convs = [f"c{i}" for i in range(5)]
            for c in convs:
                toks[c] = [np.asarray(pool.start(c, prompt).result())]
            for c in convs:
                toks[c].append(np.asarray(pool.step(c).result()))
            # warm_pool=3 < 5 conversations: evictions routed to demotion
            assert pool.stats()["demotions"] > 0
            assert sorted(pool.conversations()) == sorted(convs)
            # KV pressure shows up in gateway load snapshots
            snap = client.gateway.load_snapshot()
            assert snap.resident_sessions + snap.paged_sessions == 5
            # suspend/resume round-trip continues the conversation
            first = np.asarray(pool.step("c0").result())
            assert pool.is_resident("c0")  # just stepped -> hot
            assert pool.suspend("c0")
            assert not pool.is_resident("c0")
            assert pool.resume("c0")
            tok = np.asarray(pool.step("c0").result())
            assert tok.shape == first.shape

    def test_admission_sheds_when_budget_exhausted(self, tmp_path):
        cfg, params = _model()
        with MarvelClient(ClusterConfig(
            name="shed-test", tiers=("dram", "pmem"),
            invokers=1, warm_pool=8, commit_every=1,
            journal="pmem", journal_path=str(tmp_path),
        )) as client:
            pool = client.serving(
                params, cfg, prompt_len=8, max_tokens=4,
                config=ServingConfig(block_tokens=8, lossless=True),
            )
            prompt = _prompt(cfg)
            pool.start("c0", prompt).result()
            # budget: room for exactly one resident session
            one = pool.pager.dram_bytes()
            pool.pager.dram_budget_bytes = int(1.5 * one)
            # idle LRU demotion makes room -> admitted, c0 demoted
            pool.start("c1", prompt).result()
            assert not pool.is_resident("c0")
            assert pool.is_resident("c1")
            assert pool.stats()["shed"] == 0
            # now pin both hot: nothing demotable -> shed
            pool.resume("c0")
            pool.pager.dram_budget_bytes = 1
            with pytest.raises(AdmissionError):
                pool.start("c2", prompt)
            assert pool.stats()["shed"] == 1

    def test_serving_rejects_sharded_client(self):
        cfg, params = _model()
        with MarvelClient(ClusterConfig(name="x", sharded=True,
                                        nodes=2)) as client:
            with pytest.raises(ConfigError):
                client.serving(params, cfg, prompt_len=4, max_tokens=2)

    def test_restart_resumes_through_pager(self, tmp_path):
        cfg, params = _model()
        cluster = ClusterConfig(
            name="restart-test",
            tiers=(TierSpec("dram", capacity_bytes=8 << 20),
                   TierSpec("pmem", path=str(tmp_path / "pmem"))),
            invokers=1, warm_pool=4, commit_every=1,
            journal="pmem", journal_path=str(tmp_path / "journal"),
            serving=ServingConfig(block_tokens=8, lossless=True),
        )
        prompt = _prompt(cfg)
        with MarvelClient(cluster) as client:
            pool = client.serving(params, cfg, prompt_len=8, max_tokens=8)
            pool.start("c0", prompt).result()
            baseline = [np.asarray(pool.step("c0").result())
                        for _ in range(3)]
            client.runtime.commit_all()
            pool.pager.sync()
        # fresh client over the same durable config: the pager re-adopts
        # the session from the PMEM tier and decode continues mid-stream
        with MarvelClient(cluster) as client:
            pool = client.serving(params, cfg, prompt_len=8, max_tokens=8)
            assert pool.pager.recover() == 1
            tok = np.asarray(pool.step("c0").result())
            assert tok.shape == baseline[-1].shape


# -- the block layout writes the bytes it always wrote --------------------


def _sha16(data):
    return hashlib.sha256(data).hexdigest()[:16]


class _RecordingDram(DramTier):
    """A DRAM tier that keeps, per ``put_many``, each key with the
    digest of its blob (in the order the pager wrote them)."""

    def __init__(self):
        super().__init__()
        self.batches = []

    def put_many(self, items):
        self.batches.append([(k, _sha16(v)) for k, v in items.items()])
        super().put_many(items)


def _golden_layers(kind, seed):
    """One layer of ``kind`` as ``(B, S, ...)`` and one stacked body layer
    with a leading period axis ``(P, B, S, ...)``; S=12."""
    rng = np.random.default_rng(seed)

    def bf16(*shape):
        x = rng.standard_normal(shape).astype(np.float32)
        return jnp.asarray(x).astype(jnp.bfloat16)

    def int8(*shape):
        return jnp.asarray(rng.integers(-128, 128, shape, dtype=np.int8))

    out = []
    for lead in ((), (2,)):
        if kind == "attn":
            out.append(AttnCache(k=bf16(*lead, 1, 12, 2, 4),
                                 v=bf16(*lead, 1, 12, 2, 4)))
        elif kind == "quant":
            out.append(QuantAttnCache(
                k_q=int8(*lead, 1, 12, 2, 4), v_q=int8(*lead, 1, 12, 2, 4),
                k_s=bf16(*lead, 1, 12, 2), v_s=bf16(*lead, 1, 12, 2)))
        elif kind == "mla":
            out.append(MLACache(c_kv=bf16(*lead, 1, 12, 8),
                                k_pe=bf16(*lead, 1, 12, 4)))
        else:  # a recurrent mixer's state: no sequence axis
            out.append(jnp.asarray(
                rng.standard_normal((*lead, 1, 6)).astype(np.float32)))
    return out


def _golden_run(kind):
    """create, write one token, demote (int8 where it applies), then load
    the session back through a fresh pager; returns every blob digest,
    the last meta JSON and each reloaded layer's type and digest."""
    store = _RecordingDram()
    pager = KVPager(store, block_tokens=4, lossless=False)
    pager.create("s", _golden_layers(kind, 0), t=4)
    pager.write("s", _golden_layers(kind, 1), t=5)
    pager.demote("s")
    back, t = KVPager(store, block_tokens=4).load("s")
    assert t == 5
    reloaded = [
        (type(layer).__name__,
         _sha16(b"".join(np.asarray(x).tobytes()
                         for x in jax.tree_util.tree_leaves(layer))))
        for layer in back
    ]
    return store.batches, store.get("kv/s/meta").decode(), reloaded


#: What the parent commit of the one-table block layout wrote, recorded
#: by running ``_golden_run`` on that commit in a copy outside the repo:
#: per kind, each ``put_many`` batch as (key, sha256[:16]) pairs, the
#: meta JSON after the demote, and each reloaded layer's type and digest.
_GOLDEN = {
    "attn": (
        [
            [
                ("kv/s/L000/B00000", "b99cc0d90d919956"),
                ("kv/s/L000/B00001", "369d9ce6d4e1a99a"),
                ("kv/s/L000/B00002", "4a77eb080e14c023"),
                ("kv/s/L001/B00000", "23919383ef99087f"),
                ("kv/s/L001/B00001", "414dd9926ef52815"),
                ("kv/s/L001/B00002", "d524c07b01d98d92"),
                ("kv/s/meta", "db624c45fdf0c75c"),
            ],
            [
                ("kv/s/L000/B00001", "dd794f1e8683ae2c"),
                ("kv/s/L001/B00001", "17a561b7af49bcd8"),
                ("kv/s/meta", "a9380cea889c6906"),
            ],
            [
                ("kv/s/L000/B00000", "730e82f7e10a5b33"),
                ("kv/s/L000/B00001", "e6dccc308f39435a"),
                ("kv/s/L000/B00002", "eaa1543c6f718848"),
                ("kv/s/L001/B00000", "b8c29e3e9a1c2317"),
                ("kv/s/L001/B00001", "90f9d13d74d60b90"),
                ("kv/s/L001/B00002", "2387b18649e29764"),
                ("kv/s/meta", "3349dd330f5248c7"),
            ],
        ],
        ('{"t": 5, "quantized": true, "lossless": false, '
         '"layers": [{"kind": "quant", "S": 12, "blocks": 3}, '
         '{"kind": "quant", "S": 12, "blocks": 3}]}'),
        [
            ("QuantAttnCache", "06388ff4520e524d"),
            ("QuantAttnCache", "0bc2ac8c5481d026"),
        ],
    ),
    "quant": (
        [
            [
                ("kv/s/L000/B00000", "019095be21f95d05"),
                ("kv/s/L000/B00001", "02e57c0b1c9c2ce0"),
                ("kv/s/L000/B00002", "4a3cf1a35701aac4"),
                ("kv/s/L001/B00000", "9d42251cff8eba81"),
                ("kv/s/L001/B00001", "38c2462144945420"),
                ("kv/s/L001/B00002", "b9d185c1ec355bb4"),
                ("kv/s/meta", "c3b8cc136b0d6a34"),
            ],
            [
                ("kv/s/L000/B00001", "c921202930885484"),
                ("kv/s/L001/B00001", "5e0b787a16ca1c53"),
                ("kv/s/meta", "3349dd330f5248c7"),
            ],
        ],
        ('{"t": 5, "quantized": true, "lossless": false, '
         '"layers": [{"kind": "quant", "S": 12, "blocks": 3}, '
         '{"kind": "quant", "S": 12, "blocks": 3}]}'),
        [
            ("QuantAttnCache", "e7a08d097fdb2d5e"),
            ("QuantAttnCache", "f7c70400529fba41"),
        ],
    ),
    "mla": (
        [
            [
                ("kv/s/L000/B00000", "a6671392575721c8"),
                ("kv/s/L000/B00001", "efbba78ff332afb2"),
                ("kv/s/L000/B00002", "bbce0516da372172"),
                ("kv/s/L001/B00000", "aa749ac8e9f9e16e"),
                ("kv/s/L001/B00001", "e4e8e79b495d1e7d"),
                ("kv/s/L001/B00002", "4b510c71653b1c09"),
                ("kv/s/meta", "53e847a8ca9b16a9"),
            ],
            [
                ("kv/s/L000/B00001", "77a14db86d428516"),
                ("kv/s/L001/B00001", "7f90f5fea6fac407"),
                ("kv/s/meta", "d7431feefa28a4cd"),
            ],
            [
                ("kv/s/L000/B00000", "be887261c4f59a52"),
                ("kv/s/L000/B00001", "77a14db86d428516"),
                ("kv/s/L000/B00002", "5db6dbfd73846124"),
                ("kv/s/L001/B00000", "df3fc6f8abfd84d1"),
                ("kv/s/L001/B00001", "7f90f5fea6fac407"),
                ("kv/s/L001/B00002", "c0653b649cb9b686"),
                ("kv/s/meta", "d7431feefa28a4cd"),
            ],
        ],
        ('{"t": 5, "quantized": false, "lossless": false, '
         '"layers": [{"kind": "mla", "S": 12, "blocks": 3}, '
         '{"kind": "mla", "S": 12, "blocks": 3}]}'),
        [
            ("MLACache", "a911ccce6c2cedbe"),
            ("MLACache", "735cd7148840e0ec"),
        ],
    ),
    "opaque": (
        [
            [
                ("kv/s/L000/B00000", "f366665828741290"),
                ("kv/s/L001/B00000", "d60ca98649feb52b"),
                ("kv/s/meta", "8352d4d22f100a2c"),
            ],
            [
                ("kv/s/L000/B00000", "a533c31edf77bd87"),
                ("kv/s/L001/B00000", "9865e0a89f5d79ad"),
                ("kv/s/meta", "e2aaea4129d63810"),
            ],
            [
                ("kv/s/L000/B00000", "a533c31edf77bd87"),
                ("kv/s/L001/B00000", "9865e0a89f5d79ad"),
                ("kv/s/meta", "e2aaea4129d63810"),
            ],
        ],
        ('{"t": 5, "quantized": false, "lossless": false, '
         '"layers": [{"kind": "opaque", "S": 0, "blocks": 1}, '
         '{"kind": "opaque", "S": 0, "blocks": 1}]}'),
        [
            ("ArrayImpl", "1d0eea839f439507"),
            ("ArrayImpl", "9089b0e52cb7cb34"),
        ],
    ),
}


@pytest.mark.parametrize("kind", ["attn", "quant", "mla", "opaque"])
def test_block_layout_writes_the_golden_bytes(kind):
    """Every blob, its key and the order of each batch, the meta JSON and
    the reassembled layers are those pinned above."""
    batches, meta, reloaded = _golden_run(kind)
    want_batches, want_meta, want_reloaded = _GOLDEN[kind]
    assert batches == want_batches
    assert meta == want_meta
    assert reloaded == want_reloaded
