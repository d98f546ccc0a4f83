"""Adaptive tier hierarchy: placement policy, write-back, prefetch, wiring.

Covers the `TieredStore` state machine (DESIGN.md §7) and its integration
points: the gateway warm-pool demotion, the StateCache/FunctionRuntime
state path, hierarchy-backed BlockStore DataNodes, and the adaptive
(write-back) MapReduce shuffle.
"""

import threading
import time

import pytest

from repro.core import FunctionRuntime, Gateway, StatefulFunction, run_job
from repro.core.mapreduce import wordcount_job
from repro.storage import (
    S3_SPEC,
    BlockStore,
    DataNode,
    DeviceSpec,
    DramTier,
    FaultInjectingTier,
    InjectedIOError,
    PlacementPolicy,
    PmemTier,
    SimulatedTier,
    StateCache,
    Tier,
    TieredStore,
    TierLevel,
)


def _stack(cap0=None, policy=None, journal=None, home=None, name="hier"):
    home = home if home is not None else SimulatedTier(S3_SPEC)
    return TieredStore(
        [TierLevel("dram", DramTier(), cap0), TierLevel("home", home)],
        policy=policy, journal=journal, name=name,
    ), home


# -- placement: promotion / demotion ------------------------------------------

def test_put_lands_fast_and_get_serves_fast():
    store, home = _stack()
    store.put("k", b"v" * 100)
    assert store.level_of("k") == "dram"
    base = store.stats.modeled_seconds
    assert store.get("k") == b"v" * 100
    assert store.stats.modeled_seconds == base  # no device time inline
    store.close()


def test_capacity_triggers_demotion_and_read_promotes_back():
    store, home = _stack(cap0=100, policy=PlacementPolicy(promote_after=2))
    store.put("a", b"x" * 60)
    store.put("b", b"y" * 60)  # overflows: LRU victim "a" demoted
    assert store.level_of("a") == "home"
    assert store.level_of("b") == "dram"
    assert store.get("a") == b"x" * 60  # 1st lower-level hit: stays
    assert store.level_of("a") == "home"
    assert store.get("a") == b"x" * 60  # 2nd hit clears admission
    assert store.level_of("a") == "dram"
    assert store.promotions == 1
    store.close()


def test_size_aware_admission_never_promotes_huge_keys():
    store, _ = _stack(
        cap0=10_000,
        policy=PlacementPolicy(promote_after=1, max_promote_bytes=64),
    )
    store.put("big", b"z" * 500)
    store.demote("big")
    for _ in range(5):
        store.get("big")
    assert store.level_of("big") == "home"  # too big to admit
    store.put("small", b"s" * 10)
    store.demote("small")
    store.get("small")
    assert store.level_of("small") == "dram"
    store.close()


def test_cost_aware_eviction_prefers_big_cold_keys():
    store, _ = _stack(
        cap0=200, policy=PlacementPolicy(eviction="cost", promote_after=99)
    )
    store.put("bigcold", b"b" * 150)
    store.put("smallhot", b"s" * 40)
    for _ in range(4):
        store.get("smallhot")  # hits-per-byte: high
    store.put("new", b"n" * 100)  # overflow: must evict someone
    assert store.level_of("bigcold") == "home"
    assert store.level_of("smallhot") == "dram"
    store.close()


def test_demote_walks_down_one_level_per_call():
    mid = SimulatedTier(S3_SPEC)
    bottom = DramTier()
    store = TieredStore(
        [TierLevel("l0", DramTier(), None), TierLevel("l1", mid, None),
         TierLevel("l2", bottom)],
    )
    store.put("k", b"v")
    assert store.level_of("k") == "l0"
    assert store.demote("k")
    assert store.level_of("k") == "l1"
    assert store.demote("k")
    assert store.level_of("k") == "l2"
    assert not store.demote("k")  # already home
    assert store.get("k") == b"v"
    store.close()


def test_adopts_preexisting_data_in_lower_tiers():
    home = SimulatedTier(S3_SPEC)
    home.put("legacy", b"old-data")
    store = TieredStore(
        [TierLevel("dram", DramTier(), None), TierLevel("home", home)]
    )
    assert store.contains("legacy")
    assert store.get("legacy") == b"old-data"
    assert store.level_of("legacy") == "home"
    store.close()


# -- write-back ----------------------------------------------------------------

def test_write_back_acks_fast_and_flushes_home():
    store, home = _stack(policy=PlacementPolicy(write_back=True))
    base = store.stats.modeled_seconds
    store.put("k", b"v" * 1000)
    assert store.stats.modeled_seconds == base  # S3 latency off hot path
    store.flush()
    assert home.contains("k")
    assert store.dirty_keys == []
    store.close()


def test_flusher_batches_via_put_many():
    # Per-op latency is huge; a batched flush charges it once per round,
    # not once per key (the SimulatedTier.put_many contract).
    spec = DeviceSpec(name="slow", read_bw=1e9, write_bw=1e9,
                      read_latency=1.0, write_latency=1.0)
    home = SimulatedTier(spec)
    store = TieredStore(
        [TierLevel("dram", DramTier(), None), TierLevel("home", home)],
        policy=PlacementPolicy(write_back=True, flush_batch=64),
    )
    store.put_many({f"k{i}": b"v" for i in range(50)})
    store.flush()
    assert home.stats.write_ops == 50
    assert home.stats.modeled_seconds < 3.0  # ~1 request, never ~50
    store.close()


def test_torn_flush_never_loses_acked_writes(tmp_path):
    journal = StateCache(write_through=PmemTier(str(tmp_path / "j")))
    home = FaultInjectingTier(
        PmemTier(str(tmp_path / "home")), seed=3, torn_put_many_rate=1.0
    )
    store = TieredStore(
        [TierLevel("dram", DramTier(), None), TierLevel("home", home)],
        policy=PlacementPolicy(write_back=True, flush_interval=0.005),
        journal=journal, name="wb",
    )
    items = {f"k{i}": bytes([65 + i]) * 20 for i in range(8)}
    store.put_many(items)  # acked
    deadline = time.monotonic() + 10.0
    while store.flush_errors == 0 and time.monotonic() < deadline:
        time.sleep(0.005)  # torn flush rounds fail behind our back
    assert store.flush_errors > 0
    for k, v in items.items():
        assert store.get(k) == v  # still served from the fast level
    # crash with keys still dirty: the journal replays every acked put
    store.crash()
    assert store.recover() == len(items)
    home.heal()
    store.flush()
    for k, v in items.items():
        assert home.get(k) == v
    store.close()


def test_write_back_survives_process_restart(tmp_path):
    jpath, hpath = str(tmp_path / "j"), str(tmp_path / "home")

    def build():
        journal = StateCache(write_through=PmemTier(jpath))
        journal.recover()
        # torn batches keep flushes failing -> dirty at "process death"
        home = FaultInjectingTier(PmemTier(hpath), seed=1,
                                  torn_put_many_rate=1.0)
        return TieredStore(
            [TierLevel("dram", DramTier(), None), TierLevel("home", home)],
            policy=PlacementPolicy(write_back=True, flush_interval=5.0),
            journal=journal, name="wb",
        ), home

    s1, h1 = build()
    s1.put("durable", b"ack-then-die")
    del s1  # no close/flush: the process dies

    s2, h2 = build()
    assert s2.recover() == 1
    assert s2.get("durable") == b"ack-then-die"
    h2.heal()
    s2.flush()
    assert h2.get("durable") == b"ack-then-die"
    s2.close()


def test_demote_skips_keys_pinned_by_inflight_flush():
    """A key snapshotted by an unresolved flush round must not be
    demoted into the home level: the in-flight (older) batch write could
    clobber the newer home copy after its dirty record was cleared."""
    store, _ = _stack(policy=PlacementPolicy(write_back=True))
    store.put("k", b"v")
    with store._mutex:
        store._inflight_flush.add("k")
    assert not store.demote("k")  # pinned while the round is in flight
    assert store.level_of("k") == "dram"
    with store._mutex:
        store._inflight_flush.discard("k")
    assert store.demote("k")
    assert store.level_of("k") == "home"
    assert store.get("k") == b"v"
    store.close()


# -- write-back redo records, written with the placement lock released --------

class _GatedJournalDevice(Tier):
    """A durable journal device whose writes, once ``hold()`` is called,
    wait at a gate until the test opens it: a slow fsync kept open for as
    long as the test needs.  With ``fail`` set a held write raises when
    released, as a device lost before the record landed.  ``writes``
    lists what landed, in order."""

    name = "gated"
    persistent = True

    def __init__(self) -> None:
        super().__init__()
        self.backing = DramTier()  # survives the journal cache's crash()
        self.gate = threading.Event()
        self.gate.set()
        self.entered = threading.Event()
        self.fail = False
        self.writes = []

    def hold(self) -> None:
        self.entered.clear()
        self.gate.clear()

    def put(self, key, value):
        self.put_many({key: value})

    def put_many(self, items):
        if not self.gate.is_set():
            self.entered.set()
            self.gate.wait(10.0)
            if self.fail:
                raise InjectedIOError("journal device lost mid-write")
        self.backing.put_many(items)
        self.writes.extend(items.items())

    def get(self, key):
        return self.backing.get(key)

    def delete(self, key):
        self.backing.delete(key)

    def contains(self, key):
        return self.backing.contains(key)

    def keys(self, prefix=""):
        return self.backing.keys(prefix)

    def size_of(self, key):
        return self.backing.size_of(key)


def _gated_store(home=None, flush_interval=0.005):
    """A write-back store whose redo log rides a gated journal device."""
    device = _GatedJournalDevice()
    journal = StateCache(write_through=device)
    store, home = _stack(
        policy=PlacementPolicy(write_back=True, flush_interval=flush_interval),
        journal=journal, home=home if home is not None else DramTier(),
        name="redo")
    return store, home, journal, device


def _start(fn, *args):
    out = []

    def run():
        try:
            out.append(fn(*args))
        except Exception as exc:  # the test inspects it
            out.append(exc)

    th = threading.Thread(target=run)
    th.start()
    return th, out


def _torn_home(tmp_path):
    """A home level whose flushes always tear: every acked put stays
    dirty, so recovery has only the journal to go on."""
    return FaultInjectingTier(PmemTier(str(tmp_path / "home")), seed=0,
                              torn_put_many_rate=1.0)


def test_installs_and_gets_proceed_while_a_redo_is_in_flight():
    store, _, _, device = _gated_store()
    store.put("q", b"read me")
    device.hold()
    writer, _ = _start(store.put_many, {"p": b"x" * 64, "p2": b"y" * 64})
    assert device.entered.wait(10.0)
    # the committer's install and a reader finish behind the open redo
    store.install_many({"s": b"state"})
    assert store.get("q") == b"read me"
    assert store.get("s") == b"state"
    assert writer.is_alive() and not device.gate.is_set()
    device.gate.set()
    writer.join(10.0)
    assert store.get("p") == b"x" * 64
    assert store.redo_order_waits == 0
    store.close()


def test_a_crash_before_the_redo_lands_loses_no_returned_put(tmp_path):
    store, _, journal, device = _gated_store(home=_torn_home(tmp_path))
    store.put_many({"a": b"a1", "b": b"b1"})
    store.put("a", b"a2")
    device.hold()
    device.fail = True
    writer, out = _start(store.put_many, {"a": b"a3", "c": b"c1"})
    assert device.entered.wait(10.0)
    assert store._entries["a"].version == 3  # installed, redo not landed
    store.crash()
    journal.crash()
    device.gate.set()  # the held write dies with the node
    writer.join(10.0)
    assert isinstance(out[0], InjectedIOError)  # never acknowledged
    assert store.recover() == 2
    assert store.get("a") == b"a2" and store.get("b") == b"b1"
    with pytest.raises(KeyError):
        store.get("c")
    store.put("a", b"a4")  # nothing is left pending
    assert store.get("a") == b"a4"
    store.close(flush=False)


def test_a_failed_redo_write_raises_and_leaves_the_put_installed_and_dirty():
    store, home, _, device = _gated_store(flush_interval=3600.0)
    device.hold()
    device.fail = True
    writer, out = _start(store.put_many, {"k": b"unacked"})
    assert device.entered.wait(10.0)
    device.gate.set()  # the held write fails
    writer.join(10.0)
    assert isinstance(out[0], InjectedIOError)  # never acknowledged
    assert not device.contains("redo/wb/done/k")  # no marker landed
    # the failed put is the value served and, once flushed, the home one
    assert store.get("k") == b"unacked"
    assert store.dirty_keys == ["k"]
    device.fail = False
    assert store.flush() == 1
    assert home.get("k") == b"unacked" and store.dirty_keys == []
    store.close()


def test_same_key_redos_land_in_version_order(tmp_path):
    store, _, journal, device = _gated_store(home=_torn_home(tmp_path))
    device.hold()
    first, _ = _start(store.put, "k", b"first")
    assert device.entered.wait(10.0)
    second, _ = _start(store.put, "k", b"second")
    deadline = time.monotonic() + 10.0
    while store.redo_order_waits == 0 and time.monotonic() < deadline:
        time.sleep(0.001)
    assert store.redo_order_waits == 1  # the second put waits its turn
    device.gate.set()
    first.join(10.0)
    second.join(10.0)
    blobs = [v for k, v in device.writes if k == "redo/wbdata/k"]
    assert blobs == [b"first", b"second"]
    store.crash()
    journal.crash()
    assert store.recover() == 1
    assert store.get("k") == b"second"
    assert store._entries["k"].version == 2
    store.close(flush=False)


def test_flusher_and_demote_skip_a_key_whose_redo_is_in_flight():
    store, home, _, device = _gated_store()
    device.hold()
    writer, _ = _start(store.put, "k", b"v")
    assert device.entered.wait(10.0)
    assert store.dirty_keys == ["k"]
    assert store._flush_once() == 0  # a flusher round leaves it dirty
    assert not store.demote("k")  # and no demotion lands it at home
    assert store.level_of("k") == "dram" and not home.contains("k")
    flusher, flushed = _start(store.flush)
    device.gate.set()
    writer.join(10.0)
    flusher.join(10.0)
    assert not flusher.is_alive() and not isinstance(flushed[0], Exception)
    assert store.dirty_keys == [] and home.get("k") == b"v"
    # the flushed key's redo record is retracted, blob and marker
    assert not device.contains("redo/wbdata/k")
    assert not device.contains("redo/wb/done/k")
    store.close()


def test_an_install_waits_for_the_redo_it_would_retract():
    store, _, _, device = _gated_store()
    device.hold()
    writer, _ = _start(store.put, "k", b"paged")
    assert device.entered.wait(10.0)
    installer, _ = _start(store.install_many, {"k": b"installed"})
    deadline = time.monotonic() + 10.0
    while store.redo_order_waits == 0 and time.monotonic() < deadline:
        time.sleep(0.001)
    assert store.redo_order_waits == 1
    device.gate.set()
    writer.join(10.0)
    installer.join(10.0)
    # the install retracted the landed record: replay cannot bring the
    # older value back
    assert not device.contains("redo/wbdata/k")
    assert not device.contains("redo/wb/done/k")
    assert store.get("k") == b"installed"
    store.close()


# -- stats: logical vs physical rollup ----------------------------------------

def test_promoted_read_counts_once_logically():
    store, home = _stack(policy=PlacementPolicy(promote_after=1))
    store.put("k", b"v" * 100)
    store.demote("k")
    n_reads = store.stats.read_ops
    assert store.get("k") == b"v" * 100  # hit at home + promotion
    assert store.stats.read_ops == n_reads + 1  # one logical read
    # physically: a home read and a fast-level write happened
    rolled = store.physical_stats()
    assert rolled.read_ops >= 1 and rolled.write_ops >= 2
    by_level = store.stats_by_level()
    assert by_level["home"].read_ops == 1
    store.close()


def test_hit_rates_roll_up_per_level():
    store, _ = _stack(policy=PlacementPolicy(promote_after=99))
    store.put("hot", b"h")
    store.put("cold", b"c")
    store.demote("cold")
    for _ in range(3):
        store.get("hot")
    store.get("cold")
    rates = store.hit_rates()
    assert rates["dram"] == pytest.approx(0.75)
    assert rates["home"] == pytest.approx(0.25)
    store.close()


# -- prefetch ------------------------------------------------------------------

def test_prefetch_pulls_producer_commits_into_fast_tier():
    shared = SimulatedTier(S3_SPEC)
    producer = TieredStore(
        [TierLevel("dram", DramTier(), None), TierLevel("s3", shared)],
        policy=PlacementPolicy(write_back=True, flush_interval=0.005),
        name="prod",
    )
    consumer = TieredStore(
        [TierLevel("dram", DramTier(), None), TierLevel("s3", shared)],
        policy=PlacementPolicy(write_back=True), name="cons",
    )
    consumer.prefetch("shuffle/")
    producer.put_many({f"shuffle/p{i}": b"d" * 64 for i in range(4)})
    producer.flush()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if all(consumer.level_of(f"shuffle/p{i}") == "dram" for i in range(4)):
            break
        time.sleep(0.005)
    levels = [consumer.level_of(f"shuffle/p{i}") for i in range(4)]
    assert levels == ["dram"] * 4
    base = consumer.stats.modeled_seconds
    assert consumer.get("shuffle/p0") == b"d" * 64
    assert consumer.stats.modeled_seconds == base  # hot before first ask
    producer.close()
    consumer.close()


# -- integration: gateway / runtime / blockstore / mapreduce -------------------

def _hier_cache(tmp_path):
    pmem = PmemTier(str(tmp_path / "pmem"))
    store = TieredStore(
        [TierLevel("dram", DramTier(), None), TierLevel("pmem", pmem)],
        policy=PlacementPolicy(promote_after=1), name="state",
    )
    return StateCache(memory=store), store


def test_gateway_warm_pool_eviction_demotes_state(tmp_path):
    cache, hier = _hier_cache(tmp_path)
    rt = FunctionRuntime(cache=cache, commit_every=1)
    rt.register(StatefulFunction(
        "counter", lambda s, x: (s + x, s + x), init=lambda: 0, jit=False
    ))
    gw = Gateway(rt, invokers=2, warm_pool=1)
    try:
        gw.invoke("counter", session="s0", x=5)
        gw.invoke("counter", session="s1", x=7)  # evicts+demotes s0
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if hier.level_of("state/s0/counter") == "pmem":
                break
            time.sleep(0.005)
        assert hier.level_of("state/s0/counter") == "pmem"
        # demoted state reloads correctly (and re-promotes on the read)
        assert gw.invoke("counter", session="s0", x=1) == 6
    finally:
        gw.close()
    hier.close()


def test_runtime_on_hierarchy_survives_crash(tmp_path):
    cache, hier = _hier_cache(tmp_path)
    rt = FunctionRuntime(cache=cache, commit_every=1)
    rt.register(StatefulFunction(
        "counter", lambda s, x: (s + x, s + x), init=lambda: 0, jit=False
    ))
    assert rt.invoke("counter", session="s", x=3) == 3
    assert rt.invoke("counter", session="s", x=4) == 7
    rt.crash()  # drops DRAM level; PMEM level survives
    rt.recover()
    assert rt.state_report("counter", "s") in ("warm", "hot")
    assert rt.invoke("counter", session="s", x=1) == 8
    hier.close()


def test_blockstore_datanodes_can_be_hierarchy_backed(tmp_path):
    nodes = []
    for i in range(3):
        hier = TieredStore(
            [TierLevel("dram", DramTier(), 4096),
             TierLevel("pmem", PmemTier(str(tmp_path / f"n{i}")))],
            name=f"node{i}",
        )
        nodes.append(DataNode(f"w{i}", hier))
    bs = BlockStore(nodes, block_size=1024, replication=2)
    data = b"block-data " * 500
    bs.write("/f", data)
    assert bs.read("/f") == data
    # replica loss still recovers through the hierarchy tiers
    bs.fail_node("w0")
    assert bs.read("/f") == data
    for nd in nodes:
        nd.tier.close()


def test_adaptive_shuffle_matches_static_and_cuts_inline_io():
    def mkbs():
        nodes = [DataNode(f"w{i}", DramTier()) for i in range(4)]
        bs = BlockStore(nodes, block_size=800, replication=2)
        bs.write("/in", b"\n".join([b"a b a c b a"] * 300), record_delim=b"\n")
        return bs

    static = run_job(
        wordcount_job(4), mkbs(), "/in", "/out", SimulatedTier(S3_SPEC),
        mode="pipelined",
    )
    backing = SimulatedTier(S3_SPEC)
    adaptive = run_job(
        wordcount_job(4), mkbs(), "/in", "/out", backing,
        mode="pipelined", adaptive=True,
    )
    assert adaptive.output_bytes == static.output_bytes
    # inline S3 latency left the map/reduce critical path entirely …
    assert adaptive.modeled_io_seconds < 0.25 * static.modeled_io_seconds
    # … yet the backing tier holds the shuffle data (background flush)
    assert any(k.startswith("mr/wordcount/") for k in backing.keys())


def test_adaptive_journaled_job_resumes(tmp_path):
    journal = StateCache(write_through=PmemTier(str(tmp_path / "j")))
    backing = SimulatedTier(S3_SPEC)
    nodes = [DataNode(f"w{i}", DramTier()) for i in range(4)]
    bs = BlockStore(nodes, block_size=800, replication=2)
    bs.write("/in", b"\n".join([b"x y z x"] * 200), record_delim=b"\n")
    r1 = run_job(wordcount_job(4), bs, "/in", "/o", backing,
                 journal=journal, adaptive=True)
    r2 = run_job(wordcount_job(4), bs, "/in", "/o", backing,
                 journal=journal, adaptive=True)
    assert r1.resumed_tasks == 0
    assert r2.resumed_tasks == r2.map_tasks + r2.reduce_tasks


def test_pin_survives_crash_and_promotes_on_first_read(tmp_path):
    """A pinned prefix keeps working across a node failure: survivors are
    re-adopted at the persistent home, and the first read promotes them
    straight back into the fast level (pins bypass frequency admission)."""
    store = TieredStore(
        [
            TierLevel("dram", DramTier(), None),
            TierLevel("pmem", PmemTier(str(tmp_path / "home"))),
        ],
        policy=PlacementPolicy(promote_after=5),  # high admission bar
        name="pin-crash",
    )
    store.pin("df/job/")
    store.put("df/job/state", b"loop-state")
    store.put("unpinned", b"cold")
    assert store.level_of("df/job/state") == "dram"
    store.crash()
    # both survive at the persistent home
    assert store.level_of("df/job/state") == "pmem"
    assert store.level_of("unpinned") == "pmem"
    # one read: the pinned key skips the promote_after=5 bar …
    assert store.get("df/job/state") == b"loop-state"
    assert store.level_of("df/job/state") == "dram"
    # … the unpinned key does not
    assert store.get("unpinned") == b"cold"
    assert store.level_of("unpinned") == "pmem"
    store.close()
