"""Fault injection + the crash/recovery matrix.

The matrix is the paper's durability claim, tested deterministically:
for {DRAM-only, PMEM write-through} x {crash mid-invocation, crash
mid-commit (torn/failed put via FaultInjectingTier)} — write-through
sessions resume from the last commit **byte-identically**, DRAM-only
sessions report state lost and cold-start.
"""

import pytest

from repro.core import FunctionRuntime, StatefulFunction, StateJournal
from repro.storage import (
    DramTier,
    FaultInjectingTier,
    InjectedIOError,
    PmemTier,
    StateCache,
    TornWriteError,
)


def _counter_runtime(cache, commit_every=1):
    rt = FunctionRuntime(cache=cache, commit_every=commit_every)
    rt.register(
        StatefulFunction(
            "counter", lambda s, x: (s + x, s + x), init=lambda: 0, jit=False
        )
    )
    return rt


STATE_KEY = "state/a/counter"


# -- FaultInjectingTier unit behavior -----------------------------------------

def test_fault_tier_is_deterministic_per_seed():
    def run(seed):
        tier = FaultInjectingTier(DramTier(), seed=seed, put_error_rate=0.3)
        outcomes = []
        for i in range(50):
            try:
                tier.put(f"k{i}", b"v")
                outcomes.append(True)
            except InjectedIOError:
                outcomes.append(False)
        return outcomes

    assert run(7) == run(7)
    assert run(7) != run(8)  # different schedule, same shape


def test_fault_tier_scheduled_faults_fire_exactly():
    tier = FaultInjectingTier(
        DramTier(), schedule=[("put", 1), ("get", 0)]
    )
    tier.put("a", b"1")  # put #0 ok
    with pytest.raises(InjectedIOError):
        tier.put("b", b"2")  # put #1 injected
    tier.put("c", b"3")  # put #2 ok
    with pytest.raises(InjectedIOError):
        tier.get("a")  # get #0 injected
    assert tier.get("a") == b"1"
    assert tier.injected == {"put": 1, "get": 1, "torn": 0, "spike": 0}


def test_fault_tier_torn_put_many_persists_strict_prefix():
    tier = FaultInjectingTier(DramTier(), seed=3, schedule=[("torn", 0)])
    items = {f"k{i}": bytes([i]) for i in range(8)}
    with pytest.raises(TornWriteError) as ei:
        tier.put_many(items)
    landed = ei.value.landed
    assert 0 <= landed < 8
    keys = list(items)
    for i, key in enumerate(keys):
        assert tier.contains(key) == (i < landed)
    # healed tier serves normally
    tier.heal()
    tier.put_many(items)
    assert sorted(tier.keys()) == sorted(keys)


def test_fault_tier_latency_spike_delays_but_succeeds():
    tier = FaultInjectingTier(
        DramTier(), spike_seconds=0.05, schedule=[("spike", 0)]
    )
    import time

    t0 = time.perf_counter()
    tier.put("k", b"v")
    assert time.perf_counter() - t0 >= 0.05
    assert tier.get("k") == b"v"
    assert tier.injected["spike"] == 1


# -- torn journal batches ------------------------------------------------------

def test_journal_marker_never_survives_without_details():
    """commit_many_ordered puts the summary marker last, so a torn batch
    can leave details without a marker but never the reverse."""
    wt = FaultInjectingTier(DramTier(), seed=11, schedule=[("torn", 0)])
    cache = StateCache(write_through=wt)
    journal = StateJournal(cache, "mr/job")
    entries = {f"map_0.part_{p:04d}": {"bytes": p} for p in range(6)}
    entries["map_0"] = {"task": "map_0"}
    with pytest.raises(TornWriteError):
        journal.commit_many_ordered(entries, marker="map_0")
    cache.crash()  # volatile view gone; durable view = the torn prefix
    wt.heal()
    durable = set(journal.entries())
    assert "map_0" not in durable  # marker was last — cannot have landed
    # what did land is a prefix of the detail entries
    detail_order = [f"map_0.part_{p:04d}" for p in range(6)]
    assert durable == set(detail_order[: len(durable)])


# -- the crash/recovery matrix -------------------------------------------------

def _fresh(tmp_path, kind, commit_every, fault_schedule=()):
    """(runtime, faulty_tier_or_None) for one matrix cell."""
    if kind == "dram":
        memory = FaultInjectingTier(DramTier(), schedule=fault_schedule) \
            if fault_schedule else DramTier()
        return _counter_runtime(StateCache(memory=memory), commit_every), memory
    wt = PmemTier(str(tmp_path / "pmem"))
    faulty = FaultInjectingTier(wt, schedule=fault_schedule) \
        if fault_schedule else wt
    return _counter_runtime(
        StateCache(write_through=faulty), commit_every
    ), faulty


@pytest.mark.parametrize("kind", ["dram", "pmem_wt"])
def test_matrix_crash_mid_invocation(tmp_path, kind):
    rt, _ = _fresh(tmp_path, kind, commit_every=3)
    for _ in range(4):  # commit lands after invocation 3; #4 is uncommitted
        rt.invoke("counter", session="a", x=1)
    if kind == "pmem_wt":
        committed_blob = rt.cache.write_through.get(STATE_KEY)
    rt.crash()
    rt.recover()
    if kind == "pmem_wt":
        # resumes from the last commit, byte-identically
        assert rt.cache.get(STATE_KEY) == committed_blob
        assert rt.state_report("counter", "a") == "warm"
        assert rt.session("a").seq == 3  # the seq the commit reflects + 1
        assert rt.invoke("counter", session="a", x=1) == 4
    else:
        # stock-serverless: everything since birth is gone
        assert rt.state_report("counter", "a") == "lost"
        assert rt.session("a").seq == 0
        assert rt.invoke("counter", session="a", x=1) == 1
        assert rt.log[-1].cold


@pytest.mark.parametrize("kind", ["dram", "pmem_wt"])
def test_matrix_crash_mid_commit(tmp_path, kind):
    # Each invocation issues 2 durable puts (state blob, journal marker).
    # Fail the *state* put of invocation 3 -> the commit is interrupted
    # exactly between invocations 2 and 3.
    rt, faulty = _fresh(
        tmp_path, kind, commit_every=1, fault_schedule=[("put", 4)]
    )
    for _ in range(2):
        rt.invoke("counter", session="a", x=1)
    if kind == "pmem_wt":
        committed_blob = faulty.get(STATE_KEY)
    with pytest.raises(InjectedIOError):
        rt.invoke("counter", session="a", x=1)
    rt.crash()
    faulty.heal()
    rt.recover()
    if kind == "pmem_wt":
        # the torn commit must not have corrupted the durable state: the
        # session resumes from the previous commit byte-identically
        assert rt.cache.get(STATE_KEY) == committed_blob
        assert rt.state_report("counter", "a") == "warm"
        assert rt.session("a").seq == 2
        # the value of the failed invocation 3 was recorded nowhere —
        # re-running it converges to the same result
        assert rt.invoke("counter", session="a", x=1) == 3
    else:
        assert rt.state_report("counter", "a") == "lost"
        assert rt.invoke("counter", session="a", x=1) == 1


def _hierarchy_runtime(tmp_path, commit_every=1, torn_rate=0.0):
    """Matrix extension: runtime state on a write-back TieredStore whose
    home level (PMEM) can fault, with the redo journal on its own durable
    cache — the Ignite-over-PMEM configuration of DESIGN.md §7."""
    from repro.storage import PlacementPolicy, TieredStore, TierLevel

    journal = StateCache(write_through=PmemTier(str(tmp_path / "jrnl")))
    home = PmemTier(str(tmp_path / "home"))
    faulty = (
        FaultInjectingTier(home, torn_put_many_rate=torn_rate)
        if torn_rate else home
    )
    hier = TieredStore(
        [TierLevel("dram", DramTier(), None), TierLevel("pmem", faulty)],
        policy=PlacementPolicy(write_back=True, flush_interval=0.005),
        journal=journal, name="state",
    )
    rt = _counter_runtime(StateCache(memory=hier), commit_every)
    return rt, hier, journal, faulty


def test_matrix_crash_mid_invocation_hierarchy(tmp_path):
    """Write-back hierarchy cell: commits ack at DRAM latency, yet a
    crash after the 4th (uncommitted) invocation resumes from the last
    commit byte-identically — the redo journal covers whatever the
    background flusher had not drained yet."""
    rt, hier, journal, _ = _hierarchy_runtime(tmp_path, commit_every=3)
    for _ in range(4):
        rt.invoke("counter", session="a", x=1)
    committed_blob = rt.cache.get(STATE_KEY)
    rt.crash()  # hierarchy loses its DRAM level only
    journal.crash()  # the journal's volatile view dies too
    journal.recover()
    rt.recover()
    assert rt.cache.get(STATE_KEY) == committed_blob
    assert rt.state_report("counter", "a") in ("warm", "hot")
    assert rt.session("a").seq == 3
    assert rt.invoke("counter", session="a", x=1) == 4
    hier.close()


def test_matrix_crash_mid_flush_hierarchy(tmp_path):
    """Write-back hierarchy cell, torn-flush variant: every home flush
    tears before the crash, so the acked commits exist *only* in DRAM +
    journal at crash time.  Recovery must still be byte-identical (a
    torn flush may never lose an acked write)."""
    rt, hier, journal, faulty = _hierarchy_runtime(
        tmp_path, commit_every=1, torn_rate=1.0,
    )
    for _ in range(3):
        rt.invoke("counter", session="a", x=1)
    committed_blob = rt.cache.get(STATE_KEY)
    assert hier.dirty_keys  # flusher could not drain anything
    rt.crash()
    journal.crash()
    journal.recover()
    faulty.heal()
    rt.recover()
    assert rt.cache.get(STATE_KEY) == committed_blob
    assert rt.session("a").seq == 3
    assert rt.invoke("counter", session="a", x=1) == 4
    hier.flush()
    assert hier.dirty_keys == []
    hier.close()


def test_matrix_crash_torn_group_commit(tmp_path):
    """Group-commit cell: a torn ``put_many`` mid flush round.  The batch
    is pair-adjacent (blob_a, marker_a, blob_b, marker_b, ...), so the
    strict-prefix tear can strand at most one blob without its marker
    and **never** a marker without its blob; every marker-landed session
    resumes byte-identically at its last committed state."""
    wt = PmemTier(str(tmp_path / "pmem"))
    # seed 7 tears after 5 of the 8 batch items: sessions 0-1 land both
    # blob and marker, session 2's blob is stranded without its marker,
    # session 3 loses both — all three recovery classes in one cell.
    faulty = FaultInjectingTier(wt, seed=7, schedule=[("torn", 0)])
    cache = StateCache(write_through=faulty)
    rt = FunctionRuntime(
        cache=cache, commit_every=1, group_commit=True, flush_interval=0.2
    )
    rt.register(
        StatefulFunction(
            "counter", lambda s, x: (s + x, s + x), init=lambda: 0, jit=False
        )
    )
    sessions = [f"s{i}" for i in range(4)]
    tickets, expected = {}, {}
    # deferred commits pile into one flush round (the 0.2s accumulation
    # window opens at the first enqueue; the rest land microseconds later)
    for s in sessions:
        _, rec = rt.invoke_with_record(
            "counter", session=s, defer_commit=True, x=1
        )
        tickets[s] = rec.commit_ticket
        expected[s] = rt.state_bytes("counter", s)
    for s in sessions:
        with pytest.raises(TornWriteError):
            tickets[s].wait(timeout=10)
    rt.crash()
    faulty.heal()
    blobs = {s for s in sessions if wt.contains(f"state/{s}/counter")}
    markers = {s for s in sessions if wt.contains(f"fn/done/{s}/counter")}
    # the pair-adjacency invariant on the durable prefix
    assert markers <= blobs, "a journal marker landed without its blob"
    assert len(blobs - markers) <= 1, "tear stranded more than one blob"
    # enqueue order == flush order: what landed is a session prefix
    assert sorted(blobs) == sessions[: len(blobs)]
    rt.recover()
    for s in sessions:
        if s in markers:
            # acked-at-marker sessions resume byte-identically
            assert rt.cache.get(f"state/{s}/counter") == expected[s]
            assert rt.state_report("counter", s) == "warm"
            assert rt.session(s).seq == 1
            assert rt.invoke("counter", session=s, x=1) == 2
        elif s not in blobs:
            # fully-lost sessions cold-start from scratch
            assert rt.state_report("counter", s) == "lost"
            assert rt.invoke("counter", session=s, x=1) == 1
    rt.close()


# -- mid-iteration cells: the iterative dataflow loop --------------------------

def _loop_stack(tmp_path):
    """Loop state on a write-back TieredStore with a PMEM home + durable
    redo journal, loop markers on a PMEM write-through cache — the full
    iterative-dataflow durability stack (DESIGN.md §8)."""
    from repro.storage import PlacementPolicy, TieredStore, TierLevel

    redo = StateCache(write_through=PmemTier(str(tmp_path / "redo")))
    store = TieredStore(
        [
            TierLevel("dram", DramTier(), None),
            TierLevel("pmem", PmemTier(str(tmp_path / "home"))),
        ],
        policy=PlacementPolicy(write_back=True, flush_interval=0.002),
        journal=redo, name="loop",
    )
    journal = StateCache(write_through=PmemTier(str(tmp_path / "jrnl")))
    return store, redo, journal


def _crash_loop_stack(store, redo, journal):
    store.crash()  # DRAM level gone
    journal.crash()
    journal.recover()  # loop markers back from PMEM
    redo.crash()
    redo.recover()  # redo records back from PMEM
    store.recover()  # acked-unflushed state replayed


@pytest.mark.parametrize("workload", ["pagerank", "kmeans"])
@pytest.mark.parametrize("cell", ["between_supersteps", "mid_superstep"])
def test_matrix_crash_mid_iteration(tmp_path, cell, workload):
    """Matrix extension: kill an iterative dataflow job {between
    supersteps, mid-superstep (partial next-version state, no marker)} —
    the journal-resumed run recomputes nothing that committed and its
    final output is byte-identical to an uninterrupted run."""
    import numpy as np

    from repro.core import Scheduler
    from repro.core.workloads import (
        kmeans_loop, kmeans_points, pagerank_graph, pagerank_loop,
    )

    def sched():
        return Scheduler(["w0", "w1"], speculation_factor=None)

    if workload == "pagerank":
        src, dst = pagerank_graph(90, 500, seed=21)

        def run(state, journal, **kw):
            res = pagerank_loop(
                "mx", state, src, dst, 90, n_parts=2, tol=0.0,
                max_iterations=5, journal=journal, scheduler=sched(), **kw
            )
            return res.report, res.rank_bytes
    else:
        pts, _ = kmeans_points(160, 2, 3, seed=22)

        def run(state, journal, **kw):
            res = kmeans_loop(
                "mx", state, pts, 3, n_parts=2, tol=0.0,
                max_iterations=5, journal=journal, scheduler=sched(), **kw
            )
            return res.report, res.centroid_bytes

    _, golden_bytes = run(DramTier(), None)
    store, redo, journal = _loop_stack(tmp_path)
    try:
        first, _ = run(store, journal, halt_after=3)
        assert first.last_iteration == 2  # init + 2 supersteps committed
        if cell == "mid_superstep":
            # superstep 3 died after (some) state landed, before its marker
            store.put("df/mx/state/it00003/partial", b"garbage")
        _crash_loop_stack(store, redo, journal)
        second, got_bytes = run(store, journal)
        assert second.resumed_iterations == first.iterations
        assert second.last_iteration == 5
        assert got_bytes == golden_bytes
    finally:
        store.close()


def test_serde_state_roundtrip_is_byte_identical(tmp_path):
    """The byte-identical recovery claim requires dumps(loads(x)) == x —
    including NamedTuple nodes (attention KV caches), which a previous
    serde version silently collapsed into plain tuples."""
    import jax.numpy as jnp

    from repro.models.attention import AttnCache
    from repro.storage import serde

    state = {
        "cache": AttnCache(
            jnp.arange(12.0).reshape(1, 3, 2, 2),
            jnp.ones((1, 3, 2, 2)),
        ),
        "t": 3,
        "nested": [(1, 2), None],
    }
    blob = serde.dumps(state)
    restored = serde.loads(blob)
    assert isinstance(restored["cache"], AttnCache)
    assert serde.dumps(restored) == blob


# -- a commit made durable once -------------------------------------------------

def test_acked_group_commit_survives_a_crash_without_a_redo_record(tmp_path):
    """The runtime's state rides a write-back DRAM→PMEM hierarchy under a
    persistent write-through: an acked group commit is durable in the
    write-through alone.  The home flush never lands (every one tears)
    and no redo record is written, yet a crash loses nothing acked."""
    from repro.api import ClusterConfig, FaultSpec, MarvelClient, TierSpec

    cfg = ClusterConfig(
        name="once",
        tiers=(TierSpec("dram", capacity_bytes=1 << 20),
               TierSpec("pmem", path=str(tmp_path / "home"))),
        invokers=1, commit_every=1, journal="pmem",
        journal_path=str(tmp_path / "journal"),
        faults=FaultSpec(torn_put_many_rate=1.0))
    with MarvelClient(cfg) as client:
        client.function("counter", init=lambda: 0, jit=False)(
            lambda s, x: (s + x, s + x))
        for _ in range(3):  # each returns once its commit is durable
            client.invoke("counter", session="a", x=1)
        rt, store = client.runtime, client.state
        acked = rt.state_bytes("counter", "a")
        assert STATE_KEY in store.dirty_keys  # the home flush never landed
        assert store._journal.entries() == {}  # nor did any redo record
        rt.crash()
        store.levels[-1].tier.heal()
        rt.recover()
        assert rt.cache.get(STATE_KEY) == acked
        assert rt.session("a").seq == 3
        assert client.invoke("counter", session="a", x=1) == 4


def test_torn_write_through_fails_the_ticket_and_replays_nothing(tmp_path):
    """The write-through is written before the hierarchy sees the put:
    a torn write-through fails the ticket with nothing newer in DRAM and
    no redo record, so a crash brings back the last acked state, not
    the failed one."""
    from repro.storage import PlacementPolicy, TieredStore, TierLevel

    # seed 1 tears the second round before either item lands
    durable = FaultInjectingTier(PmemTier(str(tmp_path / "journal")),
                                 seed=1, schedule=[("torn", 1)])
    store = TieredStore(
        [TierLevel("dram", DramTier(), None),
         TierLevel("pmem", PmemTier(str(tmp_path / "home")))],
        policy=PlacementPolicy(write_back=True, flush_interval=0.005),
        journal=StateCache(write_through=durable), name="state")
    rt = FunctionRuntime(cache=StateCache(memory=store, write_through=durable),
                         group_commit=True)
    rt.register(StatefulFunction(
        "counter", lambda s, x: (s + x, s + x), init=lambda: 0, jit=False))
    _, rec = rt.invoke_with_record("counter", session="a",
                                   defer_commit=True, x=1)
    rec.commit_ticket.wait(timeout=10)
    acked = durable.get(STATE_KEY)
    _, rec = rt.invoke_with_record("counter", session="a",
                                   defer_commit=True, x=1)
    with pytest.raises(TornWriteError):
        rec.commit_ticket.wait(timeout=10)
    assert durable.get(STATE_KEY) == acked  # nothing of the round landed
    assert store.get(STATE_KEY) == acked  # nor reached DRAM
    assert store.unjournaled_puts == 2  # the acked round's blob and marker
    rt.crash()
    durable.heal()
    assert store._journal.entries() == {}
    assert rt.recover() == 2  # the write-through's two keys, no replay
    assert rt.cache.get(STATE_KEY) == acked
    assert rt.session("a").seq == 1
    assert rt.invoke("counter", session="a", x=1) == 2
    rt.close()
    store.close()
