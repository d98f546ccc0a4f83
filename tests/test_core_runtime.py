"""Stateful function runtime + task scheduler."""

import time

import jax.numpy as jnp
import pytest

from repro import obs
from repro.api import ClusterConfig, MarvelClient, TierSpec
from repro.core import FunctionRuntime, Scheduler, Task, TaskFailedError
from repro.models.attention import AttnCache
from repro.serving import KVPager
from repro.storage import DramTier, PmemTier, StateCache


def _counter_runtime(tmp_path=None):
    cache = StateCache(
        write_through=PmemTier(str(tmp_path)) if tmp_path else DramTier()
    )
    rt = FunctionRuntime(cache=cache)

    @rt.function("counter", init=lambda start=0: jnp.int32(start))
    def step(state, x):
        new = state + x
        return new, new

    return rt


def test_stateful_invocations_accumulate():
    rt = _counter_runtime()
    assert int(rt.invoke("counter", x=jnp.int32(5))) == 5
    assert int(rt.invoke("counter", x=jnp.int32(2))) == 7
    assert rt.log[0].cold and not rt.log[1].cold


def test_sessions_isolate_state():
    rt = _counter_runtime()
    rt.invoke("counter", session="a", x=jnp.int32(10))
    rt.invoke("counter", session="b", x=jnp.int32(1))
    assert int(rt.invoke("counter", session="a", x=jnp.int32(0))) == 10
    assert int(rt.invoke("counter", session="b", x=jnp.int32(0))) == 1


def test_init_kwargs_cold_start():
    rt = _counter_runtime()
    out = rt.invoke("counter", init_kwargs={"start": 100}, x=jnp.int32(1))
    assert int(out) == 101


def test_crash_recovery_with_write_through(tmp_path):
    rt = _counter_runtime(tmp_path)
    rt.invoke("counter", x=jnp.int32(41))
    rt.commit_all()
    rt.crash()
    rt.recover()
    assert int(rt.invoke("counter", x=jnp.int32(1))) == 42


def test_crash_without_persistence_loses_state():
    rt = FunctionRuntime(cache=StateCache())  # stock stateless-serverless

    @rt.function("c", init=lambda: jnp.int32(0))
    def step(state, x):
        return state + x, state + x

    rt.invoke("c", x=jnp.int32(5))
    rt.crash()
    # state re-initialized from scratch — computation lost (paper §1)
    assert int(rt.invoke("c", x=jnp.int32(1))) == 1


def test_commit_every_controls_durability(tmp_path):
    cache = StateCache(write_through=PmemTier(str(tmp_path)))
    rt = FunctionRuntime(cache=cache, commit_every=3)

    @rt.function("c", init=lambda: jnp.int32(0))
    def step(state, x):
        return state + x, state + x

    for _ in range(2):
        rt.invoke("c", x=jnp.int32(1))
    rt.crash()
    # only 2 invocations — below commit_every, nothing durable yet
    assert int(rt.invoke("c", x=jnp.int32(1))) == 1


def test_invocation_seq_is_per_session():
    """Regression: seq used to record the *global* log position; recovery
    ordering must be per-session."""
    rt = _counter_runtime()
    rt.invoke("counter", session="a", x=jnp.int32(1))
    rt.invoke("counter", session="b", x=jnp.int32(1))
    rt.invoke("counter", session="a", x=jnp.int32(1))
    rt.invoke("counter", session="b", x=jnp.int32(1))
    seqs = {(r.session, r.seq) for r in rt.log}
    assert seqs == {("a", 0), ("a", 1), ("b", 0), ("b", 1)}


def test_session_object_wires_invocations():
    rt = _counter_runtime()
    sess = rt.session("chat")
    assert int(sess.invoke("counter", x=jnp.int32(3))) == 3
    assert int(sess.invoke("counter", x=jnp.int32(4))) == 7
    assert sess.seq == 2
    assert rt.session("chat") is sess


def test_session_seq_resumes_from_journal_after_crash(tmp_path):
    """Per-session sequence survives a crash via the unified journal."""
    rt = _counter_runtime(tmp_path)
    for _ in range(3):
        rt.invoke("counter", session="a", x=jnp.int32(1))
    rt.invoke("counter", session="b", x=jnp.int32(5))
    rt.crash()
    rt.recover()
    # sessions rebuild from committed journal entries, not from zero
    assert rt.session("a").seq == 3
    assert rt.session("b").seq == 1
    rt.invoke("counter", session="a", x=jnp.int32(1))
    assert rt.log[-1].seq == 3 and rt.log[-1].session == "a"


# -- a group commit made durable once ----------------------------------------

def _pmem_client(tmp_path):
    """Runtime state on a write-back DRAM→PMEM hierarchy with a PMEM
    journal: the stack ``MarvelClient`` builds for serving."""
    client = MarvelClient(ClusterConfig(
        name="once",
        tiers=(TierSpec("dram", capacity_bytes=1 << 20),
               TierSpec("pmem", path=str(tmp_path / "home"))),
        invokers=1, commit_every=1, journal="pmem",
        journal_path=str(tmp_path / "journal")))
    client.function("counter", init=lambda: 0, jit=False)(
        lambda s, x: (s + x, s + x))
    return client


def test_a_group_commit_round_is_made_durable_once(tmp_path):
    """A one-entry round writes its blob and marker to the PMEM journal,
    one fsync each, and hands them to the hierarchy without a redo
    record of its own."""
    with _pmem_client(tmp_path) as client:
        client.invoke("counter", session="a", x=1)  # the cold start
        durable = client.runtime.cache.write_through
        syncs = durable.stats.syncs
        t = time.perf_counter()
        assert client.invoke("counter", session="a", x=1) == 2  # acked
        flush = [s for s in obs.spans(t) if s.name == "journal.flush"]
        assert len(flush) == 1
        assert flush[0].attrs["entries"] == 1
        assert flush[0].attrs["syncs"] == 2
        assert not [s for s in obs.spans(t) if s.name == "tier.journal_put"]
        assert durable.stats.syncs - syncs == 2
        assert client.state.unjournaled_puts == 4  # two rounds of two keys
        assert client.state._journal.entries() == {}


def test_a_pager_write_on_the_same_store_keeps_its_redo_record(tmp_path):
    """The pager's blocks go to the hierarchy alone, so they keep the
    write-back redo log: a redo blob and a marker a key, one fsync each."""
    layers = [AttnCache(k=jnp.full((1, 8, 2, 4), float(i)),
                        v=jnp.full((1, 8, 2, 4), -float(i)))
              for i in range(2)]
    with _pmem_client(tmp_path) as client:
        pager = KVPager(client.state, block_tokens=4, lossless=True)
        pager.create("c", layers, t=0)
        t = time.perf_counter()
        pager.write("c", layers, t=5)
        puts = [s for s in obs.spans(t) if s.name == "tier.journal_put"]
        assert len(puts) == 1
        # a block of each of the two layers, and the meta record
        assert puts[0].attrs["keys"] == 3 and puts[0].attrs["syncs"] == 6
        assert client.state.unjournaled_puts == 0


# -- scheduler ---------------------------------------------------------------

def test_scheduler_runs_all_tasks():
    sched = Scheduler(["w0", "w1"], speculation_factor=None)
    tasks = [Task(f"t{i}", lambda w, i=i: i * 2) for i in range(10)]
    res = sched.run_wave(tasks)
    assert sorted(r.value for r in res.values()) == [i * 2 for i in range(10)]


def test_scheduler_retries_transient_failures():
    attempts = {"n": 0}

    def flaky(worker):
        attempts["n"] += 1
        if attempts["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    sched = Scheduler(["w0"], max_attempts=3, speculation_factor=None)
    res = sched.run_wave([Task("t", flaky)])
    assert res["t"].value == "ok"
    assert res["t"].attempts == 3


def test_scheduler_permanent_failure_raises():
    def broken(worker):
        raise RuntimeError("always")

    sched = Scheduler(["w0"], max_attempts=2, speculation_factor=None)
    with pytest.raises(TaskFailedError):
        sched.run_wave([Task("t", broken)])


def test_scheduler_speculation_beats_straggler():
    calls = {"n": 0}

    def task(worker):
        calls["n"] += 1
        if calls["n"] == 1:  # first attempt is a straggler
            time.sleep(2.0)
            return "slow"
        return "fast"

    sched = Scheduler(
        ["w0", "w1"], speculation_factor=1.5, min_speculation_seconds=0.02
    )
    fast = [Task(f"f{i}", lambda w: "ok") for i in range(4)]
    t0 = time.perf_counter()
    res = sched.run_wave(fast + [Task("straggler", task)])
    dt = time.perf_counter() - t0
    assert res["straggler"].value in ("fast", "slow")
    # the backup attempt should win well before the 2 s straggler finishes
    assert dt < 1.8
    assert res["straggler"].speculative_win or res["straggler"].value == "fast"


def test_scheduler_elastic_pool():
    sched = Scheduler(["w0"], speculation_factor=None)
    sched.add_workers(["w1", "w2"])
    assert len(sched.workers) == 3
    sched.remove_workers(["w0"])
    res = sched.run_wave([Task("t", lambda w: w)])
    assert res["t"].worker in ("w1", "w2")


def test_scheduler_locality_preference():
    sched = Scheduler(["w0", "w1", "w2"], speculation_factor=None)
    res = sched.run_wave([Task("t", lambda w: w, preferred=["w2"])])
    assert res["t"].worker == "w2"
