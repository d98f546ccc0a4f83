"""The in-program span recorder (``repro.obs``) and the spans the serving
path records with it: nesting, request ids carried across threads, the
tier counters spans carry, the bounded ring and the bounded runtime log."""

import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api import ClusterConfig, MarvelClient, ServingConfig, TierSpec
from repro.configs import get_config
from repro.core.stateful import FunctionRuntime
from repro.models import init_cache, init_params, model_defs, reduced_for_smoke
from repro.storage import (
    DramTier,
    PlacementPolicy,
    PmemTier,
    StateCache,
    TieredStore,
    TierLevel,
)


def _mine(since, prefix):
    return [s for s in obs.spans(since) if s.name.startswith(prefix)]


def test_spans_nest_under_the_enclosing_span_and_inherit_its_request():
    t = time.perf_counter()
    with obs.span("test.outer", rid=7, keys=2) as attrs:
        attrs["bytes"] = 10
        with obs.span("test.inner"):
            assert obs.request() == 7
        obs.record("test.recorded", t, t + 1e-3, 9, round=3)
    assert obs.request() is None
    got = {s.name: s for s in _mine(t, "test.")}
    outer, inner, rec = got["test.outer"], got["test.inner"], got["test.recorded"]
    assert outer.parent == 0 and outer.rid == 7
    assert outer.attrs == {"keys": 2, "bytes": 10}
    assert inner.parent == outer.sid and inner.rid == 7
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert rec.parent == outer.sid and rec.rid == 9 and rec.attrs == {"round": 3}
    assert abs(rec.t1 - rec.t0 - 1e-3) < 1e-9
    assert {s.thread for s in got.values()} == {threading.get_ident()}


def test_a_span_on_another_thread_starts_without_a_parent():
    t = time.perf_counter()
    with obs.span("test.main", rid=1):
        worker = []

        def work():
            with obs.span("test.other"):
                worker.append(obs.request())

        th = threading.Thread(target=work)
        th.start()
        th.join()
    other = _mine(t, "test.other")[0]
    assert worker == [None] and other.parent == 0 and other.rid is None


def test_threads_keep_their_own_stacks_and_lose_no_span():
    threads, per = 16, 500
    t = time.perf_counter()
    before, kept = obs.dropped(), len(obs.spans())
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(i):
            for _ in range(per // 2):
                with obs.span("test.stress.outer", rid=i):
                    with obs.span("test.stress.inner"):
                        pass

        pool = [threading.Thread(target=work, args=(i,)) for i in range(threads)]
        for th in pool:
            th.start()
        for th in pool:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in pool)
    finally:
        sys.setswitchinterval(switch)
    got = _mine(t, "test.stress.")
    assert len(got) == threads * per
    assert obs.dropped() - before == max(0, kept + threads * per - obs.RING)
    outer = {s.sid: s for s in got if s.name == "test.stress.outer"}
    for s in got:
        if s.name == "test.stress.inner":
            parent = outer[s.parent]
            assert parent.thread == s.thread and parent.rid == s.rid


def test_the_ring_counts_what_falls_off_it():
    t = time.perf_counter()
    before = obs.dropped()
    for _ in range(obs.RING + 5):
        obs.record("test.flood", t, t)
    assert obs.dropped() >= before + 5
    assert not obs.kept_since(t - 1.0)
    assert obs.kept_since(t + 1.0)
    assert len(obs.spans()) == obs.RING


def test_lock_wait_is_timed_only_when_another_thread_holds_the_lock():
    store = TieredStore([TierLevel("dram", DramTier(), None)], name="lw")
    t = time.perf_counter()
    store.put("a", b"x")
    assert not _mine(t, "tier.lock_wait")
    held = threading.Event()

    def hold():
        with store._mutex:
            held.set()
            time.sleep(0.05)

    th = threading.Thread(target=hold)
    th.start()
    held.wait()
    store.put_many({"b": b"yy", "c": b"zzz"})
    th.join()
    put = [s for s in _mine(t, "tier.put") if s.attrs["keys"] == 2][0]
    wait = _mine(t, "tier.lock_wait")[0]
    assert put.attrs["bytes"] == 5
    assert wait.parent == put.sid and wait.t1 - wait.t0 >= 0.03
    assert wait.attrs == {"store": "lw"}
    # a put of a key whose redo record another put is still writing
    # waits for it, as a lock wait named for the redo
    journaled = TieredStore(
        [TierLevel("dram", DramTier(), None), TierLevel("home", DramTier())],
        policy=PlacementPolicy(write_back=True), journal=StateCache(),
        name="lr")
    with journaled._mutex:
        journaled._redo_pending.add("k")

    def land():
        time.sleep(0.05)
        with journaled._redo_landed:
            journaled._redo_pending.discard("k")
            journaled._redo_landed.notify_all()

    th = threading.Thread(target=land)
    th.start()
    t = time.perf_counter()
    journaled.put("k", b"v")
    th.join()
    journaled.close()
    put = [s for s in _mine(t, "tier.put")
           if s.thread == threading.get_ident()][0]
    wait = [s for s in _mine(t, "tier.lock_wait") if s.parent == put.sid]
    assert len(wait) == 1 and wait[0].t1 - wait[0].t0 >= 0.03
    assert wait[0].attrs == {"store": "lr", "lock": "redo"}
    assert journaled.redo_order_waits == 1


def test_journal_put_carries_the_fsyncs_it_issued(tmp_path):
    pmem = PmemTier(str(tmp_path / "journal"))
    store = TieredStore(
        [TierLevel("dram", DramTier(), None), TierLevel("home", DramTier())],
        policy=PlacementPolicy(write_back=True),
        journal=StateCache(write_through=pmem), name="js")
    t = time.perf_counter()
    store.put_many({"a": b"1", "b": b"22", "c": b"333"})
    store.close()
    jp = _mine(t, "tier.journal_put")[0]
    # three redo blobs and three markers, each one fsync'd file
    assert jp.attrs == {"keys": 3, "bytes": 6, "syncs": 6}
    assert pmem.stats.syncs == 6


def test_the_runtime_log_keeps_the_most_recent_records(monkeypatch):
    monkeypatch.setattr(FunctionRuntime, "LOG_RECORDS", 8)
    rt = FunctionRuntime()

    @rt.function("count", init=lambda: {"n": 0}, jit=False)
    def count(state):
        return {"n": state["n"] + 1}, state["n"]

    for _ in range(20):
        rt.invoke("count", session="s")
    assert len(rt.log) == 8
    assert [r.seq for r in rt.log] == list(range(12, 20))


def _model():
    cfg = reduced_for_smoke(get_config("qwen2.5-3b"))
    return cfg, init_params(model_defs(cfg), jax.random.PRNGKey(0))


def test_a_request_is_followed_from_submit_to_its_durable_ack(tmp_path):
    cfg, params = _model()
    cluster = ClusterConfig(
        name="obs", tiers=(TierSpec("dram", capacity_bytes=8 << 20),
                           TierSpec("pmem", path=str(tmp_path / "home"))),
        invokers=2, commit_every=1, journal="pmem",
        journal_path=str(tmp_path / "journal"),
        serving=ServingConfig(block_tokens=8, lossless=True))
    with MarvelClient(cluster) as client:
        pool = client.serving(params, cfg, prompt_len=8, max_tokens=8)
        prompt = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 0, cfg.vocab)
        np.asarray(pool.start("c", prompt).result())
        t = time.perf_counter()
        np.asarray(pool.step("c").result())
        client.gateway.quiesce(5.0)
        waits = [w for st in client.gateway._stripes for w in st.waits]
    mine = obs.spans(t)
    queue = [s for s in mine if s.name == "gateway.queue"]
    assert len(queue) == 1
    rid = queue[0].rid
    assert queue[0].t1 - queue[0].t0 in waits  # the lane wait's own numbers
    step = {s.name: s for s in mine if s.rid == rid}
    assert {"gateway.queue", "gateway.dispatch", "runtime.invoke",
            "serve.decode", "serve.device_wait", "pager.load", "pager.write",
            "pager.serialize", "tier.put", "tier.journal_put",
            "runtime.commit", "gateway.ack"} <= set(step)
    by_sid = {s.sid: s for s in mine}

    def chain(s):
        out = []
        while s.parent:
            s = by_sid[s.parent]
            out.append(s.name)
        return out

    assert chain(step["tier.journal_put"]) == [
        "tier.put", "pager.write", "serve.decode", "runtime.invoke",
        "gateway.dispatch"]
    assert chain(step["runtime.commit"]) == ["runtime.invoke", "gateway.dispatch"]
    dispatch, ack = step["gateway.dispatch"], step["gateway.ack"]
    assert queue[0].t1 <= dispatch.t0 and dispatch.t1 <= ack.t0
    assert step["pager.serialize"].attrs["blocks"] > 0
    assert step["pager.write"].attrs["bytes"] == \
        step["pager.serialize"].attrs["bytes"]
    # the group committer's flush names the request it settles, from its
    # own thread, and the ack came when it landed
    flush = [s for s in mine if s.name == "journal.flush"
             and rid in s.attrs["rids"]]
    assert len(flush) == 1 and flush[0].rid is None
    assert flush[0].thread != dispatch.thread
    assert flush[0].attrs["syncs"] > 0 and ack.t1 >= flush[0].t1
    assert ack.attrs["round"] == flush[0].attrs["entries"]


def test_the_decode_step_program_has_a_name_of_its_own():
    from chipbench.drivers.serve import module_name
    from repro.serving import PagedDecoder

    cfg, params = _model()
    dec = PagedDecoder(params, cfg, pager=None, prompt_len=4, max_tokens=4)
    assert module_name(dec._decode) == "jit_paged_decode_step"
    lowered = dec._decode.lower(params, jnp.zeros((1, 1), jnp.int32),
                                init_cache(cfg, 1, 8), jnp.int32(4))
    assert "@jit_paged_decode_step" in lowered.as_text()
    assert "jit__lambda" not in lowered.as_text()
