"""Façade tests: golden equivalence vs the engine entry points, unified
report schema, lazy dataset lowering, and client lifecycle.

The golden-equivalence suite: every path through
:class:`repro.api.MarvelClient` must produce byte-identical outputs to
the engine's own ``run_job`` / ``run_stages`` / ``run_loop`` called on
the same inputs.
"""

import numpy as np
import pytest

from repro.api import (
    ClientClosedError,
    ClusterConfig,
    ConfigError,
    FaultSpec,
    JobReport,
    MarvelClient,
    TierSpec,
    unify_report,
)
from repro.core import Scheduler, run_job, run_loop, run_stages
from repro.core.dataflow import Stage, StageTask
from repro.core.mapreduce import wordcount_job
from repro.core.workloads import (
    kmeans_loop,
    kmeans_points,
    pagerank_graph,
    pagerank_loop,
    terasort,
    terasort_output,
)
from repro.storage import BlockStore, DataNode, DramTier


def _corpus(n_lines=60, seed=0):
    rng = np.random.default_rng(seed)
    words = [f"w{i:02d}".encode() for i in range(20)]
    return b"\n".join(
        b" ".join(rng.choice(words, size=5)) for _ in range(n_lines)
    )


def _engine_cluster(n=4, block_size=1 << 12):
    nodes = [DataNode(f"w{i}", DramTier()) for i in range(n)]
    store = BlockStore(nodes, block_size=block_size, replication=2)
    sched = Scheduler([nd.node_id for nd in nodes], speculation_factor=None)
    return store, sched


def _read_parts(store, path, n):
    return [
        store.read(f"{path}/part_{p:04d}")
        for p in range(n)
        if store.exists(f"{path}/part_{p:04d}")
    ]


def wc_map(rec):
    for w in rec.split():
        yield (w, 1)


def wc_reduce(k, vs):
    yield (k, sum(vs))


# -- golden equivalence --------------------------------------------------------

class TestGoldenEquivalence:
    """The engine functions (``run_job``/``run_stages``/``run_loop`` and the
    workloads built on them) against the façade: same inputs, same bytes.

    The tests keep the names they had when the engine was reached through
    deprecated wrappers ("legacy", "shim"), so each one's record carries on.
    """

    def test_dataset_matches_legacy_run_job(self):
        data = _corpus()
        store, sched = _engine_cluster()
        store.write("/in", data, record_delim=b"\n")
        run_job(wordcount_job(4), store, "/in", "/out", DramTier(), sched)
        golden = _read_parts(store, "/out", 4)
        assert golden, "engine run produced no output"

        with MarvelClient(ClusterConfig(name="eq", tiers=("dram",))) as c:
            handle = (
                c.dataset([data], name="wc")
                .map(wc_map)
                .shuffle(partitions=4)
                .reduce(wc_reduce)
                .combine(wc_reduce)
                .run()
            )
            got = _read_parts(c.store, handle.result, 4)
        assert got == golden

    def test_mapreduce_method_matches_legacy_same_stack(self):
        """Same store/tier through both entry points → identical bytes."""
        data = _corpus(seed=3)
        store, sched = _engine_cluster()
        store.write("/in", data, record_delim=b"\n")
        run_job(wordcount_job(4), store, "/in", "/engine", DramTier(), sched)
        with MarvelClient(ClusterConfig(name="eqmr", tiers=("dram",))) as c:
            c.mapreduce(wordcount_job(4), "/in", "/facade", store=store,
                        intermediate=DramTier())
        assert _read_parts(store, "/facade", 4) == \
            _read_parts(store, "/engine", 4)

    def test_stages_matches_legacy_run_stages(self):
        rng = np.random.default_rng(5)
        parts = [
            b"\n".join(rng.bytes(8).hex().encode() for _ in range(40))
            for _ in range(3)
        ]
        engine_state = DramTier()
        terasort("ts", engine_state, parts, n_ranges=3)
        golden = terasort_output(engine_state, "ts", 3)

        with MarvelClient(ClusterConfig(name="eqts", tiers=("dram",))) as c:
            handle = c.terasort("ts", parts, n_ranges=3)
        assert handle.result == golden
        assert handle.report.kind == "stages"

    def test_iterate_matches_legacy_run_loop(self):
        src, dst = pagerank_graph(n_nodes=120, n_edges=700, seed=9)
        engine = pagerank_loop(
            "pr", DramTier(), src, dst, 120, tol=1e-8,
            max_iterations=8, pin_state=False,
        )
        with MarvelClient(ClusterConfig(name="eqpr", tiers=("dram",))) as c:
            handle = c.pagerank("pr", src, dst, 120, tol=1e-8,
                                max_iterations=8, pin_state=False)
        assert handle.result.rank_bytes == engine.rank_bytes
        assert handle.report.kind == "loop"
        assert handle.report.iterations == engine.report.iterations

    def test_kmeans_matches_legacy(self):
        pts, _ = kmeans_points(n_points=120, dim=3, k=4, seed=2)
        engine = kmeans_loop("km", DramTier(), pts, 4, tol=1e-9,
                             max_iterations=10, pin_state=False)
        with MarvelClient(ClusterConfig(name="eqkm", tiers=("dram",))) as c:
            handle = c.kmeans("km", pts, 4, tol=1e-9, max_iterations=10,
                              pin_state=False)
        assert handle.result.centroid_bytes == engine.centroid_bytes

    def test_raw_run_stages_shim_delegates(self):
        """Engine ``run_stages`` and ``client.stages`` give the same result."""

        def stages(state):
            def t1(_):
                state.put("x", b"1")
                return {}

            def t2(_):
                return {"v": state.get("x")}

            return [
                Stage("a", [StageTask("t1", t1, outputs=["x"])]),
                Stage("b", [StageTask("t2", t2)]),
            ]

        state = DramTier()
        rep = run_stages("s", stages(state), state)
        assert rep.result("t2").value["v"] == b"1"
        with MarvelClient(ClusterConfig(name="eqst", tiers=("dram",))) as c:
            facade = DramTier()
            got = c.stages("s", stages(facade), state=facade).raw
        assert got.result("t2").value == rep.result("t2").value

    def test_raw_run_loop_shim_delegates(self):
        """Engine ``run_loop`` and ``client.iterate`` converge identically."""

        def init(ctx):
            ctx.write("v", b"\x00")

        def superstep(ctx):
            def bump(_):
                ctx.write("v", bytes([ctx.read("v")[0] + 1]))
                return {}

            return [Stage("s", [StageTask("bump", bump)])]

        def until(ctx):
            return ctx.read_current("v")[0] >= 3

        state = DramTier()
        rep = run_loop("l", init, superstep, until, state, pin_state=False)
        assert rep.converged
        assert state.get("df/l/state/it00003/v") == b"\x03"
        with MarvelClient(ClusterConfig(name="eqlp", tiers=("dram",))) as c:
            facade = DramTier()
            got = c.iterate("l", init=init, superstep=superstep, until=until,
                            state=facade, pin_state=False).raw
        assert got.converged and got.iterations == rep.iterations
        assert facade.get("df/l/state/it00003/v") == b"\x03"


# -- journaled resume through the façade --------------------------------------

class TestFacadeResume:
    def test_dataset_journaled_resume(self):
        data = _corpus(seed=7)
        cfg = ClusterConfig(name="res", tiers=("dram",))
        with MarvelClient(cfg) as c:
            ds = (
                c.dataset([data], name="wc")
                .map(wc_map).shuffle(partitions=3).reduce(wc_reduce)
            )
            h1 = ds.run()
            first = _read_parts(c.store, h1.result, 3)
            h2 = ds.run()  # same journal, same job name → full resume
            assert h2.report.resumed_tasks == h2.report.tasks
            assert _read_parts(c.store, h2.result, 3) == first

    def test_iterate_journal_resume_byte_identical(self):
        src, dst = pagerank_graph(n_nodes=80, n_edges=500, seed=4)
        with MarvelClient(ClusterConfig(name="resl", tiers=("dram",))) as c:
            partial = c.pagerank("pr", src, dst, 80, tol=0.0,
                                 max_iterations=6, pin_state=False,
                                 halt_after=3)
            assert not partial.report.converged
            done = c.pagerank("pr", src, dst, 80, tol=0.0,
                              max_iterations=6, pin_state=False)
            assert done.report.extra["resumed_iterations"] > 0
        with MarvelClient(ClusterConfig(name="resg", tiers=("dram",))) as c:
            golden = c.pagerank("pr", src, dst, 80, tol=0.0,
                                max_iterations=6, pin_state=False)
        assert done.result.rank_bytes == golden.result.rank_bytes


# -- unified report schema -----------------------------------------------------

class TestUnifiedReport:
    def test_field_accessor_fails_loudly(self):
        rep = JobReport(job="j", kind="stages", wall_seconds=1.0)
        assert rep.field("wall_seconds") == 1.0
        assert rep.field("total_seconds") == rep.total_seconds
        with pytest.raises(KeyError, match="unknown JobReport field"):
            rep.field("walls_seconds")

    def test_unify_rejects_unknown_shapes(self):
        with pytest.raises(TypeError):
            unify_report(object())

    def test_all_kinds_share_schema(self):
        data = _corpus(seed=1)
        src, dst = pagerank_graph(n_nodes=60, n_edges=300, seed=1)
        with MarvelClient(ClusterConfig(name="sch", tiers=("dram",))) as c:
            handles = [
                c.dataset([data], name="wc").map(wc_map)
                .shuffle(partitions=2).reduce(wc_reduce).run(),
                c.terasort("ts", [data], n_ranges=2),
                c.pagerank("pr", src, dst, 60, tol=1e-6, max_iterations=4,
                           pin_state=False),
            ]
        kinds = {h.report.kind for h in handles}
        assert kinds == {"mapreduce", "stages", "loop"}
        for h in handles:
            d = h.report.to_dict()
            for key in ("wall_seconds", "modeled_io_seconds",
                        "total_seconds", "tasks", "resumed_tasks",
                        "iterations", "tiers"):
                assert key in d, (h.report.kind, key)
            assert h.report.tiers, "tier rollup missing"


# -- dataset plan validation ---------------------------------------------------

class TestDatasetPlan:
    def test_lazy_until_run(self):
        with MarvelClient(ClusterConfig(name="lazy")) as c:
            ds = c.dataset([b"a b"], name="n").map(wc_map)
            assert not c.store.exists("/api/n/in")  # nothing ran yet
            with pytest.raises(ConfigError, match="reduce"):
                ds.run()

    def test_requires_mapper(self):
        with MarvelClient(ClusterConfig(name="nomap")) as c:
            with pytest.raises(ConfigError, match="map"):
                c.dataset([b"x"], name="n").reduce(wc_reduce).run()

    def test_shuffle_by_rekeys(self):
        with MarvelClient(ClusterConfig(name="rekey")) as c:
            out = (
                c.dataset([b"aa ab ba"], name="n")
                .map(wc_map)
                .shuffle(by=lambda k: k[:1], partitions=2)
                .reduce(wc_reduce)
                .collect()
            )
        assert sorted(out) == sorted([b"b'a'\t2", b"b'b'\t1"])

    def test_anonymous_datasets_get_distinct_names(self):
        with MarvelClient(ClusterConfig(name="anon")) as c:
            a = (c.dataset([b"aaa bbb"]).map(wc_map)
                 .shuffle(partitions=2).reduce(wc_reduce))
            b = (c.dataset([b"ccc ddd"]).map(wc_map)
                 .shuffle(partitions=2).reduce(wc_reduce))
            assert a.name != b.name
            assert sorted(b.collect()) == sorted(
                [b"b'ccc'\t1", b"b'ddd'\t1"]
            )

    def test_same_name_different_input_refused(self):
        with MarvelClient(ClusterConfig(name="clash")) as c:
            (c.dataset([b"aaa"], name="n").map(wc_map)
             .shuffle(partitions=1).reduce(wc_reduce).run())
            with pytest.raises(ConfigError, match="different.*input"):
                (c.dataset([b"bbb"], name="n").map(wc_map)
                 .shuffle(partitions=1).reduce(wc_reduce).run())

    def test_plan_immutable(self):
        with MarvelClient(ClusterConfig(name="imm")) as c:
            base = c.dataset([b"x"], name="n")
            mapped = base.map(wc_map)
            assert base.mapper is None and mapped.mapper is wc_map
            with pytest.raises(ConfigError, match="already has a mapper"):
                mapped.map(wc_map)


# -- lifecycle -----------------------------------------------------------------

class TestLifecycle:
    def test_double_close(self):
        c = MarvelClient(ClusterConfig(name="dc"))
        c.close()
        c.close()  # idempotent
        assert c.closed

    def test_crash_inside_with_still_closes(self):
        with pytest.raises(RuntimeError, match="boom"):
            with MarvelClient(ClusterConfig(name="crash")) as c:
                raise RuntimeError("boom")
        assert c.closed
        # gateway rejects new work after the abortive exit
        from repro.core.gateway import GatewayClosedError

        with pytest.raises(GatewayClosedError):
            c.gateway.submit("nope")

    def test_session_after_close_raises(self):
        c = MarvelClient(ClusterConfig(name="sac"))
        c.close()
        with pytest.raises(ClientClosedError):
            c.session("s")
        with pytest.raises(ClientClosedError):
            c.dataset([b"x"])
        with pytest.raises(ClientClosedError):
            c.iterate("l", init=lambda ctx: None,
                      superstep=lambda ctx: [], until=lambda ctx: True)

    def test_reenter_after_close_raises(self):
        c = MarvelClient(ClusterConfig(name="re"))
        c.close()
        with pytest.raises(ClientClosedError):
            with c:
                pass

    def test_construction_failure_is_transactional(self):
        import threading

        before = {t.name for t in threading.enumerate()}
        with pytest.raises(ConfigError):
            MarvelClient(ClusterConfig(
                name="txn",
                tiers=(TierSpec("dram", capacity_bytes=1 << 20), "s3"),
                replication=9, nodes=2,  # invalid: caught by validate()
            ))
        # an unexpected mid-build failure must also tear down cleanly
        class Boom(TierSpec):
            def build(self):
                raise RuntimeError("device exploded")

        with pytest.raises(ConfigError, match="construction failed"):
            MarvelClient(ClusterConfig(name="txn2", tiers=(Boom("dram"),)))
        after = {t.name for t in threading.enumerate()}
        leaked = {t for t in after - before if t.startswith(("txn", "gw"))}
        assert not leaked, f"leaked threads: {leaked}"


# -- config surface ------------------------------------------------------------

class TestClusterConfig:
    def test_overrides_kwargs(self):
        c = MarvelClient(ClusterConfig(name="ov"), invokers=2)
        try:
            assert len(c.gateway.invokers) == 2
        finally:
            c.close()

    def test_unknown_override_raises(self):
        with pytest.raises(ConfigError, match="unknown ClusterConfig"):
            MarvelClient(ClusterConfig(), invokerz=3)

    def test_tiered_stack_with_faults(self):
        cfg = ClusterConfig(
            name="ft",
            tiers=(TierSpec("dram", capacity_bytes=1 << 20), "s3"),
            faults=FaultSpec(seed=1, schedule=(("get", 0),)),
        )
        with MarvelClient(cfg) as c:
            from repro.storage import TieredStore

            assert isinstance(c.state, TieredStore)
            assert c.state.levels[-1].tier.name.startswith("faulty:")
            c.state.put("k", b"v")
            assert c.state.get("k") == b"v"  # served from fast level

    def test_validate_rejects_bad_fault_rates(self):
        with pytest.raises(ConfigError, match="put_error_rate"):
            ClusterConfig(faults=FaultSpec(put_error_rate=1.5)).validate()
