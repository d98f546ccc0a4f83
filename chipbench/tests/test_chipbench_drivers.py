"""Both drivers run through the harness's internal entry point at smoke
size on the CPU, report the metrics their cells promise, and prove
correct."""

import pytest

from chipbench.tests import smoke


def _names(cell, per_layer):
    return {m["name"] for m in (cell.per_layer if per_layer else cell.end_to_end)}


def test_serving_cell_reports_its_end_to_end_metrics():
    cell = smoke.serve_cell()
    r = smoke.run(cell)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == _names(cell, False)
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_serving_traced_run_reads_its_host_side_metrics():
    cell = smoke.serve_cell()
    r = smoke.run(cell, trace=True)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) <= _names(cell, True)
    # no device plane on the CPU: device readers find nothing, others do
    # nor peaks: the CPU is in no table
    assert {"gateway_wait_ms.serve", "pager_ms_per_token.serve"} <= set(r["metrics"])
    assert "device_idle_share.serve" not in r["metrics"]
    assert "breakdown" in r


def test_gaps_and_decode_time_cover_the_windows_calls():
    from chipbench.drivers import serve

    win = serve.Window(None, None, {})
    win.t0, win.end = 0.0, 10.0
    a, b = serve._Conv(None, 0.0), serve._Conv(None, 1.0)
    a.times, b.times = [1.0, 1.5, 2.5, 11.0], [2.0, 2.2]
    win.convs = [a, b]
    assert sorted(win.gaps()) == pytest.approx([0.2, 0.5, 1.0])
    win.calls["step"] = [(1.0, 1.5), (1.2, 2.5), (3.0, 3.5), (9.8, 10.4)]
    assert win.busy_s("step") == pytest.approx(2.6)
    assert win.metrics()["decode_ms_per_token"] == pytest.approx(1e3 * 2.6 / 4)


def test_wordcount_cell_runs_fresh_text_and_matches_the_reference():
    cell = smoke.wordcount_cell()
    r = smoke.run(cell, trace=True)
    assert r["correct"], r["checks"]
    assert r["attempted"] == 3
    # every task's shape was compiled in set-up, although each job's
    # blocks and reduce tasks differ in size
    assert r["metrics"]["compiles_in_window.wc"]["value"] == 0
    assert "shuffle_wall_share.wc" in r["metrics"]
    r = smoke.run(cell)
    assert set(r["metrics"]) == _names(cell, False)


def test_words_in_counts_what_the_mapper_splits():
    from chipbench.drivers.mapreduce import words_in

    for text in (b"a b\tc\n", b"  a\n\nbb  c d\t\te\n", b"x", b""):
        assert words_in(text) == len(text.split())
