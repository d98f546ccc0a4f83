"""The knee sweep with a tail, at smoke size on the CPU: only the
arrivals a window of ``--seconds`` admits are started, and the tail
lets each of them finish."""

from __future__ import annotations

import importlib.util
import json
import sys

from chipbench import harness
from chipbench.tests import smoke


def test_sweep_tail_finishes_every_arrival_of_the_window(monkeypatch, capsys):
    spec = importlib.util.spec_from_file_location(
        "sweep_tail", harness.HERE / "tools" / "sweep_tail.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    cell = smoke.serve_cell()
    monkeypatch.setattr(harness, "load_cell", lambda name: cell)
    monkeypatch.setattr(harness, "device_info", lambda chips, require_tpu: {})
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    seconds, tail = 2.0, 6.0
    monkeypatch.setattr(sys, "argv", [
        "sweep_tail.py", "--workload", cell.name, "--seed", str(smoke.SEED),
        "--seconds", str(seconds), "--tail", str(tail), "--rates", "3"])
    tool.main()
    setup, line = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
    assert setup["setup_s"] > 0
    gen = cell.module("traffic", cell.traffic["generator"])
    mix = dict(cell.traffic, rate=3.0)
    due = [c.due for c in gen.conversations(
        mix, smoke.SEED, gen.open_loop_count(mix, seconds), 256)]
    assert line["started"] == sum(d < seconds for d in due) == 3 < len(due)
    assert line["finished"] == line["started"] and line["failed"] == 0
    assert seconds < line["last_finish_s"] < seconds + tail
