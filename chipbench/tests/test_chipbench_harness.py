"""The harness finds every cell, configuration, mix and metric by name,
from files alone, and refuses to run without a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys

from chipbench import harness

BENCH = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_loads_its_files_by_name():
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.module("drivers", cell.config["driver"]).run
        assert cell.module("traffic", cell.traffic["generator"])
        assert cell.module("configs", cell.config["reference"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}


def test_every_metric_has_a_reader():
    from chipbench.tests.smoke import WORDCOUNT_METRICS

    for m in BENCH["per_layer"] + WORDCOUNT_METRICS[1]:
        reader = harness.load_module(harness.HERE / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)


def test_every_metric_moves_an_end_to_end_metric_of_its_cells():
    names = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert set(m["workloads"]) <= names
        for cell in m["workloads"]:
            assert harness.reports(e2e[m["moves"]], cell, BENCH), (m["name"], cell)


def test_benchmark_file_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    configs = {c["name"] for c in BENCH["configs"]}
    assert configs == {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["file"].startswith("chipbench/") and os.path.exists(
            harness.ROOT / c["file"])
        assert set(c["reduced"]) <= set(harness.load_json(harness.ROOT / c["file"]))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCH["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].endswith("_roofline")


def test_a_new_cell_and_metric_need_only_new_files(tmp_path):
    shutil.copytree(harness.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    base = tmp_path / "chipbench"
    mix = harness.load_json(base / "traffic" / "chat_steady.json")
    mix["rate"] = 0.1
    (base / "traffic" / "chat_sparse.json").write_text(json.dumps(mix))
    cell = {"config": "qwen2_5_3b", "traffic": "chat_sparse", "chips": 1,
            "why": "a sparser open loop"}
    (base / "workloads" / "qwen2_5_3b.chat_sparse.json").write_text(
        json.dumps(dict(cell, check={"served_tokens": 8, "max_logit_gap": 1})))
    (base / "metrics" / "conversations.serve.py").write_text(
        "def read(out, ctx):\n    return float(out.attempted)\n")
    bench["workloads"].append(dict(cell, name="qwen2_5_3b.chat_sparse"))
    for m in bench["end_to_end"]:
        if "workloads" in m and "qwen2_5_3b.chat_steady" in m["workloads"]:
            m["workloads"].append("qwen2_5_3b.chat_sparse")
    bench["per_layer"].append({
        "name": "conversations.serve", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "gateway", "moves": "serve_tokens_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    new = harness.load_cell("qwen2_5_3b.chat_sparse", root=tmp_path)
    assert new.traffic["rate"] == 0.1
    assert "conversations.serve" in {m["name"] for m in new.per_layer}
    reader = new.module("metrics", "conversations.serve")
    assert reader.read(harness.Outcome({}, 7, 0, [], 0), None) == 7.0
    old = harness.load_cell("qwen2_5_3b.chat_steady", root=tmp_path)
    assert "conversations.serve" in {m["name"] for m in old.per_layer}


def test_command_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cell in (w["name"] for w in BENCH["workloads"]):
        p = subprocess.run(
            [sys.executable, "chipbench/run.py", "--workload", cell, "--seed",
             "1", "--seconds", "1", "--trace", "0"],
            cwd=harness.ROOT, env=env, capture_output=True, text=True,
            timeout=300)
        assert p.returncode != 0
        assert p.stdout == ""
        assert "TPU" in p.stderr
