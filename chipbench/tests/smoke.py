"""Smoke-size cells for the CPU tests: the real cell files with every
size cut down, run through ``harness.run_cell`` without the chip."""

from __future__ import annotations

import tempfile
from pathlib import Path

from chipbench import harness

SEED = 12345678901  # wider than 32 bits, as a run's seed may be


def serve_cell() -> harness.Cell:
    cell = harness.load_cell("qwen2_5_3b.chat_steady")
    cell.config.update(hidden_size=64, num_attention_heads=4,
                       num_key_value_heads=2, intermediate_size=128,
                       num_hidden_layers=2, vocab_size=256)
    cell.config["deployment"].update(prompt_len=16, max_tokens=8)
    mix = cell.traffic
    mix["prompt_tokens"] = {"lengths": [8, 16], "weights": [0.5, 0.5]}
    mix["output_tokens"] = {"median": 4, "sigma": 0.5, "min": 2, "max": 8}
    mix["rate"] = 3.0
    # At this size logits spread ~0.2: over 6 s windows with every served
    # token compared, sound runs read gaps up to 0.004 and the fp8
    # control 0.026 or more (12 seeds each); a wrong token reads 0.3 or more.
    cell.workload["check"].update(served_tokens=24, max_logit_gap=0.01)
    return cell


#: metrics of the WordCount cell, which is kept as files only while device
#: mode compiles per task size (PERF.md, Open questions)
WORDCOUNT_METRICS = (
    [{"name": "wordcount_mb_per_s", "unit": "MB/s"}, {"name": "setup_s", "unit": "s"}],
    [{"name": n, "unit": u} for n, u in (
        ("shuffle_wall_share.wc", "%"), ("bucket_histogram_roofline", "%"),
        ("compiles_in_window.wc", "count"), ("device_idle_share.wc", "%"))])


def wordcount_cell() -> harness.Cell:
    name = "wordcount_hibench.no_combiner"
    workload = harness.load_json(harness.HERE / "workloads" / f"{name}.json")
    cell = harness.Cell(
        name=name, workload=workload,
        config=harness.load_json(harness.HERE / "configs" / f"{workload['config']}.json"),
        traffic=harness.load_json(harness.HERE / "traffic" / f"{workload['traffic']}.json"),
        end_to_end=WORDCOUNT_METRICS[0], per_layer=WORDCOUNT_METRICS[1])
    cell.config.update(list_words=10, datasize=60_000, block_size=25_000)
    cell.config["deployment"]["device_interpret"] = True
    cell.traffic["jobs"] = 3
    return cell


def run(cell: harness.Cell, seconds: float = 3.0, trace: bool = False,
        seed: int = SEED, **kw) -> dict:
    # a state directory of its own: test files run in parallel workers
    with tempfile.TemporaryDirectory(prefix="chipbench-") as state:
        return harness.run_cell(cell, seed, seconds, trace, require_tpu=False,
                                state_root=Path(state), **kw)
