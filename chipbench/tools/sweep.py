#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: one set-up, then one
window at each offered rate, in one process on the chip.

    python3 chipbench/tools/sweep.py --workload qwen2_5_3b.chat_steady \\
        --seed 7 --seconds 40 --rates 0.2,0.3,0.4,0.5

Prints one JSON line per rate: tokens/s, TTFT p50 of the first and the
last third of the arrivals, conversations started and finished.  The
backlog grows where the last third waits far longer than the first.
Used once, to fix the rate of a cell's mix; the benchmark never sweeps.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

from chipbench import harness, spans  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    harness.device_info(int(cell.workload["chips"]), True)
    harness.enable_compile_cache()
    serve = cell.module("drivers", cell.config["driver"])
    state = ROOT / ".chipbench_state" / "sweep"
    shutil.rmtree(state, ignore_errors=True)
    state.mkdir(parents=True)
    ctx = harness.RunContext(cell=cell, seed=args.seed, seconds=args.seconds,
                             trace=False, t_start=T_START, peak=None,
                             state_dir=state, spans=spans.Spans(False),
                             compiles=harness.CompileCounter())
    try:
        s = serve.Served(ctx)
        print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = dict(s.mix, rate=rate)
            convs = s.conversations(mix, args.seed + i, args.seconds, f"r{i}c")
            win = serve.Window(ctx, s.pool, mix)
            win.run(convs, args.seconds)
            third = max(1, len(win.convs) // 3)
            ttft = [cv.times[0] - cv.due if cv.times else float("inf")
                    for cv in win.convs]
            finished = sum(len(cv.tokens) == cv.c.out_tokens for cv in win.convs)
            print(json.dumps(dict(
                win.metrics(), rate=rate, started=len(win.convs),
                finished=finished, failed=win.failed + win.shed,
                ttft_first_third_s=float(np.median(ttft[:third])),
                ttft_last_third_s=float(np.median(ttft[-third:])),
                drain_s=win.closed - win.end)), flush=True)
            for cv in win.convs:
                if len(cv.tokens) < cv.c.out_tokens and not cv.failed:
                    s.pool.drop(cv.c.cid)
        s.client.close()
    finally:
        shutil.rmtree(state, ignore_errors=True)


if __name__ == "__main__":
    main()
