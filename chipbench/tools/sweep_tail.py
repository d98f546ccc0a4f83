#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell, as ``sweep.py`` does, with
a tail after the arrivals: at each offered rate the conversations due in
the first ``--seconds`` arrive (those the benchmark's window of that
length admits), and the window stays open ``--tail`` seconds more, so
that each of them has the time to finish.

    python3 chipbench/tools/sweep_tail.py \\
        --workload deepseek_v2_lite_ep4.chat_steady_dsv2 --seed 7 \\
        --seconds 50 --tail 10 --rates 0.25,0.3,0.35

``sweep.py`` closes its window at the last arrival, so that a
conversation due near the close never finishes at any rate.  Here the
knee is the highest rate at which every conversation finishes within
the tail: a backlog longer than the tail at the close leaves some
unfinished.  Prints one JSON line per rate: started and finished
conversations, tokens/s and decode ms over the whole open window, TTFT
p50 of the first and the last third of the arrivals, and when the last
conversation finished, in seconds after the window opened.
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
    ap.add_argument("--tail", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    harness.device_info(int(cell.workload["chips"]), True)
    harness.enable_compile_cache()
    serve = cell.module("drivers", cell.config["driver"])
    state = ROOT / ".chipbench_state" / "sweep_tail"
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
            convs = [c for c in s.conversations(mix, args.seed + i, args.seconds,
                                                f"r{i}c")
                     if c.due < args.seconds]
            win = serve.Window(ctx, s.pool, mix)
            win.run(convs, args.seconds + args.tail)
            third = max(1, len(win.convs) // 3)
            ttft = [cv.times[0] - cv.due if cv.times else float("inf")
                    for cv in win.convs]
            done = [cv for cv in win.convs if len(cv.tokens) == cv.c.out_tokens]
            print(json.dumps(dict(
                win.metrics(), rate=rate, started=len(win.convs),
                finished=len(done), failed=win.failed + win.shed,
                ttft_first_third_s=float(np.median(ttft[:third])),
                ttft_last_third_s=float(np.median(ttft[-third:])),
                last_finish_s=max((cv.times[-1] - win.t0 for cv in done),
                                  default=None))), flush=True)
            for cv in win.convs:
                if len(cv.tokens) < cv.c.out_tokens and not cv.failed:
                    s.pool.drop(cv.c.cid)
        s.client.close()
    finally:
        shutil.rmtree(state, ignore_errors=True)


if __name__ == "__main__":
    main()
