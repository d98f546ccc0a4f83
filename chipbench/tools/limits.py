#!/usr/bin/env python3
"""Read the numbers that a cell's correctness limit is set from: for each
seed, one run of the cell at its own size and load, with the reference
also computed in the control's lower precision, all in one process on
the chip.

    python3 chipbench/tools/limits.py --workload qwen2_5_3b.chat_steady \\
        --seconds 20 --control fp8 --seeds 1,2,3

Prints one JSON line per seed: the program's reading (``program``) and
the control's (``control:<precision>``), before any limit, ``correct``
for the program and ``control_correct``: the same comparison with the
control's reading held to the limit in the program's place, which has to
be false.  The benchmark's own runs never compute the control.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import harness  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    for seed in (int(s) for s in args.seeds.split(",")):
        gc.collect()  # the last seed's weights go before the next are drawn
        t = time.perf_counter()
        cell = harness.load_cell(args.workload)
        r = harness.run_cell(cell, seed, args.seconds, False, t_start=t,
                             control=args.control)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "control_correct": r["control_correct"],
                          "readings": r["readings"], "checks": r["checks"],
                          "metrics": {k: v["value"] for k, v in r["metrics"].items()},
                          "wall_s": time.perf_counter() - t}), flush=True)


if __name__ == "__main__":
    main()
