#!/usr/bin/env python3
"""One traced run of a cell that keeps its raw trace, for reading by
hand (``trace_dump.py``):

    python3 chipbench/tools/traced.py --workload <cell> --seed 1 \\
        --seconds 10 --keep cell.xplane.pb
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    r = harness.run_cell(cell, args.seed, args.seconds, True, t_start=T_START,
                         keep_trace=Path(args.keep).resolve())
    print(json.dumps(r))


if __name__ == "__main__":
    main()
