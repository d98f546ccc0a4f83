#!/usr/bin/env python3
"""Marvel-JAX chip benchmark: run one cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and per-layer metrics are data
files under ``chipbench/``, found by the names in ``BENCHMARK.json``.
The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1`` also
``breakdown``) and, last, ``checks``: each number compared beside its
limit, which also close standard error.  ``--trace 0`` prints the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""

import time

T_START = time.perf_counter()  # set-up starts with the process

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
