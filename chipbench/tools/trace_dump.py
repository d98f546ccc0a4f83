#!/usr/bin/env python3
"""Print what a recorded trace holds, as ``trace.reduce`` reads it:

    python3 chipbench/tools/trace_dump.py <file.xplane.pb> [--ops 40]

planes and lines with their event counts, the window, busy time, every
program with its executions and device seconds, the operations that took
most time, and idle time by host span.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import trace  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--ops", type=int, default=40)
    args = ap.parse_args()
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(args.path).planes:
        for line in plane.lines:
            evs = list(line.events)
            first = evs[0].name[:100] if evs else ""
            print(f"{plane.name} | {line.name} | {len(evs)} | {first}")
    s = trace.reduce(args.path)
    print(json.dumps({"window_s": s.window_s, "busy_s": s.busy_s,
                      "devices": s.devices}))
    for name, (n, sec) in sorted(s.programs.items(), key=lambda kv: -kv[1][1]):
        print(f"program {name} x{n} {sec:.6f} s")
    for name, sec in sorted(s.ops.items(), key=lambda kv: -kv[1])[:args.ops]:
        print(f"op {name} {sec:.6f} s")
    for name, sec in sorted(s.idle_by_span.items(), key=lambda kv: -kv[1]):
        print(f"idle {name} {sec:.6f} s")


if __name__ == "__main__":
    main()
