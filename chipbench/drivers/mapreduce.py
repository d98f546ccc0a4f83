"""Driver for WordCount jobs through ``MarvelClient.mapreduce(..., device=True)``.

Set-up: the text of every job (``jobs`` of the mix) is generated from the
seed, fresh for each job, and written into the client's block store, one
job per file, which splits it into blocks of the configuration's
``block_size`` at line ends.  Device mode compiles its partition step for
each map task's pair count and its segment-sum for each reduce task's, so
set-up compiles every one of those shapes that the jobs will meet, by
calling the program's device shuffle at each (pair counts read from the
stored blocks, reduce sizes from the generator's word counts).

Window: jobs run back to back from its start; a job starts only while
the window is open.  ``wordcount_mb_per_s`` is the input bytes of every
job started in the window over the time from the window's start to the
end of the last of them.

Correctness: every job's output (all reducer parts) is compared with the
plain reference's word counts of its input; the number of words whose
count differs, is missing or is extra is held to 0.
"""

from __future__ import annotations

import ast
import math
import time
from dataclasses import replace
from typing import Any, Dict, List

import numpy as np

from chipbench.harness import Check, Outcome, RunContext, memory_peak


def cluster_config(dep: Dict, state_dir, block_size: int) -> Any:
    from repro.api import ClusterConfig, TierSpec

    tiers = tuple(
        TierSpec(t["kind"], capacity_bytes=t.get("capacity_bytes"),
                 path=str(state_dir / t["path"]) if t.get("path") else None)
        for t in dep["tiers"])
    return ClusterConfig(
        name="chipbench-mapreduce", tiers=tiers, invokers=dep["invokers"],
        journal=dep["journal"],
        journal_path=(str(state_dir / "journal") if dep["journal"] == "pmem"
                      else None),
        nodes=dep["nodes"], replication=dep["replication"],
        block_size=block_size, device=dep["device"],
        device_interpret=dep.get("device_interpret"),
    )


def read_counts(client: Any, out: str, reducers: int) -> Dict[bytes, int]:
    counts: Dict[bytes, int] = {}
    dup = 0
    for p in range(reducers):
        part = f"{out}/part_{p:04d}"
        if not client.store.exists(part):
            continue
        for line in client.store.read(part).split(b"\n"):
            if line:
                k, v = line.split(b"\t")
                word = ast.literal_eval(k.decode())
                dup += word in counts
                counts[word] = counts.get(word, 0) + int(v)
    if dup:  # a word in two parts, or twice in one, is a wrong answer
        counts[b"\x00duplicate-words"] = dup
    return counts


def _instrument(ctx: RunContext) -> None:
    from repro.core import device_shuffle

    sp = ctx.spans
    sp.wrap(device_shuffle, "device_partition", "device_partition",
            before=lambda dest, n_parts, *a, **k: sp.note("histogram_keys", len(dest)))
    sp.wrap(device_shuffle, "device_segment_reduce", "device_segment_reduce")


def words_in(block: bytes) -> int:
    """Whitespace-separated words of ``block``: the map task's pairs."""
    b = np.frombuffer(block, np.uint8)
    space = (b == ord(" ")) | (b == ord("\t")) | (b == ord("\n"))
    return int(np.count_nonzero(~space & np.concatenate([[True], space[:-1]])))


def warm_shapes(client: Any, jobs: List[Any], words: List[bytes],
                reducers: int, interpret: Any) -> int:
    """Compile the device shuffle at every shape that ``jobs`` (each a
    store path and its word counts) will give it; returns the number of
    calls made."""
    from repro.core import device_shuffle as ds
    from repro.core.mapreduce import _partition

    part = np.array([_partition(w, reducers) for w in words])
    factor = ds.DeviceExec().capacity_factor
    calls = 0
    for path, counts in jobs:
        for block in client.store.locate(path):
            n = words_in(client.store.read_block(block))
            cap = max(1, math.ceil(factor * n / reducers))
            ds.device_partition(np.zeros(n, np.int32), reducers, capacity=cap,
                                interpret=interpret)
            calls += 1
        for p in range(reducers):
            held = (part == p) & (counts > 0)
            if held.any():
                n = int(counts[held].sum())
                ds.device_segment_reduce(np.zeros(n, np.int32),
                                         np.zeros(n, np.int64), int(held.sum()))
                calls += 1
    return calls


def run(ctx: RunContext) -> Outcome:
    from repro.api import MarvelClient
    from repro.core.mapreduce import wordcount_job
    from repro.kernels import ops

    cell, cfg, mix = ctx.cell, ctx.cell.config, ctx.cell.traffic
    dep = cell.deployment
    gen = cell.module("traffic", mix["generator"]).TextWriter(cfg)
    ref = cell.module("configs", cfg["reference"])
    n_jobs = int(mix["jobs"])
    inputs = [gen.job(ctx.seed, j) for j in range(n_jobs)]
    reducers = int(cfg["reducers"])
    job = replace(wordcount_job(reducers), combiner=None)

    client = MarvelClient(cluster_config(dep, ctx.state_dir, int(cfg["block_size"])))
    for j, (data, _) in enumerate(inputs):
        client.store.write(f"/in/{j:04d}", data, record_delim=b"\n")
    t = time.perf_counter()
    calls = warm_shapes(client, [(f"/in/{j:04d}", c) for j, (_, c) in enumerate(inputs)],
                        gen.words, reducers, dep.get("device_interpret"))
    ctx.log(f"{calls} shapes of the device shuffle warmed up in "
            f"{time.perf_counter() - t:.3f} s")
    # Jobs are journaled by name: each submission gets its own.
    named = lambda j: replace(job, name=f"{job.name}-{j:04d}")

    if ctx.trace:
        _instrument(ctx)
        tracer = ctx.tracer()
        tracer.start()
    setup_s = time.perf_counter() - ctx.t_start
    ctx.spans.active = ctx.compiles.active = True
    reports: List[Any] = []
    with ctx.spans.span("window"):
        t0 = time.perf_counter()
        for j in range(n_jobs):
            if time.perf_counter() - t0 >= ctx.seconds:
                break
            with ctx.spans.span("job"):
                reports.append(client.mapreduce(
                    named(j), f"/in/{j:04d}", f"/out/{j:04d}", device=True).report)
        t_end = time.perf_counter()
    ctx.spans.active = ctx.compiles.active = False
    if ctx.trace:
        tracer.halt()
    summary = tracer.summary() if ctx.trace else None
    peak = memory_peak(int(cell.workload["chips"]))
    ran = len(reports)
    counters_program = "jit_" + ops.shuffle_histogram.__name__
    outputs = [read_counts(client, f"/out/{j:04d}", reducers) for j in range(ran)]
    client.close()
    extra = reports[0].extra if reports else {}
    ctx.log(f"{ran} jobs in {t_end - t0:.3f} s; first job: map_tasks "
            f"{extra.get('map_tasks')} device_mode {extra.get('device_mode')} "
            f"device_pairs {extra.get('device_pairs')} spilled "
            f"{extra.get('device_spilled_pairs')} fallback "
            f"{extra.get('device_fallback_tasks')}; compiles in window "
            f"{ctx.compiles.compiles} (persistent-cache hits "
            f"{ctx.compiles.cache_hits}) {ctx.compiles.names[:8]}")

    if summary is not None:
        ctx.log(f"trace: {summary.window_s:.3f} s, dropped {summary.dropped}, "
                f"histogram program {summary.program(counters_program)} for "
                f"{len(ctx.spans.values['histogram_keys'])} partition calls")
    t = time.perf_counter()
    wrong = sum(ref.mismatches(got, ref.word_counts(inputs[j][0]))
                for j, got in enumerate(outputs))
    ctx.log(f"reference over {ran} jobs in {time.perf_counter() - t:.3f} s")
    readings = {"program": wrong}
    checks = [Check("jobs_missing", float(ran == 0), 0.0),
              Check("mismatched_words", float(wrong), 0.0)]
    controls: Dict[str, List[Check]] = {}
    for control in ctx.control.split(",") if ctx.control else ():
        k = f"control:{control}"
        readings[k] = sum(
            ref.mismatches(ref.word_counts(inputs[j][0], control),
                           ref.word_counts(inputs[j][0])) for j in range(ran))
        controls[k] = checks[:1] + [Check("mismatched_words", float(readings[k]), 0.0)]
    mb = sum(len(data) for data, _ in inputs[:ran]) / 1e6
    e2e = {"wordcount_mb_per_s": mb / (t_end - t0),
           "setup_s": setup_s}
    counters = {
        "window_s": t_end - t0,
        "jobs": ran,
        "histogram_program": counters_program,
        "readings": readings,
    }
    return Outcome(e2e=e2e, attempted=ran, failed=0, checks=checks,
                   memory_peak_bytes=peak, counters=counters, trace=summary,
                   control_checks=controls)
