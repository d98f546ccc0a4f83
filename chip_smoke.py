#!/usr/bin/env python3
"""Bring-up smoke run of Marvel-JAX on a TPU, through ``repro.api.MarvelClient``.

With no arguments it needs one chip and runs two phases:

  (a) Stateful LM serving at published width.  qwen2.5-3b (36 layers,
      d_model 2048, vocab 151936, bf16; random weights from ``--seed``) is
      served through ``client.serving()``: ServingPool -> gateway ->
      PagedDecoder -> KVPager over a DRAM + PMEM tier stack with a PMEM
      journal and lossless demotion.  Four conversations with 128-token
      prompts decode 32 tokens each; one is suspended mid-stream (its KV
      blocks demoted to PMEM) and resumed.  Every conversation's tokens
      must equal a plain, unpaged jitted greedy decode of the same prompt.
  (b) Device-mode MapReduce.  A WordCount (no map-side combiner) over a
      seeded Zipf corpus, 16+ map tasks of 300K pairs each, runs through
      ``client.mapreduce(..., device=True)`` with the Pallas kernels
      compiled for the chip.  Its output must equal the host run byte for
      byte and the word counts of the generated corpus; the report must
      show the device path ran with no host fallback.

``--chips 4`` runs only phase (c): ``device_histogram`` over a 4-device
``"data"`` mesh, keys placed by ``NamedSharding``, checked against
``host_histogram``.

Bring-up readings (times, peak device memory) go on earlier lines; they
are not benchmark numbers.  The last line is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed.  Without a TPU, or when any check
fails, the script exits non-zero and prints no such line.

Usage:
  python chip_smoke.py [--seed 0]
  python chip_smoke.py --chips 4
"""

from __future__ import annotations

import argparse
import ast
import json
import shutil
import sys
import time
from collections import Counter
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: PMEM tier + journal of phase (a); removed before and after the run
STATE_DIR = ROOT / ".smoke_state"


def log(msg: str) -> None:
    print(msg, flush=True)


def reading(name: str, value) -> None:
    log(f"bring-up reading (not a benchmark): {name} = {value}")


def check(ok: bool, what: str) -> None:
    """A failed check ends the run (unlike ``assert``, not lost to -O)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


# -- (a) stateful LM serving ----------------------------------------------


def reference_decode(params, cfg, prompt, max_tokens: int):
    """Plain unpaged greedy decode: one jitted prefill, then one jitted
    ``decode_step`` per token with the cache kept in hand."""
    import jax
    import jax.numpy as jnp

    from repro.models import ShapeConfig, decode_step, forward, logits_fn

    plen = prompt.shape[1]
    shape = ShapeConfig(
        name="ref", kind="prefill", seq_len=plen, global_batch=1,
        q_chunk=min(8, plen), kv_chunk=min(8, plen), remat="none",
    )

    @jax.jit
    def prefill(p, toks):
        h, _aux, cache = forward(p, cfg, {"tokens": toks}, shape,
                                 collect_cache=True,
                                 cache_len=plen + max_tokens)
        tok = jnp.argmax(logits_fn(p, cfg, h[:, -1]), -1)
        return tok.astype(jnp.int32)[:, None], cache

    step = jax.jit(lambda p, tok, cache, t: decode_step(p, cfg, tok, cache, t))
    tok, cache = prefill(params, prompt)
    out = []
    for i in range(max_tokens):
        logits, cache, _ = step(params, tok, cache, jnp.int32(plen + i))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        out.append(int(tok[0, 0]))
    return out


def phase_serving(seed: int, cfg=None, n_conv: int = 4, prompt_len: int = 128,
                  gen: int = 32, suspend_round: int = 12,
                  resume_round: int = 20) -> None:
    import jax
    import numpy as np

    from repro.api import ClusterConfig, MarvelClient, ServingConfig, TierSpec
    from repro.configs import get_config
    from repro.models import init_params, model_defs

    cfg = cfg or get_config("qwen2.5-3b")
    log(f"(a) serving {cfg.name}: {cfg.n_periods} layers, d_model "
        f"{cfg.d_model}, vocab {cfg.vocab}; {n_conv} conversations x "
        f"{prompt_len}-token prompts x {gen} decoded tokens")
    key = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    params = init_params(model_defs(cfg), key)
    jax.block_until_ready(params)
    reading("(a) init_params_s", time.perf_counter() - t0)
    prompts = [
        jax.random.randint(jax.random.fold_in(key, 1000 + i),
                           (1, prompt_len), 0, cfg.vocab)
        for i in range(n_conv)
    ]

    t0 = time.perf_counter()
    want = [reference_decode(params, cfg, p, gen) for p in prompts]
    reading("(a) reference_decode_s (incl. compile)", time.perf_counter() - t0)

    shutil.rmtree(STATE_DIR, ignore_errors=True)
    cluster = ClusterConfig(
        name="chip-smoke-serve",
        tiers=(TierSpec("dram", capacity_bytes=1 << 30),
               TierSpec("pmem", path=str(STATE_DIR / "pmem"))),
        invokers=n_conv, warm_pool=2 * n_conv, commit_every=1,
        journal="pmem", journal_path=str(STATE_DIR / "journal"),
        serving=ServingConfig(lossless=True),
    )
    convs = [f"conv{i}" for i in range(n_conv)]
    got = {c: [] for c in convs}
    with MarvelClient(cluster) as client:
        pool = client.serving(params, cfg, prompt_len=prompt_len,
                              max_tokens=gen)
        for c, prompt in zip(convs, prompts):
            t0 = time.perf_counter()
            got[c].append(int(np.asarray(pool.start(c, prompt).result())[0, 0]))
            note = " (incl. compile)" if c == convs[0] else ""
            reading(f"(a) ttft_s[{c}]{note}", time.perf_counter() - t0)
        held = convs[0]
        decoded, rnd = 0, 1
        t0 = time.perf_counter()
        while any(len(got[c]) < gen for c in convs):
            if rnd == suspend_round:
                check(pool.suspend(held), "suspend demotes the conversation")
                check(not pool.is_resident(held), "suspended conversation is cold")
            if rnd == resume_round:
                check(pool.resume(held), "resume re-pins the conversation")
            futs = {
                c: pool.step(c) for c in convs
                if len(got[c]) < gen
                and not (c == held and suspend_round <= rnd < resume_round)
            }
            for c, fut in futs.items():
                got[c].append(int(np.asarray(fut.result())[0, 0]))
            decoded += len(futs)
            rnd += 1
        dt = time.perf_counter() - t0
        stats = pool.stats()
    reading("(a) decode_tokens_per_s", decoded / dt)
    mem = jax.devices()[0].memory_stats() or {}
    reading("(a) peak_bytes_in_use", mem.get("peak_bytes_in_use"))
    log(f"(a) pager: {stats}")
    check(stats["demotions"] >= 1 and stats["resumes"] >= 1,
          f"pager demoted and resumed: {stats}")
    for c, w in zip(convs, want):
        check(got[c] == w, f"{c}: paged {got[c]} == reference {w}")
    log(f"(a) PASS: {n_conv} paged conversations == unpaged reference "
        f"({gen} tokens each; {held} suspended to PMEM at round "
        f"{suspend_round}, resumed at round {resume_round})")


# -- (b) device-mode MapReduce ----------------------------------------------


def zipf_corpus(seed: int, n_words: int, vocab: int = 50_000,
                per_line: int = 16):
    """Seeded text: random lowercase words drawn with Zipf(1) ranks.
    Returns (bytes, Counter of word -> occurrences)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lens = rng.integers(3, 11, vocab)
    letters = rng.integers(97, 123, int(lens.sum()), dtype=np.uint8).tobytes()
    ends = np.cumsum(lens)
    words = np.array(
        [letters[e - n:e] for e, n in zip(ends.tolist(), lens.tolist())],
        dtype=object,
    )
    p = 1.0 / np.arange(1, vocab + 1)
    idx = rng.choice(vocab, size=n_words, p=p / p.sum())
    drawn = words[idx]
    lines = [b" ".join(row) for row in drawn.reshape(-1, per_line)]
    truth: Counter = Counter()
    for w, n in zip(words.tolist(), np.bincount(idx, minlength=vocab).tolist()):
        if n:
            truth[w] += n
    return b"\n".join(lines), truth


def phase_mapreduce(seed: int, n_maps: int = 16, pairs_per_map: int = 300_000,
                    n_reducers: int = 4) -> None:
    import jax
    import jax.numpy as jnp

    from repro.api import ClusterConfig, MarvelClient
    from repro.core.mapreduce import wordcount_job
    from repro.kernels import ops
    from repro.kernels.bucket_histogram import DEFAULT_BLOCK

    n_words = n_maps * pairs_per_map
    t0 = time.perf_counter()
    data, truth = zipf_corpus(seed, n_words)
    reading("(b) corpus_gen_s", time.perf_counter() - t0)
    log(f"(b) device-mode WordCount: {n_words} words, {len(truth)} distinct, "
        f"{len(data)} bytes, >= {n_maps} map tasks")
    # No map-side combiner: every pair reaches the device partition step.
    job = replace(wordcount_job(n_reducers), combiner=None)
    block_size = len(data) // n_maps

    def run(device: bool):
        cfg = ClusterConfig(name="chip-smoke-wc", block_size=block_size)
        with MarvelClient(cfg) as client:
            client.store.write("/in", data, record_delim=b"\n")
            t = time.perf_counter()
            handle = client.mapreduce(job, "/in", "/out", device=device)
            wall = time.perf_counter() - t
            outs = [
                client.store.read(f"/out/part_{p:04d}")
                if client.store.exists(f"/out/part_{p:04d}") else b""
                for p in range(n_reducers)
            ]
        return handle.report, outs, wall

    dev_rep, dev_out, dev_wall = run(True)
    reading("(b) device_job_wall_s (incl. compile)", dev_wall)
    host_rep, host_out, host_wall = run(False)
    reading("(b) host_job_wall_s", host_wall)
    extra = dev_rep.extra
    log(f"(b) device report: map_tasks={extra['map_tasks']} "
        f"device_mode={extra['device_mode']} "
        f"device_pairs={extra['device_pairs']} "
        f"device_groups={extra['device_groups']} "
        f"device_spilled_pairs={extra['device_spilled_pairs']} "
        f"device_fallback_tasks={extra['device_fallback_tasks']}")
    check(extra["map_tasks"] >= n_maps, f"map_tasks >= {n_maps}")
    check(extra["device_mode"] == 1, "device_mode == 1")
    check(extra["device_pairs"] == n_words, f"device_pairs == {n_words}")
    check(extra["device_groups"] > 0, "device_groups > 0")
    check(extra["device_fallback_tasks"] == 0, "device_fallback_tasks == 0")
    check(host_rep.extra["device_mode"] == 0, "host run is not in device mode")
    check(dev_out == host_out, "device output bytes == host output bytes")
    counts: Counter = Counter()
    for blob in dev_out:
        for line in blob.split(b"\n"):
            if line:
                k, v = line.split(b"\t")
                counts[ast.literal_eval(k.decode())] += int(v)
    check(counts == truth, "WordCount output == corpus word counts")

    # The partition step the map tasks ran is a Mosaic kernel, not XLA.
    check(pairs_per_map // DEFAULT_BLOCK >= 100,
          "each histogram call spans >= 100 grid blocks")
    lowered = jax.jit(lambda d: ops.partition_counts(d, n_reducers)).lower(
        jax.ShapeDtypeStruct((pairs_per_map,), jnp.int32)
    )
    check("tpu_custom_call" in lowered.as_text(),
          "partition step lowers to a Mosaic tpu_custom_call")
    log(f"(b) PASS: device output == host output ({sum(map(len, dev_out))} "
        f"bytes) == corpus counts; partition step lowers to tpu_custom_call")


# -- (c) four-chip shuffle --------------------------------------------------


def phase_mesh(seed: int, n_dev: int = 4, n: int = 1 << 22,
               vocab: int = 1 << 16) -> None:
    import jax
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.core.device_shuffle import device_histogram, host_histogram
    from repro.storage import DramTier

    devices = jax.devices()
    check(len(devices) >= n_dev, f"{n_dev} devices (have {len(devices)})")
    mesh = jax.make_mesh((n_dev,), ("data",), axis_types=(AxisType.Auto,),
                         devices=devices[:n_dev])
    rng = np.random.default_rng(seed)
    # Zipf-ranked keys scattered over the key space (hot keys land on
    # random owners), 5% padding.
    ranks = np.minimum(rng.zipf(1.1, n), vocab) - 1
    keys = rng.permutation(vocab).astype(np.int32)[ranks]
    keys[rng.random(n) < 0.05] = -1
    vals = np.ones(n, np.int32)
    sharding = NamedSharding(mesh, P("data"))
    kj = jax.device_put(keys, sharding)
    vj = jax.device_put(vals, sharding)
    log(f"(c) device_histogram over {n_dev} devices: {n} keys, vocab {vocab}")
    t0 = time.perf_counter()
    res = device_histogram(kj, vj, mesh, "data", vocab=vocab,
                           capacity_factor=2.0, spill_tier=DramTier())
    res.counts.block_until_ready()
    reading("(c) device_histogram_s (incl. compile)", time.perf_counter() - t0)
    want = host_histogram(keys, vals, vocab)
    log(f"(c) shuffled_bytes={res.shuffled_bytes} "
        f"buffer_bytes={res.buffer_bytes} spilled={res.spilled}")
    held = {s.device for s in res.counts.addressable_shards}
    check(held == set(devices[:n_dev]), f"result sharded over {n_dev} devices: {held}")
    check(int(res.dropped) == 0, "dropped == 0")
    check(np.asarray(res.counts).tobytes() == want.tobytes(),
          "device_histogram == host_histogram")
    log(f"(c) PASS: sharded over {len(held)} devices, dropped == 0, "
        f"== host_histogram")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip device_histogram phase")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU attached (JAX backend is "
                         f"{dev.platform!r})")

    from repro.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_mesh(args.seed)
        else:
            phase_serving(args.seed)
            phase_mapreduce(args.seed)
    finally:
        shutil.rmtree(STATE_DIR, ignore_errors=True)
    reading("total_s", time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
