"""Fig. 7 (serving-side analogue of fig6, beyond the paper): the gateway.

Part 1 — warm vs cold start latency.  A warm invocation hits the hot
device/DRAM view kept by the gateway's warm pool; a cold one re-loads the
serialized state from the (simulated, sleeping) Ignite/PMEM tier and
re-materializes it.  We report p50/p99 for both, reproducing the
Faasm/Cloudburst observation that warm contexts beat cold starts by
orders of magnitude.  Cold traffic is generated by thrashing a
``warm_pool=1`` gateway across sessions; warm traffic by re-invoking
pooled sessions.  The reported p50 is the **median of three measurement
windows taken after a warmup pass** — single-window microsecond-scale
p50s on shared CI runners flapped the smoke assert on scheduler noise.

Part 2 — throughput vs invoker count, in two commit modes:

  * ``fig7/throughput/invokers=N`` — group commit **on** (the façade
    default): invokers enqueue (blob, marker) pairs on the
    :class:`~repro.core.journal.GroupCommitter` and acknowledge when the
    batched flush lands, so N concurrent sessions pay one modeled tier
    request per flush round instead of two per invocation.  This is the
    warm-path headline number.
  * ``fig7/throughput_sync/invokers=N`` — group commit **off**: every
    invocation blocks on its own blob+marker tier write.  Invocation
    cost is device-bound (the simulated tier *sleeps*, releasing the
    GIL), so this mode is where invoker-count scaling is physically
    observable — ``speedup_8v1_invokers`` is computed from these rows.

``--smoke`` additionally *asserts* the acceptance bars: warm p50 < 0.2x
cold p50, sync throughput at 8 invokers >= 4x throughput at 1, and the
group-commit path >= 2x the sync path at the widest pool.
"""

from __future__ import annotations

import numpy as np

from repro.api import ClusterConfig, TierSpec
from repro.core import StatefulFunction
from repro.storage import DeviceSpec

from benchmarks.common import emit, make_client


def _percentile(samples, q):
    s = sorted(samples)
    return s[min(len(s) - 1, int(q * len(s)))]


def _counter_fn():
    def step(state, x):
        state = dict(state)
        state["n"] = state["n"] + x
        return state, state["n"]

    def init(payload_bytes=1024):
        return {"n": 0, "payload": np.zeros(payload_bytes // 8, np.float64)}

    return StatefulFunction("serve", step, init=init, jit=False)


def _latency_part(
    state_bytes: int,
    per_session: int,
    sessions: int,
    sleep_scale: float,
    windows: int = 3,
):
    """Warm vs cold invocation latency (seconds) from InvocationRecords.

    Each mode runs a warmup pass (session inits, allocator/interpreter
    warm-up — excluded from the samples), then ``windows`` measurement
    windows; the reported p50 is the median of the per-window p50s and
    the p99 pools every windowed sample.
    """
    results = {}
    for mode, warm_pool in (("warm", sessions + 8), ("cold", 1)):
        # The sleeping simulated tier is the Ignite/PMEM state tier: cold
        # starts pay its read latency + bandwidth for the state blob.
        cfg = ClusterConfig(
            name="fig7lat",
            tiers=(TierSpec("pmem", sleep=True, sleep_scale=sleep_scale),),
            invokers=1,
            warm_pool=warm_pool,
            commit_every=1_000_000_000,
        )
        with make_client(cfg) as client:
            client.register(_counter_fn())
            init_kwargs = {"payload_bytes": state_bytes}
            rt = client.runtime

            def one_pass():
                for s in range(sessions):
                    client.gateway.invoke(
                        "serve", session=f"s{s}", x=1, init_kwargs=init_kwargs
                    )

            one_pass()  # warmup: cold inits + first-touch costs, excluded
            window_p50 = []
            pooled = []
            for _ in range(windows):
                # the log keeps only the most recent records: start the
                # window's afresh
                rt.log.clear()
                for _ in range(per_session):
                    one_pass()
                # keep warm hits (mode=warm) / cache reloads (mode=cold);
                # any stray cold *init* is excluded
                lat = [
                    r.wall_seconds
                    for r in list(rt.log)
                    if not r.cold and (r.warm == (mode == "warm"))
                ]
                window_p50.append(_percentile(lat, 0.50))
                pooled.extend(lat)
        p50 = sorted(window_p50)[len(window_p50) // 2]
        p99 = _percentile(pooled, 0.99)
        results[mode] = (p50, p99)
        emit(
            f"fig7/latency/{mode}",
            p50 * 1e6,
            f"p50_us={p50 * 1e6:.1f};p99_us={p99 * 1e6:.1f};n={len(pooled)}",
        )
    return results


#: serving state device: ~1 ms writes so invocation cost is device-bound
#: (sleep releases the GIL — what real tier I/O would do) and thread
#: scaling across invokers is physically observable.
SERVE_SPEC = DeviceSpec(
    name="pmem_serve",
    read_bw=40 * 2**30,
    write_bw=13 * 2**30,
    read_latency=0.5e-3,
    write_latency=1.0e-3,
)


def _throughput_part(
    invoker_counts, sessions: int, per_session: int, group_commit: bool, row: str
):
    """Invocations/sec for the same workload at each pool size."""
    import time

    out = {}
    for n_inv in invoker_counts:
        cfg = ClusterConfig(
            name="fig7tp",
            tiers=(TierSpec(device=SERVE_SPEC, sleep=True),),
            invokers=n_inv,
            warm_pool=sessions + 8,
            commit_every=1,
            group_commit=group_commit,
        )
        with make_client(cfg) as client:
            client.register(_counter_fn())
            # warmup: materialize every session so the timed window is
            # pure warm-path (no cold inits skewing the short run)
            for s in range(sessions):
                client.gateway.invoke("serve", session=f"s{s}", x=0)
            t0 = time.perf_counter()
            futures = []
            for k in range(per_session):
                for s in range(sessions):
                    futures.append(client.gateway.submit("serve", session=f"s{s}", x=1))
            for f in futures:
                f.result(timeout=120)
            dt = time.perf_counter() - t0
        n = sessions * per_session
        out[n_inv] = n / dt
        emit(
            f"fig7/{row}/invokers={n_inv}",
            dt / n * 1e6,
            f"inv_per_s={n / dt:.1f};n={n}",
        )
    return out


def main(
    invoker_counts=(1, 2, 4, 8),
    sessions: int = 16,
    per_session: int = 10,
    state_bytes: int = 1 << 18,
    latency_sessions: int = 8,
    latency_per_session: int = 20,
    sleep_scale: float = 300.0,
    gc_scale: int = 24,
    smoke: bool = False,
) -> None:
    lat = _latency_part(state_bytes, latency_per_session, latency_sessions, sleep_scale)
    # sync rows: per-invocation tier writes — invoker scaling is the story
    tp_sync = _throughput_part(
        invoker_counts,
        sessions,
        per_session,
        group_commit=False,
        row="throughput_sync",
    )
    # group-commit rows: batched flushes — absolute inv/s is the story.
    # The volume is scaled up (gc_scale) so fixed flush costs amortize.
    tp = _throughput_part(
        invoker_counts,
        sessions,
        per_session * gc_scale,
        group_commit=True,
        row="throughput",
    )
    warm_p50, cold_p50 = lat["warm"][0], lat["cold"][0]
    ratio = warm_p50 / max(cold_p50, 1e-12)
    lo, hi = min(invoker_counts), max(invoker_counts)
    speedup = tp_sync[hi] / max(tp_sync[lo], 1e-12)
    gc_gain = tp[hi] / max(tp_sync[hi], 1e-12)
    emit(
        "fig7/summary",
        warm_p50 * 1e6,
        f"warm_over_cold_p50={ratio:.4f};"
        f"speedup_{hi}v{lo}_invokers={speedup:.2f};"
        f"group_commit_gain={gc_gain:.2f}",
    )
    if smoke:
        # acceptance bars — fail the benchmark harness if serving-side
        # scaling, the warm pool, or the batched commit path regresses
        assert ratio < 0.2, (
            f"warm p50 {warm_p50 * 1e6:.1f}us not < 0.2x cold p50 "
            f"{cold_p50 * 1e6:.1f}us"
        )
        assert speedup >= 4.0, (
            f"sync throughput at {hi} invokers only {speedup:.2f}x of {lo}"
        )
        assert gc_gain >= 2.0, (
            f"group commit only {gc_gain:.2f}x over sync at {hi} invokers"
        )


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="scaled-down run that asserts the acceptance bars",
    )
    args = ap.parse_args()
    if args.smoke:
        main(
            invoker_counts=(1, 8),
            sessions=12,
            per_session=8,
            latency_sessions=6,
            latency_per_session=10,
            smoke=True,
        )
    else:
        main()
