"""Driver for a model served through ``MarvelClient.serving()``.

Set-up: the weights are drawn on the device from the seed, the client and
its ``ServingPool`` are built as the configuration's deployment (with the
cell's changes), and one warm-up conversation per prompt length of the
mix runs through the pool, so that nothing compiles in the window.

Window: conversations from the mix, open loop: each is started at its due
time.  Each token is one ``pool.start`` or ``pool.step``; its time is
when its future resolves.  At the close no new step is submitted; those
in flight are waited for.

End-to-end metrics: ``serve_tokens_per_s`` (tokens completed in the
window over its length), ``ttft_p50_s`` (from each conversation's due
time to its first token), ``decode_ms_per_token`` (the time in which at
least one decode step was in flight, from the harness's ``pool.step`` to
its token, over the decode steps: every one submitted in the window,
those finished in the drain included), ``setup_s``.

Correctness: a sample of finished conversations, drawn from the seed with
the longest among them, up to ``check.served_tokens`` served tokens; the
reference runs over each prompt and its served tokens, and the widest gap
by which a served token's logit lies below the reference's best is held
to ``check.max_logit_gap``.
"""

from __future__ import annotations

import queue
import re
import threading
import time
from typing import Any, Callable, Dict, List, Sequence, Tuple

import numpy as np

from chipbench.harness import Check, Outcome, RunContext, memory_peak
from chipbench.spans import union_length

#: how long past the close a step may take before it counts as lost
DRAIN_S = 60.0


def module_name(jitted: Any) -> str:
    """The XLA module name JAX gives a jitted function."""
    return ("jit_" + re.sub(r"[^\w.-]", "_", jitted.__name__)).rstrip("_")


def cluster_config(dep: Dict, state_dir) -> Any:
    from repro.api import ClusterConfig, ServingConfig, TierSpec

    tiers = tuple(
        TierSpec(t["kind"], capacity_bytes=t.get("capacity_bytes"),
                 path=str(state_dir / t["path"]) if t.get("path") else None)
        for t in dep["tiers"])
    journal_path = (str(state_dir / "journal") if dep["journal"] == "pmem"
                    else None)
    return ClusterConfig(
        name="chipbench-serve", tiers=tiers, invokers=dep["invokers"],
        warm_pool=dep["warm_pool"], commit_every=dep["commit_every"],
        journal=dep["journal"], journal_path=journal_path,
        serving=ServingConfig(**dep["serving"]),
    )


class _Conv:
    __slots__ = ("c", "due", "sent", "tokens", "times", "failed")

    def __init__(self, c: Any, due: float) -> None:
        self.c = c
        self.due = due
        self.sent = due  # submit time of the step in flight
        self.tokens: List[int] = []
        self.times: List[float] = []
        self.failed = False


class Window:
    """One measured window of conversations through ``pool``."""

    def __init__(self, ctx: RunContext, pool: Any, mix: Dict) -> None:
        self.ctx, self.pool, self.mix = ctx, pool, mix
        self.done: "queue.Queue" = queue.Queue()
        self.inflight = 0
        self.failed = 0
        self.shed = 0
        self.lost = 0
        self.convs: List[_Conv] = []
        #: harness submit time of each session's current step
        self.submitted: Dict[str, float] = {}
        #: (submit, resolved) of every prefill (``start``) and decode step
        self.calls: Dict[str, List[Tuple[float, float]]] = {"start": [], "step": []}

    def _submit(self, cv: _Conv, first: bool) -> bool:
        from repro.core.gateway import AdmissionError

        try:
            cv.sent = time.perf_counter()
            if first:
                fut = self.pool.start(cv.c.cid, cv.c.prompt[None, :])
            else:
                self.submitted[self.pool._scoped(cv.c.cid)] = cv.sent
                fut = self.pool.step(cv.c.cid)
        except AdmissionError:
            self.shed += 1
            cv.failed = True
            return False
        self.inflight += 1
        fut.add_done_callback(
            lambda f, cv=cv: self.done.put((cv, time.perf_counter(), f)))
        return True

    def run(self, convs: List[Any], seconds: float,
            timers: Sequence[Tuple[float, Callable[[], None]]] = ()) -> None:
        """Serve ``convs`` for ``seconds``; each ``(delay, fn)`` of
        ``timers`` runs on a thread of its own ``delay`` seconds after the
        window opens."""
        t0 = time.perf_counter()
        self.t0, self.end = t0, t0 + seconds
        self.timers = [threading.Timer(delay, fn) for delay, fn in timers]
        for timer in self.timers:
            timer.start()
        pending = [c for c in convs if c.due < seconds][::-1]
        lateness: List[float] = []
        while True:
            now = time.perf_counter()
            if now < self.end:
                while pending and t0 + pending[-1].due <= now:
                    c = pending.pop()
                    cv = _Conv(c, t0 + c.due)
                    self.convs.append(cv)
                    self._submit(cv, True)
                    lateness.append(time.perf_counter() - cv.due)
            elif self.inflight == 0:
                break
            if now < self.end:
                nxt = (t0 + pending[-1].due) if pending else self.end
                timeout = max(0.0, min(nxt, self.end) - now)
            else:
                timeout = max(0.0, self.end + DRAIN_S - now)
                if timeout == 0.0:
                    self.lost = self.inflight
                    break
            try:
                cv, t, fut = self.done.get(timeout=timeout)
            except queue.Empty:
                continue
            self.inflight -= 1
            if fut.exception() is not None:
                self.ctx.log(f"{cv.c.cid}: {fut.exception()!r}")
                cv.failed = True
                self.failed += 1
                continue
            self.calls["step" if cv.tokens else "start"].append((cv.sent, t))
            cv.tokens.append(int(np.asarray(fut.result())[0, 0]))
            cv.times.append(t)
            if len(cv.tokens) >= cv.c.out_tokens:
                self.pool.drop(cv.c.cid)
            elif time.perf_counter() < self.end:
                self._submit(cv, False)
        self.closed = time.perf_counter()
        for timer in self.timers:
            timer.join()
        if lateness:
            self.ctx.log(f"generator lateness: mean {np.mean(lateness):.6f} s, "
                         f"max {np.max(lateness):.6f} s over {len(lateness)}")

    def gaps(self) -> List[float]:
        """Every gap between successive tokens of a conversation that
        closes in the window."""
        return [b - a for cv in self.convs
                for a, b in zip(cv.times, cv.times[1:]) if b <= self.end]

    def metrics(self) -> Dict[str, float]:
        seconds = self.end - self.t0
        in_window = sum(1 for cv in self.convs for t in cv.times if t <= self.end)
        ttft = [cv.times[0] - cv.due for cv in self.convs if cv.times]
        steps = len(self.calls["step"])
        return {
            "serve_tokens_per_s": in_window / seconds,
            "ttft_p50_s": float(np.percentile(ttft, 50)) if ttft else float("inf"),
            "decode_ms_per_token": (1e3 * self.busy_s("step") / steps if steps
                                    else float("inf")),
        }

    def busy_s(self, kind: str) -> float:
        """Seconds in which at least one call of ``kind`` was in flight."""
        return union_length(sorted(self.calls[kind]))


def warm_up(ctx: RunContext, pool: Any, mix: Dict, vocab: int) -> None:
    """One conversation per prompt length of the mix: prefill and two
    decode steps."""
    lengths = sorted(set(mix["prompt_tokens"]["lengths"]))
    rng = np.random.default_rng([ctx.seed, 0x7761726D])
    for i, length in enumerate(lengths):
        cid = f"warm{i}"
        prompt = rng.integers(0, vocab, (1, length), dtype=np.int32)
        np.asarray(pool.start(cid, prompt).result())
        for _ in range(2):
            np.asarray(pool.step(cid).result())
        pool.drop(cid)


def _instrument(ctx: RunContext, pool: Any, win: Window) -> None:
    sp = ctx.spans
    dec = pool.decoder

    def load_wait(sid: str, *_a: Any) -> None:
        t = win.submitted.pop(sid, None)
        if t is not None:
            sp.note("gateway_wait_s", time.perf_counter() - t)

    sp.wrap(pool.pager, "load", "pager.load", before=load_wait)
    sp.wrap(pool.pager, "write", "pager.write",
            before=lambda sid, layers, t: sp.note("decode_positions", int(t) + 1))
    sp.wrap(pool.pager, "demote", "pager.demote")
    sp.wrap(pool.pager, "resume", "pager.resume")
    # a prefill's span closes when its outputs are on the device, so that
    # its length is the prefill's time (one prefill's device work is
    # 0.05-1.5 s, far above the host clock's error)
    sp.wrap(dec, "_prefill", "prefill",
            before=lambda params, prompt: sp.note("prefill_tokens", prompt.shape[1]),
            meta=lambda params, prompt: {"tokens": int(prompt.shape[1])},
            block=True)
    sp.wrap(dec, "_decode", "decode")


class Served:
    """The set-up: weights from the seed, the client and its pool, warmed
    up for every prompt length of the mix."""

    def __init__(self, ctx: RunContext) -> None:
        import jax

        from repro.api import MarvelClient

        cell, cfg = ctx.cell, ctx.cell.config
        self.mix = mix = cell.traffic
        self.dep = dep = cell.deployment
        self.prog = cell.module("configs", cfg["program"])
        self.ref = cell.module("configs", cfg["reference"])
        self.gen = cell.module("traffic", mix["generator"])
        self.vocab = int(cfg["vocab_size"])
        self.weights = self.prog.draw_weights(cfg, ctx.seed)
        jax.block_until_ready(self.weights)
        ctx.log(f"weights drawn at {time.perf_counter() - ctx.t_start:.3f} s")
        self.client = MarvelClient(cluster_config(dep, ctx.state_dir))
        self.pool = self.client.serving(
            self.prog.program_params(self.weights), self.prog.program_config(cfg),
            prompt_len=dep["prompt_len"], max_tokens=dep["max_tokens"])
        warm_up(ctx, self.pool, mix, self.vocab)

    def conversations(self, mix: Dict, seed: int, seconds: float,
                      tag: str = "c") -> List[Any]:
        n = self.gen.open_loop_count(mix, seconds)
        return self.gen.conversations(mix, seed, n, self.vocab, tag)


def _timed(ctx: RunContext, fn: Callable[[], None]) -> None:
    t = time.perf_counter()
    fn()
    ctx.log(f"{fn.__name__} took {time.perf_counter() - t:.3f} s")


def run(ctx: RunContext) -> Outcome:
    s = Served(ctx)
    convs = s.conversations(s.mix, ctx.seed, ctx.seconds)
    dec = s.pool.decoder
    programs = {"decode": module_name(dec._decode),
                "prefill": module_name(dec._prefill)}
    hidden = _capture_prefill_tokens(dec)
    win = Window(ctx, s.pool, s.mix)
    timers: List[Tuple[float, Callable[[], None]]] = []
    if ctx.trace:
        _instrument(ctx, s.pool, win)
        # The profiler keeps a few million device events; a 1024-token
        # prefill makes ~4.5M of them, so only the middle of the window
        # is traced.
        span = min(ctx.seconds, float(ctx.cell.workload["trace_seconds"]))
        tracer = ctx.tracer()
        lead = (ctx.seconds - span) / 2
        timers = [(lead, tracer.start), (lead + span, lambda: _timed(ctx, tracer.halt))]
    setup_s = time.perf_counter() - ctx.t_start
    ctx.spans.active = ctx.compiles.active = True
    with ctx.spans.span("window"):
        win.run(convs, ctx.seconds, timers)
    ctx.spans.active = ctx.compiles.active = False
    t = time.perf_counter()
    summary = tracer.summary() if ctx.trace else None
    if summary is not None:
        ctx.log(f"trace read in {time.perf_counter() - t:.3f} s")
    peak = memory_peak(int(ctx.cell.workload["chips"]))
    pstats = s.pool.stats()
    s.client.close()
    s.pool = s.client = None

    if summary is not None:
        ctx.log(f"trace: {summary.window_s:.3f} s, dropped {summary.dropped}, "
                f"decode program {summary.program(programs['decode'])}, "
                f"prefill program {summary.program(programs['prefill'])}")
    e2e = win.metrics()
    e2e["setup_s"] = setup_s
    gaps = win.gaps()
    ctx.log(f"pool: {pstats}; shed {win.shed}, failed {win.failed}, lost "
            f"{win.lost}; compiles in window {ctx.compiles.compiles} "
            f"{ctx.compiles.names[:8]}")
    if gaps:
        ctx.log(f"gaps: {len(gaps)}, mean {1e3 * np.mean(gaps):.3f} ms, p50 "
                f"{1e3 * np.percentile(gaps, 50):.3f} ms, p95 "
                f"{1e3 * np.percentile(gaps, 95):.3f} ms; decode steps in "
                f"flight {win.busy_s('step'):.3f} s, prefills "
                f"{win.busy_s('start'):.3f} s")
    readings: Dict[str, Any] = {}
    controls: Dict[str, List[Check]] = {}
    checks = _check(ctx, s.ref, s.weights, s.dep, win, hidden, readings, controls)
    decoded = sum(max(0, sum(1 for t in cv.times if t <= win.closed) - 1)
                  for cv in win.convs)
    counters = {
        "window_s": win.closed - win.t0,
        "decoded_tokens": decoded,
        "decode_busy_s": win.busy_s("step"),
        "prefill_busy_s": win.busy_s("start"),
        "programs": programs,
        "readings": readings,
    }
    return Outcome(e2e=e2e, attempted=len(win.convs),
                   failed=win.failed + win.shed,
                   checks=checks, memory_peak_bytes=peak, counters=counters,
                   trace=summary, control_checks=controls)


def _capture_prefill_tokens(dec: Any) -> Dict[bytes, Any]:
    """The token each prefill picks is fed to the first decode step and
    never returned to the client; keep it (on the device, no sync) by
    prompt, so that the reference can follow the same sequence."""
    hidden: Dict[bytes, Any] = {}
    orig = dec._prefill

    def prefill(params: Any, prompt: Any) -> Any:
        out = orig(params, prompt)
        hidden[np.asarray(prompt).tobytes()] = out[0]
        return out

    dec._prefill = prefill
    return hidden


def _check(ctx: RunContext, ref: Any, weights: Any, dep: Dict, win: Window,
           hidden: Dict[bytes, Any], readings: Dict[str, Any],
           controls: Dict[str, List[Check]]) -> List[Check]:
    spec = ctx.cell.workload["check"]
    finished = [cv for cv in win.convs
                if not cv.failed and len(cv.tokens) == cv.c.out_tokens]
    checks = [Check("lost_steps", float(win.lost), 0.0)]
    if not finished:
        return checks + [Check("finished_conversations_missing", 1.0, 0.0)]
    rng = np.random.default_rng([ctx.seed, 0x636B])
    longest = max(finished, key=lambda cv: (len(cv.tokens), len(cv.c.prompt)))
    rest = [finished[i] for i in rng.permutation(len(finished))
            if finished[i] is not longest]
    sample, served = [longest], len(longest.tokens)
    for cv in rest:
        if served >= spec["served_tokens"]:
            break
        sample.append(cv)
        served += len(cv.tokens)
    t = time.perf_counter()
    out: Dict[str, List[np.ndarray]] = {}
    for cv in sample:
        p0 = int(np.asarray(hidden[cv.c.prompt[None, :].tobytes()]).reshape(-1)[0])
        got = ref.served_gaps(
            weights, ctx.cell.config, cv.c.prompt, np.asarray([p0] + cv.tokens),
            dep["prompt_len"] + dep["max_tokens"], dep["max_tokens"] + 1,
            controls=tuple(ctx.control.split(",")) if ctx.control else ())
        for k, v in got.items():
            out.setdefault(k, []).append(v)
    worst = {k: float(max(g.max() for g in v)) for k, v in out.items()}
    readings.update({k: v for k, v in worst.items() if k != "served"},
                    program=worst["served"], sample=len(sample),
                    served_tokens=served)
    ctx.log(f"reference over {len(sample)} conversations, {served} served "
            f"tokens, in {time.perf_counter() - t:.3f} s: {worst}")
    for k, v in worst.items():
        if k != "served":
            controls[k] = checks + [Check("max_logit_gap", v, spec["max_logit_gap"])]
    return checks + [Check("max_logit_gap", worst["served"],
                           spec["max_logit_gap"])]
