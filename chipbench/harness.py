"""The harness: finds a cell's files by name, runs its driver, reads its
metrics and prints the result.

Everything a cell is lives in data files found by name:

- ``BENCHMARK.json`` (checkout root): the cell's entry and the metrics
  that it reports;
- ``workloads/<cell>.json``: configuration, traffic, chips, why, and the
  deployment settings that this cell changes;
- ``configs/<config>.json``: the configuration's sizes, source, the
  deployment, and the names of its driver, program adapter and reference;
- ``traffic/<traffic>.json``: the mix, read by the generator it names;
- ``metrics/<metric>.py``: one reader per per-layer metric;
- ``drivers/<driver>.py``: one driver per kind of system path;
- ``peaks.json``: per-chip peaks keyed by ``device_kind``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import re
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

_modules: Dict[Path, ModuleType] = {}


def load_json(path: Path) -> Dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path) -> ModuleType:
    """Import a file by path (metric files have dots in their names)."""
    path = Path(path).resolve()
    if path not in _modules:
        if not path.exists():
            raise FileNotFoundError(path)
        name = "chipbench_file_" + re.sub(r"\W", "_", str(path.with_suffix("")))
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        _modules[path] = mod
    return _modules[path]


def benchmark(root: Path = ROOT) -> Dict:
    return load_json(root / "BENCHMARK.json")


@dataclass
class Cell:
    name: str
    workload: Dict
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    base: Path = HERE

    @property
    def deployment(self) -> Dict:
        """The configuration's deployment with this cell's changes."""
        out = json.loads(json.dumps(self.config["deployment"]))
        for k, v in self.workload.get("deployment", {}).items():
            if isinstance(v, dict) and isinstance(out.get(k), dict):
                out[k].update(v)
            else:
                out[k] = v
        return out

    def module(self, kind: str, name: str) -> ModuleType:
        return load_module(self.base / kind / f"{name}.py")


def reports(metric: Dict, cell: str, bench: Dict) -> bool:
    """Whether ``cell`` reports ``metric``: listed in its ``workloads``,
    or, without that key, wherever the metric it moves is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    target = next(m for m in bench["end_to_end"] if m["name"] == moves)
    return reports(target, cell, bench)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of the checkout at ``root``, from its files."""
    bench = benchmark(root)
    base = Path(root) / "chipbench"
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    workload = load_json(base / "workloads" / f"{name}.json")
    for key in ("config", "traffic", "chips", "why"):
        if workload[key] != entry[key]:
            raise ValueError(f"{name}: {key} differs between BENCHMARK.json "
                             f"and workloads/{name}.json")
    return Cell(
        name=name,
        workload=workload,
        config=load_json(base / "configs" / f"{entry['config']}.json"),
        traffic=load_json(base / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if reports(m, name, bench)],
        per_layer=[m for m in bench["per_layer"] if reports(m, name, bench)],
        base=base,
    )


@dataclass
class Check:
    """One number compared with its limit: the run is correct only if
    ``value <= limit`` for every check."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Outcome:
    """What a driver hands back."""

    e2e: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: int
    #: driver counts read by per-layer metrics (tokens, sizes, positions)
    counters: Dict[str, Any] = field(default_factory=dict)
    trace: Any = None  # trace.Summary of the traced window
    #: per control (``control:<precision>``), the checks with the control's
    #: reading in the program's place
    control_checks: Dict[str, List[Check]] = field(default_factory=dict)


@dataclass
class RunContext:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float  # process start on the host clock: set-up begins
    peak: Optional[Dict[str, float]]
    state_dir: Path
    spans: Any
    compiles: Any
    #: lower precisions (comma-separated) in which to also run the
    #: reference: control readings for setting a limit; never set by the
    #: command line
    control: Optional[str] = None
    #: where a traced run keeps its raw trace (tools only)
    keep_trace: Optional[Path] = None

    def tracer(self) -> Any:
        from chipbench.trace import Tracer

        return Tracer(self.state_dir / "trace", keep=self.keep_trace)

    def log(self, msg: str) -> None:
        print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """JAX's own compile events (a jit cache miss: compiled anew or read
    from the persistent cache) and persistent-cache hits, while active."""

    _installed: Optional["CompileCounter"] = None

    def __init__(self) -> None:
        self.active = False
        self.compiles = 0
        self.cache_hits = 0
        self.names: List[str] = []
        if CompileCounter._installed is None:
            import jax.monitoring as mon

            mon.register_event_duration_secs_listener(_on_duration)
            mon.register_event_listener(_on_event)
        CompileCounter._installed = self


def _on_duration(event: str, _secs: float, **kw: Any) -> None:
    c = CompileCounter._installed
    if c is not None and c.active and event == "/jax/core/compile/backend_compile_duration":
        c.compiles += 1
        c.names.append(str(kw.get("fun_name", "")))


def _on_event(event: str, **_kw: Any) -> None:
    c = CompileCounter._installed
    if c is not None and c.active and event == "/jax/compilation_cache/cache_hits":
        c.cache_hits += 1


def enable_compile_cache() -> str:
    """The program's persistent cache (``$JAX_COMPILATION_CACHE_DIR`` or
    the fixed ``<checkout>/.jax_cache``), keeping every program."""
    import jax

    from repro.compile_cache import enable_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, require_tpu: bool) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(
            f"chipbench: needs {chips} TPU chip(s); JAX has "
            f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, t_start: Optional[float] = None,
             state_root: Optional[Path] = None,
             control: Optional[str] = None,
             keep_trace: Optional[Path] = None) -> Dict[str, Any]:
    """Run one cell and return its result line as a dict.  The command
    line always requires the chip and keeps the persistent compile cache;
    tests call this with ``require_tpu=False`` at smoke sizes, without
    the cache.  With ``control`` the result also carries ``readings``:
    the program's and the control's numbers before their limits, and
    ``control_correct``: ``correct`` as it would read with each control's
    number held to the program's limit."""
    from chipbench import spans as spans_mod
    from chipbench.peaks import peaks

    t_start = time.perf_counter() if t_start is None else t_start
    chips = int(cell.workload["chips"])
    device = device_info(chips, require_tpu)
    cache_dir = enable_compile_cache() if require_tpu else "off"
    try:
        peak = peaks(device["kind"])
    except KeyError:
        if require_tpu:
            raise
        peak = None
    state_dir = (state_root or ROOT / ".chipbench_state") / cell.name
    shutil.rmtree(state_dir, ignore_errors=True)
    state_dir.mkdir(parents=True)
    ctx = RunContext(cell=cell, seed=seed, seconds=seconds, trace=trace,
                     t_start=t_start, peak=peak, state_dir=state_dir,
                     spans=spans_mod.Spans(annotate=trace),
                     compiles=CompileCounter(), control=control,
                     keep_trace=keep_trace)
    ctx.log(f"{cell.name} seed {seed} seconds {seconds} trace {int(trace)} "
            f"on {device['count']} x {device['kind']}; compile cache {cache_dir}")
    driver = cell.module("drivers", cell.config["driver"])
    try:
        out: Outcome = driver.run(ctx)
    finally:
        ctx.spans.unwrap()
        shutil.rmtree(state_dir, ignore_errors=True)

    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for m in cell.per_layer:
            reader = cell.module("metrics", m["name"])
            value = reader.read(out, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": out.e2e[m["name"]], "unit": m["unit"]}
    device["memory_peak_bytes"] = out.memory_peak_bytes
    result: Dict[str, Any] = {
        "correct": all(c.ok for c in out.checks) and bool(out.checks),
        "attempted": out.attempted,
        "failed": out.failed,
        "metrics": metrics,
        "device": device,
    }
    if trace and out.trace is not None:
        device["busy_s"] = out.trace.busy_s
        device["window_s"] = out.trace.window_s
        result["breakdown"] = out.trace.breakdown()
    if control is not None:
        result["readings"] = out.counters.get("readings", {})
        result["control_correct"] = {
            name: all(c.ok for c in checks) and bool(checks)
            for name, checks in out.control_checks.items()}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    return result


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description="Marvel-JAX chip benchmark")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start)
    print(f"correct = {result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
