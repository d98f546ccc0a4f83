"""The reduction from a profiler trace to metrics.

``Tracer`` records a JAX profiler trace; ``start`` and ``halt`` put the
host markers ``chipbench.trace.start`` and ``chipbench.trace.stop`` into
it, and the traced window is the time between them.  ``reduce`` reads
the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and gives a
``Summary``:

- busy time: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices; idle is the rest
  of the window;
- every run of every program (XLA module) that lies wholly inside the
  window, with the executable's id, and device time per operation
  (``<program>/<op>``);
- idle gaps of the first device, each labelled by the benchmark-side
  host span (``chipbench.<name>``, see ``spans.py``) that overlapped it
  most, or ``"no span"``;
- the host spans themselves, with the stats they carried;
- whether the profiler dropped events (its buffers fill when a program
  runs millions of operations); a reader then finds nothing to read.
"""

from __future__ import annotations

import glob
import re
import shutil
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from chipbench.spans import PREFIX, union_length

NO_SPAN = "no span"
DROPPED = "Trace Buffers Dropped"
_ID = re.compile(r"\((\d+)\)$")
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)$")


def program_key(name: str) -> str:
    """A module name without the executable id XLA appends, and without
    trailing underscores, so that ``module_name(jitted)`` finds it."""
    return _SUFFIX.sub("", name).rstrip("_")


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[..] fusion(..), ..`` -> ``fusion.3``; a custom
    call keeps its target (a Pallas kernel is ``tpu_custom_call``)."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    target = re.search(r'custom_call_target="([^"]+)"', hlo)
    return f"{name}:{target.group(1)}" if target else name


def _marker(name: str) -> None:
    import jax

    with jax.profiler.TraceAnnotation(PREFIX + name):
        pass


class Tracer:
    def __init__(self, directory: Path, keep: Optional[Path] = None) -> None:
        self.dir = Path(directory)
        #: where to copy the raw ``.xplane.pb`` before it is deleted
        self.keep = keep

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        # host annotations only: no Python function tracing, no HLO protos
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        _marker("trace.start")

    def halt(self) -> None:
        import jax

        _marker("trace.stop")
        jax.profiler.stop_trace()

    def summary(self) -> "Summary":
        files = glob.glob(str(self.dir / "**" / "*.xplane.pb"), recursive=True)
        if not files:
            raise RuntimeError(f"no trace written under {self.dir}")
        if self.keep is not None:
            Path(self.keep).parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(files[0], self.keep)
        out = reduce(files[0])
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


@dataclass
class Summary:
    window_s: float
    busy_s: float
    devices: int
    dropped: bool = False
    #: program key -> [(executable id, start ns, device seconds)], runs
    #: wholly inside the window
    runs: Dict[str, List[Tuple[str, float, float]]] = field(default_factory=dict)
    #: "<program key>/<op>" -> device seconds summed over devices
    ops: Dict[str, float] = field(default_factory=dict)
    #: host span label -> idle seconds on the first device
    idle_by_span: Dict[str, float] = field(default_factory=dict)
    #: host spans inside the window: (start ns, end ns, name, stats)
    spans: List[Tuple[float, float, str, Dict[str, Any]]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program(self, key: str) -> Tuple[int, float]:
        """(runs, device seconds) of a program inside the window."""
        got = self.runs.get(program_key(key), [])
        return len(got), sum(r[2] for r in got)

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of operations whose ``<program>/<op>`` name
        matches the regular expression ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for name, s in self.ops.items() if rx.search(name))

    def breakdown(self, n: int = 10) -> Dict[str, List[List]]:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def _stats(ev: Any) -> Dict[str, Any]:
    try:
        return {k: v for k, v in dict(ev.stats).items() if not k.startswith("_")}
    except Exception:  # noqa: BLE001 - stats of odd types are left out
        return {}


def reduce(path: str) -> Summary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    spans: List[Tuple[float, float, str, Dict[str, Any]]] = []
    devices: List[Dict] = []
    dropped = False
    for plane in data.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        spans.append((ev.start_ns, ev.end_ns,
                                      ev.name[len(PREFIX):], _stats(ev)))
        elif _is_device(plane.name):
            lines = {line.name: line for line in plane.lines}
            traceme = lines.get("XLA TraceMe")
            if traceme is not None and any(ev.name == DROPPED
                                           for ev in traceme.events):
                dropped = True
            if "XLA Ops" in lines or "XLA Modules" in lines:
                devices.append({"ops": lines.get("XLA Ops"),
                                "modules": lines.get("XLA Modules")})
    starts = [b for a, b, n, _ in spans if n == "trace.start"]
    stops = [a for a, b, n, _ in spans if n == "trace.stop"]
    if starts and stops:
        w0, w1 = min(starts), max(stops)
    else:
        ends = [(ev.start_ns, ev.end_ns) for d in devices
                for ln in d.values() if ln is not None for ev in ln.events]
        w0, w1 = min(a for a, _ in ends), max(b for _, b in ends)

    def clip(a: float, b: float) -> Optional[Tuple[float, float]]:
        a, b = max(a, w0), min(b, w1)
        return (a, b) if b > a else None

    runs: Dict[str, List[Tuple[str, float, float]]] = defaultdict(list)
    ops: Dict[str, float] = defaultdict(float)
    busy_total = 0.0
    first_busy: List[Tuple[float, float]] = []
    for i, dev in enumerate(devices):
        modules: List[Tuple[float, float, str]] = []
        if dev["modules"] is not None:
            for ev in dev["modules"].events:
                iv = clip(ev.start_ns, ev.end_ns)
                if iv is None:
                    continue
                key = program_key(ev.name)
                modules.append((iv[0], iv[1], key))
                if iv == (ev.start_ns, ev.end_ns):
                    exe = _ID.search(ev.name)
                    runs[key].append((exe.group(1) if exe else "", iv[0],
                                      (iv[1] - iv[0]) * 1e-9))
        modules.sort()
        busy: List[Tuple] = []
        src = dev["ops"] if dev["ops"] is not None else dev["modules"]
        for ev in src.events:
            iv = clip(ev.start_ns, ev.end_ns)
            if iv is not None:
                busy.append(iv + (ev.name,))
        busy.sort()
        if dev["ops"] is not None:
            # an operation belongs to the program running when it starts
            m = 0
            names: Dict[Tuple[str, str], str] = {}
            for a, b, name in busy:
                while m < len(modules) and modules[m][1] <= a:
                    m += 1
                inside = m < len(modules) and modules[m][0] <= a
                key = (modules[m][2] if inside else "?", name)
                if key not in names:
                    names[key] = f"{key[0]}/{op_name(name)}"
                ops[names[key]] += (b - a) * 1e-9
        busy = [(a, b) for a, b, _ in busy]
        busy_total += union_length(busy) * 1e-9
        if i == 0:
            first_busy = busy
    labels = [s[:3] for s in spans
              if s[2] not in ("window", "trace.start", "trace.stop")]
    return Summary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_total / max(1, len(devices)),
        devices=len(devices),
        dropped=dropped,
        runs=dict(runs),
        ops=dict(ops),
        idle_by_span=_label_gaps(_gaps(first_busy, w0, w1), labels),
        spans=[s for s in spans if s[0] >= w0 and s[1] <= w1
               and not s[2].startswith("trace.")],
    )


def _gaps(busy: List[Tuple[float, float]], w0: float, w1: float
          ) -> List[Tuple[float, float]]:
    out, t = [], w0
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if w1 > t:
        out.append((t, w1))
    return out


def _label_gaps(gaps: List[Tuple[float, float]],
                spans: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Idle seconds by the host span that overlapped each gap most."""
    spans = sorted(spans)
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for a, b in gaps:
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        best, label = 0.0, NO_SPAN
        for s0, s1, name in spans[j:]:
            if s0 >= b:
                break
            ov = min(b, s1) - max(a, s0)
            if ov > best:
                best, label = ov, name
        out[label] += (b - a) * 1e-9
    return dict(out)
