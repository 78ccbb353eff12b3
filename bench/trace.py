"""Profiler trace of the window, and its reduction to device numbers.

The reduction reads only what the profiler records of the device: the
programs it ran (the "XLA Modules" line of each device plane) and their
operations (the "XLA Ops" line).  Programs are sorted into top-K and the
rest by ``programs.json``; anything the table does not name counts to the
eq. (1) iteration, so a later change that renames or replaces the step still
counts the same work.  Host planes are read only to say what the host was
doing while the device was idle.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
TABLE = Path(__file__).with_name("programs.json")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Device:
    modules: List[Event]
    ops: List[Event]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, Device]
    host: List[Event]              # every host-plane event, all threads
    window_ns: Tuple[float, float]  # the window on the trace's own clock


def program_name(module_event: str) -> str:
    """``jit_step(123)`` -> ``step``: the jitted function's name."""
    name = module_event.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def op_name(hlo: str) -> str:
    """``%fusion.1 = s32[1048576,16]{...} fusion(...), kind=kCustom, ...``
    -> ``%fusion.1 s32[1048576,16] kCustom``: name, result shape, kind."""
    name, _, rest = hlo.partition(" = ")
    shape = re.match(r"\(?([\w]+\[[\d,]*\])", rest)
    shape = shape.group(1) if shape else ""
    kind = re.search(r"kind=(\w+)", rest)
    return " ".join(x for x in (name, shape, kind.group(1) if kind else "")
                    if x)


def load_table(path: Path = TABLE) -> Dict[str, List[str]]:
    return json.loads(path.read_text())


def from_profile(pd, window_ns: Tuple[float, float]) -> Trace:
    """A ``jax.profiler.ProfileData`` as plain events."""
    devices, host = {}, []
    for plane in pd.planes:
        lines = {line.name: [Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events] for line in plane.lines}
        if _DEVICE_PLANE.match(plane.name):
            devices[plane.name] = Device(lines.get(MODULES_LINE, []),
                                         lines.get(OPS_LINE, []))
        elif plane.name.startswith("/host:"):
            for events in lines.values():
                host.extend(events)
    return Trace(devices, host, window_ns)


def load(log_dir: Path, window_ns: Tuple[float, float]) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(str(files[-1])), window_ns)


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Length of the union of the events' intervals inside [lo, hi]."""
    return sum(max(0.0, min(b, hi) - max(a, lo))
               for a, b in union([(e.start_ns, e.end_ns) for e in events]))


@dataclasses.dataclass
class Summary:
    """What the per-layer readers read, averaged over the devices traced."""
    busy_s: float
    window_s: float
    waves: int                     # top-K program runs up to the last one
    iteration_s: float             # other program time up to the last top-K
    topk_s: float                  # top-K program time
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def summarize(trace: Trace, table: Optional[Dict[str, List[str]]] = None,
              top: int = 10) -> Optional[Summary]:
    """None when the trace holds no device program."""
    table = load_table() if table is None else table
    topk_names = set(table["topk"])
    devs = [d for d in trace.devices.values() if d.modules or d.ops]
    if not devs:
        return None
    lo, hi = trace.window_ns
    busy = waves = iteration = topk = 0.0
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for dev in devs:
        busy += busy_ns(dev.ops or dev.modules, lo, hi)
        runs = sorted(dev.modules, key=lambda e: e.start_ns)
        last = max((e.end_ns for e in runs
                    if program_name(e.name) in topk_names), default=None)
        for e in runs:
            if last is None or e.end_ns > last:
                continue
            if program_name(e.name) in topk_names:
                waves += 1
                topk += e.dur_ns
            else:
                iteration += e.dur_ns
        starts = [m.start_ns for m in runs]
        for e in dev.ops:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            owner = runs[i] if i >= 0 and e.start_ns < runs[i].end_ns else None
            key = (program_name(owner.name) + "/" if owner else "") + \
                op_name(e.name)
            op_time[key] = op_time.get(key, 0.0) + e.dur_ns
        gaps.extend(_idle_gaps(dev.ops or dev.modules, trace.host, lo, hi,
                               top))
    n = len(devs)
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, key=lambda g: -g[1])[:top]
    return Summary(busy_s=busy / n / 1e9, window_s=(hi - lo) / 1e9,
                   waves=int(round(waves / n)), iteration_s=iteration / n / 1e9,
                   topk_s=topk / n / 1e9,
                   device_ops=[(k, v / n / 1e9) for k, v in ops],
                   idle_gaps=[(k, v / 1e9) for k, v in gaps])


def _idle_gaps(events: Sequence[Event], host: Sequence[Event], lo: float,
               hi: float, top: int) -> List[Tuple[str, float]]:
    """The ``top`` longest idle stretches of the device inside [lo, hi],
    each named by the host event that overlaps it most among those no
    longer than ten times the gap (longer ones wrap whole runs and say
    nothing), or "host idle"."""
    busy = union([(e.start_ns, e.end_ns) for e in events])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    stretches = sorted(((max(a, lo), min(b, hi))
                        for a, b in zip(edges[::2], edges[1::2])),
                       key=lambda ab: ab[0] - ab[1])[:top]
    out = []
    for a, b in stretches:
        if b <= a:
            continue
        best, overlap = "host idle", 0.0
        for h in host:
            if h.dur_ns > 10 * (b - a):
                continue
            ov = min(b, h.end_ns) - max(a, h.start_ns)
            if ov > overlap:
                best, overlap = h.name, ov
        out.append((best, b - a))
    return out
