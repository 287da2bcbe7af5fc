"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time, op time by name, and idle gaps
labelled by what the host was doing.

Device planes are named ``/device:TPU:<id>``; their ``XLA Ops`` line holds
one event per executed operation.  The traced window is the host span named
``WINDOW_ANNOTATION``, which the harness opens around the traced part of
the window; device events are clipped to it.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
WINDOW_ANNOTATION = "bench_window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

# (name, start_ns, end_ns, stats)
Event = Tuple[str, float, float, Dict[str, object]]


_HLO = re.compile(r"^%?([\w.\-]+) = (.*?)\s([a-z][\w\-]*)\(")


def opcode(name: str) -> str:
    """The HLO opcode of an op event named by its instruction text
    (``%fusion.7 = bf16[...] fusion(...)`` -> ``fusion``); the name itself
    where it is no instruction text."""
    m = _HLO.match(name)
    return m.group(3) if m else name


def short_name(name: str) -> str:
    """``%fusion.7 = bf16[3,4096]{...} fusion(...)`` -> ``fusion.7
    bf16[3,4096] fusion``: the instruction, its result shape and opcode."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    shape = re.sub(r"\{[^{}]*\}", "", m.group(2))
    return f"{m.group(1)} {shape} {m.group(3)}"[:120]


def find_xplane(path: Path) -> Path:
    path = Path(path)
    if path.is_file():
        return path
    found = sorted(glob.glob(str(path / "plugins" / "profile" / "*" /
                                "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return Path(found[-1])


def union_seconds(intervals: Sequence[Tuple[float, float]], lo: float,
                  hi: float) -> float:
    """Length, in seconds, of the union of ``[start, end)`` nanosecond
    intervals clipped to ``[lo, hi)``."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total * 1e-9


def gaps(intervals: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The idle ``(start, end)`` stretches of ``[lo, hi)`` that no interval
    covers, in nanoseconds."""
    out = []
    cur = lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class TraceSummary:
    window: Tuple[float, float]             # ns, on the trace's clock
    device_ids: List[int]
    ops: Dict[int, List[Event]]             # device id -> op events
    host: List[Event]                       # host spans (all threads)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_per_device(self) -> List[float]:
        lo, hi = self.window
        return [union_seconds([(s, e) for _, s, e, _ in self.ops[d]], lo, hi)
                for d in self.device_ids]

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices used."""
        b = self.busy_per_device
        return sum(b) / len(b) if b else 0.0

    def op_seconds(self, match, device: Optional[int] = None) -> List[float]:
        """Per device (or for one), the seconds of the ops for which
        ``match(name, stats)`` is true, clipped to the window."""
        lo, hi = self.window
        devs = self.device_ids if device is None else [device]
        out = []
        for d in devs:
            tot = 0.0
            for name, s, e, stats in self.ops[d]:
                if match(name, stats):
                    tot += max(0.0, min(e, hi) - max(s, lo)) * 1e-9
            out.append(tot)
        return out

    def op_count(self, match, device: int) -> int:
        lo, hi = self.window
        return sum(1 for name, s, e, stats in self.ops[device]
                   if match(name, stats) and s < hi and e > lo)

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (seconds per op name,
        averaged over the devices used) and the idle time of the first
        device grouped by the innermost host span at each gap's middle."""
        lo, hi = self.window
        per_name: Dict[str, float] = {}
        n = max(len(self.device_ids), 1)
        for d in self.device_ids:
            for name, s, e, _ in self.ops[d]:
                dt = max(0.0, min(e, hi) - max(s, lo)) * 1e-9
                key = short_name(name)
                per_name[key] = per_name.get(key, 0.0) + dt / n
        ops = sorted(per_name.items(), key=lambda kv: -kv[1])[:top]
        idle: Dict[str, float] = {}
        if self.device_ids:
            d0 = self.device_ids[0]
            spans = sorted((s, e, name) for name, s, e, _ in self.host
                           if name != WINDOW_ANNOTATION)
            # sweep the gaps by their middle; of the host spans covering a
            # middle, the one that started last is the innermost
            covering: list = []
            i = 0
            for gs, ge in gaps([(s, e) for _, s, e, _ in self.ops[d0]],
                               lo, hi):
                mid = 0.5 * (gs + ge)
                while i < len(spans) and spans[i][0] <= mid:
                    heapq.heappush(covering, (-spans[i][0], spans[i][1],
                                              spans[i][2]))
                    i += 1
                while covering and covering[0][1] < mid:
                    heapq.heappop(covering)
                label = covering[0][2] if covering else "(no host span)"
                idle[label] = idle.get(label, 0.0) + (ge - gs) * 1e-9
        gap_list = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gap_list]}


def _stats(ev) -> Dict[str, object]:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def reduce(path, devices=None) -> TraceSummary:
    """Read the trace at ``path`` (a trace directory or an xplane file).
    ``devices`` (with ``.id``) restricts the device planes to those used."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(find_xplane(path)))
    wanted = None if devices is None else {int(d.id) for d in devices}
    ops: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if wanted is not None and dev not in wanted:
                continue
            evs = ops.setdefault(dev, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = float(ev.start_ns)
                    evs.append((ev.name, s, s + float(ev.duration_ns),
                                _stats(ev)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = float(ev.start_ns)
                    host.append((ev.name, s, s + float(ev.duration_ns), {}))
    windows = [(s, e) for name, s, e, _ in host if name == WINDOW_ANNOTATION]
    if not windows:
        raise ValueError(f"no {WINDOW_ANNOTATION!r} span in the trace {path}")
    window = max(windows, key=lambda w: w[1] - w[0])
    return TraceSummary(window, sorted(ops), ops, host)
