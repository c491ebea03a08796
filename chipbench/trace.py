"""Reduce one profiler trace (``.xplane.pb``) to the benchmark's numbers.

The window is the span of the host annotations ``call:<front end>`` the
harness opens around each traced call.  Inside it:

* device busy time: the union of the intervals in which an XLA op ran on
  the device (plane ``/device:TPU:<n>``, line ``XLA Ops``), averaged
  over the chips;
* device time per XLA module (line ``XLA Modules``: ``jit_walk``,
  ``jit_fill``, ...), the program id suffix dropped;
* idle gaps: the complement of busy time, each gap named for the
  innermost host event open on the annotating thread at its midpoint
  (what the host was doing while the device waited), summed per name.

A trace with no device plane is an error: host events are never read as
device time.
"""

from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_PROGRAM_ID = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                     # mean over chips
    chips: int
    modules: dict[str, float]         # module -> device seconds, mean/chip
    module_runs: dict[str, int]       # module -> executions, mean/chip
    gaps: dict[str, float]            # host activity -> idle seconds

    def breakdown(self, top: int = 10) -> dict:
        mods = sorted(self.modules.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in mods],
                "idle_gaps": [[k, v] for k, v in gaps]}


@dataclasses.dataclass
class Context:
    """What a per-layer reader sees."""

    reduced: Reduced
    calls: int                        # traced calls that completed
    peaks: dict                       # peaks.json entry of this device
    peak_bytes: int                   # peak_bytes_in_use after the window
    kernels: list                     # frontends.Kernel per call

    def module_s_per_call(self, *stages: str) -> float | None:
        """Device seconds per call in the modules of ``stages`` (``None``
        where the trace holds none of them)."""
        names = [f"jit_{s}" for s in stages]
        if not self.calls or not any(n in self.reduced.modules
                                     for n in names):
            return None
        return sum(self.reduced.modules.get(n, 0.0)
                   for n in names) / self.calls


def _events(plane):
    for line in plane.lines:
        for ev in line.events:
            yield line.name, ev


def reduce(path: str, annotation: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), annotation)


def reduce_profile(pd, annotation: str) -> Reduced:
    """``reduce`` of an already loaded ``jax.profiler.ProfileData``."""
    planes = list(pd.planes)
    host = [p for p in planes if p.name.startswith("/host:")]
    calls, thread = [], None
    for p in host:
        for line in p.lines:
            evs = [e for e in line.events if e.name == annotation]
            if evs:
                calls, thread = evs, line
    if not calls:
        raise ValueError(f"no host annotation {annotation!r} in the trace")
    lo = min(e.start_ns for e in calls)
    hi = max(e.start_ns + e.duration_ns for e in calls)

    devices = [p for p in planes if _DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("no device plane in the trace: "
                         f"{sorted(p.name for p in planes)}")
    per_chip = []                     # (ops, modules) per device plane
    for p in devices:
        ops, mods = [], []
        for lname, ev in _events(p):
            if lname == "XLA Ops":
                ops.append(ev)
            elif lname == "XLA Modules":
                mods.append((_PROGRAM_ID.sub("", ev.name), ev))
        per_chip.append((ops, mods))

    busy_total, modules, runs, idle = 0.0, {}, {}, []
    for ops, mods in per_chip:
        busy = union(clip([(e.start_ns, e.start_ns + e.duration_ns)
                           for e in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        for name, ev in mods:
            s, e = max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi)
            if e > s:
                modules[name] = modules.get(name, 0.0) + (e - s)
                runs[name] = runs.get(name, 0) + 1
        prev = lo
        for s, e in busy + [(hi, hi)]:
            if s > prev:
                idle.append((prev, s))
            prev = max(prev, e)
    n = len(per_chip)
    # host events of one thread nest, so a sweep over the gap midpoints
    # with a stack of open events finds the innermost one at each
    host_evs = sorted(((e.start_ns, -e.duration_ns, e.name)
                       for e in thread.events))
    gaps: dict[str, float] = {}
    stack: list[tuple[float, str]] = []       # (end, name), innermost last
    i = 0
    for s, e in sorted(idle, key=lambda g: g[0] + g[1]):
        mid = (s + e) / 2
        while i < len(host_evs) and host_evs[i][0] <= mid:
            start, neg_dur, name = host_evs[i]
            while stack and stack[-1][0] <= start:
                stack.pop()
            stack.append((start - neg_dur, name))
            i += 1
        while stack and stack[-1][0] <= mid:
            stack.pop()
        name = stack[-1][1] if stack else "outside any host event"
        gaps[name] = gaps.get(name, 0.0) + (e - s) * 1e-9 / n
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n, chips=n,
        modules={k: v * 1e-9 / n for k, v in modules.items()},
        module_runs={k: v // n for k, v in runs.items()}, gaps=gaps)
