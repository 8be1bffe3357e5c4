"""From a profiler trace to device intervals, and from those to numbers.

`load` reads the `.xplane.pb` that `jax.profiler` writes into a `Trace`:
for each device, the operations that ran on it (name, start, end), and
the benchmark's own host spans,
which all start with `cb.` (`span` below). The reductions work on that
plain form, so a test can check them on a recorded trace.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

SPAN_PREFIX = "cb."

Interval = Tuple[float, float, str]      # (start_s, end_s, name)


@dataclass
class Device:
    name: str
    ops: List[Interval] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[Device]
    spans: List[Interval]                # the benchmark's host spans

    def window(self) -> Tuple[float, float]:
        """The measured window: the `cb.window` span."""
        w = [s for s in self.spans if s[2] == SPAN_PREFIX + "window"]
        if not w:
            raise ValueError("trace holds no cb.window span")
        return w[0][0], w[0][1]


def span(name: str):
    """A host span the trace reductions can find."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} xplane files under "
                                f"{trace_dir}")
    return found[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        return from_profile(ProfileData.from_serialized_xspace(f.read()))


def from_profile(pd) -> Trace:
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = _events(line)
            if dev.ops:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e[2].startswith(SPAN_PREFIX)]
    devices.sort(key=lambda d: d.name)
    spans.sort()
    return Trace(devices, spans)


def _events(line) -> List[Interval]:
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
            for e in line.events]


# -- interval arithmetic --------------------------------------------------------

def union(iv: List[Interval], lo: float, hi: float) -> np.ndarray:
    """Merged (start, end) rows of the intervals, clipped to [lo, hi]."""
    rows = sorted((max(s, lo), min(e, hi)) for s, e, _ in iv
                  if e > lo and s < hi)
    out: List[List[float]] = []
    for s, e in rows:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, float).reshape(-1, 2)


def length(u: np.ndarray) -> float:
    return float((u[:, 1] - u[:, 0]).sum()) if len(u) else 0.0


# -- reductions -------------------------------------------------------------------

def busy_s(tr: Trace) -> float:
    """Seconds of the window in which some operation ran, averaged over
    the devices."""
    lo, hi = tr.window()
    return float(np.mean([length(union(d.ops, lo, hi)) for d in tr.devices]))


def top_ops(tr: Trace, n: int = 10) -> List[list]:
    """The operations that took the most device time in the window,
    summed by name over the devices and averaged per device."""
    lo, hi = tr.window()
    tot: Dict[str, float] = {}
    for d in tr.devices:
        for s, e, name in d.ops:
            if e > lo and s < hi:
                tot[name] = tot.get(name, 0.0) + min(e, hi) - max(s, lo)
    k = len(tr.devices)
    return [[name, t / k] for name, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10) -> List[list]:
    """The longest gaps in the window in which the first device ran
    nothing, each named by the innermost benchmark span that covers its
    middle (`none` where no span does)."""
    lo, hi = tr.window()
    u = union(tr.devices[0].ops, lo, hi)
    edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
    gaps = [(e - s, s, e) for s, e in edges if e > s]
    gaps.sort(reverse=True)
    out = []
    for dur, s, e in gaps[:n]:
        mid = 0.5 * (s + e)
        inner = [sp for sp in tr.spans if sp[0] <= mid <= sp[1]
                 and sp[2] != SPAN_PREFIX + "window"]
        label = (min(inner, key=lambda sp: sp[1] - sp[0])[2]
                 if inner else "none")
        out.append([label, dur])
    return out
