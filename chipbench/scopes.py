"""Device self time of the train step, by pass and by program scope.

The program names its layers with `jax.named_scope` (`embed`, `norm`,
`attention/qkv`, `attention/core`, `attention/out`, `ffn`, `moe`, `ssm`,
`loss`, `optimizer`). Every transform keeps those names in each HLO
instruction's `op_name`, beside the marks of the pass that made it:
`jvp(` for the forward pass, `transpose(` for the backward pass and
`rematted_computation` for a recomputed forward. The trace names each
device op by its instruction, so `op_names` of the compiled step's
text gives each op its path.

The ops of the TPU's `XLA Ops` line nest (a `while` holds the ops of
its body), so each op is counted by its self time: its duration less
what the ops inside it cover. An op the compiler left without an
`op_name` (a layout copy) takes that of the innermost op around it.

`read(name, facts, trace)` has the form of a metric reader: `facts`
holds the step's instruction map (`scope_ops`), its module name
(`step_module`) and the window's `steps`; without the map it reads
nothing.
"""
from __future__ import annotations

import bisect
import functools
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from chipbench import trace as tr

SCOPES = ("embed", "norm", "attention/qkv", "attention/core",
          "attention/out", "ffn", "moe", "ssm", "loss", "optimizer")
OTHER_MODULES = "other_modules"          # the pass of ops outside the step

# each metric: device self time per window step, in ms, of one pass (all
# scopes) or one scope (all passes)
METRICS = {
    "forward_ms.train": ("pass", "forward"),
    "recompute_ms.train": ("pass", "recompute"),
    "backward_ms.train": ("pass", "backward"),
    "optimizer_ms.train": ("scope", "optimizer"),
    "attn_core_ms.train": ("scope", "attention/core"),
    "ffn_ms.train": ("scope", "ffn"),
    "loss_ms.train": ("scope", "loss"),
}

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\bmetadata=\{[^}]*?"
                    r'op_name="([^"]*)"', re.M)
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


@dataclass
class Device(tr.Device):
    modules: List[tr.Interval] = field(default_factory=list)


def load(path: str) -> tr.Trace:
    """`trace.load`, with each device's `XLA Modules` events kept as
    `modules` beside its ops."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    t = tr.from_profile(pd)
    modules = {plane.name: tr._events(line) for plane in pd.planes
               for line in plane.lines if line.name == "XLA Modules"}
    t.devices = [Device(d.name, d.ops, modules.get(d.name, []))
                 for d in t.devices]
    return t


# -- the compiled step ---------------------------------------------------------

def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> `op_name`, for every instruction of the HLO
    text that carries one."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


def module_name(hlo_text: str) -> str:
    return _MODULE.match(hlo_text).group(1)


def instruction(event_name: str) -> str:
    """`%fusion.38 = bf16[...] fusion(...)` -> `fusion.38`."""
    return event_name.split("=", 1)[0].strip().lstrip("%")


def _module_of(event_name: str) -> str:
    """`jit_step(1234)` -> `jit_step`."""
    return event_name.split("(", 1)[0]


# -- op_name paths ---------------------------------------------------------------

def _split(path: str) -> List[str]:
    """Components of a path, split at the `/`s outside parentheses."""
    out, cur, depth = [], [], 0
    for ch in path:
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    out.append("".join(cur))
    return out


def _names(path: str) -> List[str]:
    """The path's names, transforms opened (`transpose(jvp(loss))` ->
    `loss`) and `jit(f)` components dropped."""
    out = []
    for c in _split(path):
        if c.startswith("jit("):
            continue
        m = _WRAPPED.match(c)
        if m:
            out += _names(m.group(1))
        elif c:
            out.append(c)
    return out


def classify(op_name: Optional[str]) -> Tuple[str, str]:
    """(pass, innermost program scope or `none`) of an `op_name`."""
    if not op_name:
        return "unattributed", "none"
    path = op_name.split(";")[0]
    names = _names(path)
    scope, at = "none", -1
    for s in SCOPES:
        k = s.split("/")
        for i in range(len(names) - len(k) + 1):
            if names[i:i + len(k)] == k and i > at:
                scope, at = s, i
    if "optimizer" in names:
        return "optimizer", scope
    if "rematted_computation" in names:
        return "recompute", scope
    if any(c.startswith("transpose(") for c in _split(path)):
        return "backward", scope
    return "forward", scope


# -- the reduction ---------------------------------------------------------------

def self_times(ops: List[tr.Interval], lo: float, hi: float
               ) -> List[Tuple[float, float, str, float, Optional[int]]]:
    """The ops of one line clipped to [lo, hi], each as (start, end,
    name, self time, index of the innermost op that encloses it). An op
    that starts inside another is nested in it, and ends with it at the
    latest."""
    rows = sorted(((max(s, lo), min(e, hi), name) for s, e, name in ops
                   if e > lo and s < hi), key=lambda r: (r[0], -r[1]))
    out: List[list] = []
    stack: List[int] = []
    for s, e, name in rows:
        while stack and s >= out[stack[-1]][1]:
            stack.pop()
        parent = stack[-1] if stack else None
        if parent is not None:
            e = min(e, out[parent][1])
            out[parent][3] -= e - s
        out.append([s, e, name, e - s, parent])
        stack.append(len(out) - 1)
    return [tuple(r) for r in out]


def scope_times(trace: tr.Trace, names: Dict[str, str], module: str
                ) -> Dict[Tuple[str, str], float]:
    """Seconds of device self time in the window per (pass, scope),
    averaged over the devices. Ops of the step's module are classified
    by their instruction's `op_name` (or that of the innermost op around
    them); ops outside it count under (`other_modules`, `none`)."""
    lo, hi = trace.window()
    tot: Dict[Tuple[str, str], float] = {}
    classes = functools.lru_cache(maxsize=None)(classify)
    for d in trace.devices:
        steps = sorted((s, e) for s, e, n in getattr(d, "modules", [])
                       if _module_of(n) == module)
        starts = [s for s, _ in steps]
        rows = self_times(d.ops, lo, hi)
        resolved: List[Optional[str]] = []
        for s, e, name, own, parent in rows:
            op = names.get(instruction(name))
            if op is None and parent is not None:
                op = resolved[parent]
            resolved.append(op)
            k = bisect.bisect_right(starts, s) - 1
            inside = k >= 0 and s < steps[k][1]
            key = classes(op) if inside else (OTHER_MODULES, "none")
            tot[key] = tot.get(key, 0.0) + own
    n = max(len(trace.devices), 1)
    return {k: v / n for k, v in tot.items()}


def table(times: Dict[Tuple[str, str], float], steps: int
          ) -> Dict[str, Dict[str, float]]:
    """{pass: {scope: ms per step}}, the form of `detail.device_scopes`."""
    out: Dict[str, Dict[str, float]] = {}
    for (p, s), v in sorted(times.items()):
        out.setdefault(p, {})[s] = 1e3 * v / steps
    return out


def metric(name: str, times: Dict[Tuple[str, str], float], steps: int
           ) -> float:
    """The metric `name` of `METRICS` from `scope_times`, in ms per
    step."""
    by, which = METRICS[name]
    i = 0 if by == "pass" else 1
    return 1e3 * sum(v for k, v in times.items() if k[i] == which) / steps


def read(name: str, facts: dict, trace: tr.Trace) -> Optional[float]:
    """The metric `name` in a traced run, as a metric reader reads it;
    None where `facts` holds no scope map."""
    if facts.get("kind") != "train" or not facts.get("scope_ops") \
            or not facts.get("steps"):
        return None
    return metric(name, scope_times(trace, facts["scope_ops"],
                                    facts["step_module"]), facts["steps"])
