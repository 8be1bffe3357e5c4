"""Collective operations in a traced window: on each device, the time in
which a collective is in flight, and the part of it in which nothing
else runs.

A collective is an op whose instruction or opcode names one (all-gather,
all-reduce, reduce-scatter, collective-permute, all-to-all). A
synchronous one is in flight for its own event; an asynchronous one from
the start of its `-start` half to the end of its `-done` half (the two
share a name but for that word), in between which other ops may run.
Ops on a device's line do not overlap but by nesting, so "nothing else"
means no op that holds no op inside it: the `while` of the layer scan,
which spans its body, is not counted as running, the fusions of its
body are.
"""
from __future__ import annotations

import re
from collections import deque
from typing import Deque, Dict, List, Tuple

import numpy as np

from chipbench import trace as tr
from chipbench.scopes import instruction, self_times

_OPS = r"(?:all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)"
_NAME = re.compile(_OPS)
_OPCODE = re.compile(r"\s" + _OPS + r"(?:-start|-done)?\(")


def is_collective(event: str) -> bool:
    return bool(_NAME.search(instruction(event)) or _OPCODE.search(event))


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        tot += max(0.0, hi - lo)
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return tot


def in_flight(rows) -> List[tr.Interval]:
    """The collectives' intervals: each synchronous op's own, each
    async pair's from start to done. The rows come in time order, and a
    `-done` closes the earliest open `-start` of its name: the names
    repeat in every iteration of a loop and in every step."""
    open_starts: Dict[str, Deque[Tuple[float, float]]] = {}
    out = []
    for s, e, n, *_ in rows:
        if not is_collective(n):
            continue
        name = instruction(n)
        if "-start" in name:
            open_starts.setdefault(name.replace("-start", "-done"),
                                   deque()).append((s, e))
            continue
        starts = open_starts.get(name)
        if "-done" in name and starts:
            s = starts.popleft()[0]
        out.append((s, e, name))
    out += [(s, e, name) for name, q in open_starts.items() for s, e in q]
    return out


def per_device(trace: tr.Trace) -> List[Tuple[float, float]]:
    """(seconds with a collective in flight, seconds in which only
    collectives run) of each device, within the window."""
    lo, hi = trace.window()
    out = []
    for d in trace.devices:
        rows = self_times(d.ops, lo, hi)
        parents = {r[4] for r in rows if r[4] is not None}
        other = [(s, e, n) for i, (s, e, n, _, _) in enumerate(rows)
                 if i not in parents and not is_collective(n)]
        c = tr.union(in_flight(rows), lo, hi)
        out.append((tr.length(c),
                    tr.length(c) - overlap(c, tr.union(other, lo, hi))))
    return out
