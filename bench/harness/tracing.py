"""From a JAX profiler trace to device busy time, idle gaps and the device
time of each executable, on one clock with the harness's host spans.

A trace is read into plain lists (``load``), and every number comes from
those lists (``reduce``), so the reduction is tested on synthetic traces.
Device events are those of planes named ``/device:...``: the ``XLA Ops``
line (one event per operation, for busy time and the top operations) and
the ``XLA Modules`` line (one event per executable run).  Host spans are
the ``TraceAnnotation``s the harness opens (``SPANS``) on any host plane.
Busy time is the union of the operations' intervals inside the ``window``
span, averaged over the devices that ran anything.  The top operations
leave out control flow (a ``while`` spans the operations of its body).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

SPANS = ("window", "submit", "step_block", "validate", "prefill",
         "decode_block")
TOP = 10
CONTAINERS = ("while", "conditional", "call")  # ops that hold other ops
LOOKBACK = 256     # spans an enclosing span can start before a gap

Interval = Tuple[float, float, str]      # (start s, end s, name)


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Interval]]       # device plane -> operations
    modules: Dict[str, List[Interval]]   # device plane -> executable runs
    spans: List[Interval]                # host spans of the harness


def module_name(event_name: str) -> str:
    """``jit_fused(123)`` -> ``jit_fused``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    ops, modules, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                into = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if into is None:
                    continue
                dst = into.setdefault(plane.name, [])
                name = op_name if into is ops else module_name
                for e in line.events:
                    s = e.start_ns * 1e-9
                    dst.append((s, s + e.duration_ns * 1e-9, name(e.name)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        s = e.start_ns * 1e-9
                        spans.append((s, s + e.duration_ns * 1e-9, e.name))
    return Trace(ops, modules, spans)


def union(intervals) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) of possibly overlapping intervals."""
    out: List[List[float]] = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged, a: float, b: float) -> List[Tuple[float, float]]:
    return [(max(s, a), min(e, b)) for s, e in merged if e > a and s < b]


def gaps(merged, a: float, b: float) -> List[Tuple[float, float]]:
    """Idle (start, end) intervals of ``[a, b]`` between busy ones."""
    out, t = [], a
    for s, e in clip(merged, a, b):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < b:
        out.append((t, b))
    return out


def attribute(idle, spans: List[Interval]) -> Dict[str, float]:
    """Idle seconds by the innermost harness span (other than ``window``)
    open at each gap's midpoint; ``host`` where none was."""
    inner = sorted((s, e, n) for s, e, n in spans if n != "window")
    starts = [s for s, _, _ in inner]
    out: Dict[str, float] = collections.defaultdict(float)
    for a, b in idle:
        mid = (a + b) / 2
        name = "host"
        i = bisect.bisect_right(starts, mid)
        for s, e, n in reversed(inner[max(0, i - LOOKBACK):i]):
            if e >= mid:
                name = n
                break
        out[name] += b - a
    return dict(out)


def window_of(trace: Trace) -> Tuple[float, float]:
    win = [(s, e) for s, e, n in trace.spans if n == "window"]
    if win:
        return win[-1]
    events = [iv for lst in trace.ops.values() for iv in lst]
    return min(s for s, _, _ in events), max(e for _, e, _ in events)


def reduce(trace: Trace) -> dict:
    """Busy and idle time, executable runs and the top operations inside
    the traced window.  Returns ``{}`` when no device ran anything."""
    used = {d: lst for d, lst in trace.ops.items() if lst}
    if not used:
        return {}
    a, b = window_of(trace)
    busy, idle_by = [], collections.Counter()
    for lst in used.values():
        merged = union(lst)
        busy.append(sum(e - s for s, e in clip(merged, a, b)))
        for name, secs in attribute(gaps(merged, a, b), trace.spans).items():
            idle_by[name] += secs / len(used)
    per_op = collections.Counter()
    for lst in used.values():
        for s, e, n in lst:
            if a <= s < b and not n.startswith(CONTAINERS):
                per_op[n] += (min(e, b) - s) / len(used)
    runs: Dict[str, dict] = {}
    planes = [lst for lst in trace.modules.values() if lst]
    for lst in planes:
        for s, e, n in lst:
            if a <= s < b:
                r = runs.setdefault(module_name(n), {"count": 0,
                                                     "seconds": 0.0})
                r["count"] += 1
                r["seconds"] += e - s
    for r in runs.values():          # each chip runs every executable
        r["count"] = round(r["count"] / len(planes))
        r["seconds"] /= len(planes)
    return {"window_s": b - a, "busy_s": sum(busy) / len(busy),
            "devices": len(used), "modules": runs,
            "device_ops": [[n, s] for n, s in per_op.most_common(TOP)],
            "idle_gaps": [[n, s] for n, s in idle_by.most_common(TOP)]}
