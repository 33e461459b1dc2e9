"""The program's own serving spans in a profiler trace, and what is read
from them and from ``Request``'s stamps.

With a ``repro.obs`` tracer in annotate mode, the serving path writes its
spans into the profiler's trace as ``TraceAnnotation``s named as in
``SPANS``, with their args as event stats, on the host plane beside the
harness's own spans (``bench/harness/tracing.py``).  ``load`` reads both
kinds, so ``tracing.reduce`` of the result attributes each idle gap to the
innermost span of either kind.  The readings return ``None`` where the
program has no such span or stamp, as a program without the annotating
tracer has none.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.harness import tracing

SPANS = ("executor.admit", "executor.scatter", "prefill.dispatch",
         "decode.block", "frontier.drain", "frontier.wait", "frontier.apply",
         "frontier.commit", "host.gc")
LONGEST = 5         # idle gaps listed one by one


@dataclasses.dataclass
class ProgramTrace:
    trace: tracing.Trace     # device events; harness and program spans
    program: List[tuple]     # (start s, end s, name, stats) of program spans


def load(log_dir: str) -> ProgramTrace:
    """The newest ``.xplane.pb`` under ``log_dir``, with the program's
    spans added to the harness's."""
    from jax.profiler import ProfileData
    trace = tracing.load(log_dir)
    path = max(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    program = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        s = e.start_ns * 1e-9
                        program.append((s, s + e.duration_ns * 1e-9, e.name,
                                        {k: v for k, v in e.stats}))
    program.sort(key=lambda p: p[0])
    trace.spans = trace.spans + [p[:3] for p in program]
    return ProgramTrace(trace, program)


def _named(pt: ProgramTrace, name: str) -> List[tuple]:
    """Spans called ``name`` that start inside the window."""
    a, b = tracing.window_of(pt.trace)
    return [p for p in pt.program if p[2] == name and a <= p[0] < b]


def overlap(xs, ys) -> float:
    """Seconds two lists of sorted, disjoint (start, end) intervals share."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        lo = max(xs[i][0], ys[j][0])
        hi = min(xs[i][1], ys[j][1])
        total += max(0.0, hi - lo)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def admission_idle_ms(pt: ProgramTrace) -> Optional[float]:
    """Idle device time inside the union of the window's
    ``executor.admit`` spans, per request they admitted (the spans' ``n``),
    averaged over the devices that ran anything."""
    used = [lst for lst in pt.trace.ops.values() if lst]
    admits = _named(pt, "executor.admit")
    n = sum(p[3].get("n", 0) for p in admits)
    if not used or not n:
        return None
    a, b = tracing.window_of(pt.trace)
    held = tracing.union(p[:2] for p in admits)
    idle = sum(overlap(tracing.gaps(tracing.union(lst), a, b), held)
               for lst in used) / len(used)
    return 1e3 * idle / n


def frontier_host_ms(pt: ProgramTrace) -> Optional[float]:
    """Mean time of a ``frontier.drain`` of the window outside its
    ``frontier.wait`` children: the host's apply and commit."""
    drains = _named(pt, "frontier.drain")
    if not drains:
        return None
    waits = sorted(p[:2] for p in pt.program if p[2] == "frontier.wait")
    starts = [s for s, _ in waits]
    host = []
    for s, e, *_ in drains:
        lo, hi = bisect.bisect_left(starts, s), bisect.bisect_right(starts, e)
        host.append(e - s - sum(min(we, e) - ws for ws, we in waits[lo:hi]))
    return 1e3 * float(np.mean(host))


def _p95_ms(values) -> Optional[float]:
    return 1e3 * float(np.percentile(values, 95)) if values else None


def queue_p95_ms(requests: dict, due: List[int], closed: float
                 ) -> Optional[float]:
    """p95 of ``admit_t - submit_t``, the wait for a slot, over the
    requests due in the window that were admitted before it closed at
    ``closed`` (perf_counter): after the close a traced run stops serving
    while it writes the trace."""
    return _p95_ms([r.admit_t - r.submit_t for r in stamped(
        requests, due, "admit_t", closed)])


def commit_wait_p95_ms(requests: dict, due: List[int], closed: float
                       ) -> Optional[float]:
    """p95 of ``first_t - prefilled_t``, a first token on the host
    waiting for a drain to commit it, over the requests due in the window
    whose first token was committed before it closed."""
    return _p95_ms([r.first_t - r.prefilled_t for r in stamped(
        requests, due, "first_t", closed)])


def stamped(requests: dict, due: List[int], stamp: str, closed: float):
    """Requests of ``due`` stamped ``stamp`` before ``closed``; none where
    ``Request`` has no such stamp."""
    rs = [requests[r] for r in due]
    return [r for r in rs if 0.0 < getattr(r, stamp, 0.0) <= closed]


def longest_gaps(pt: ProgramTrace, k: int = LONGEST
                 ) -> List[Tuple[float, float, str]]:
    """The ``k`` longest idle gaps of the window on the first device that
    ran anything: (start from the window's start, seconds, the innermost
    span open at the gap's middle, ``host`` where none was)."""
    used = [lst for lst in pt.trace.ops.values() if lst]
    if not used:
        return []
    a, b = tracing.window_of(pt.trace)
    idle = tracing.gaps(tracing.union(used[0]), a, b)
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:k]:
        name, = tracing.attribute([(s, e)], pt.trace.spans)
        out.append((s - a, e - s, name))
    return out


def span_seconds(pt: ProgramTrace) -> Dict[str, dict]:
    """Count and host seconds of each program span in the window."""
    out: Dict[str, dict] = {}
    for name in SPANS:
        spans = _named(pt, name)
        if spans:
            out[name] = {"count": len(spans),
                         "seconds": sum(e - s for s, e, *_ in spans)}
    return out
