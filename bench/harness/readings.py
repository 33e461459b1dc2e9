"""What one run measured, as the metric readers under ``bench/metrics``
read it, and the arithmetic two or more of them share."""
from __future__ import annotations

import dataclasses
from typing import List, Optional

from bench.harness.work import Sizes, least_seconds


@dataclasses.dataclass
class Run:
    setup_s: float
    boot_s: float
    summary: dict              # serve.summary of the window
    trace: dict                # tracing.reduce of the traced window ({})
    exe_names: dict            # "prefill"/"decode" -> executable name
    sizes: Sizes
    peak: dict                 # bench/peaks.json entry of the device
    blocks: List[tuple]        # per decode block: (positions, steps) [B]
    prefills: List[int]        # prompt length of each prefill
    dispatch_s: List[float]    # host time of each decode_block call


def executable(run: Run, kind: str) -> Optional[dict]:
    """{"count", "seconds"} of one executable in the traced window."""
    mods = run.trace.get("modules", {})
    r = mods.get(run.exe_names.get(kind))
    return r if r and r["count"] else None


def prefill_ms(run: Run) -> Optional[float]:
    r = executable(run, "prefill")
    return 1e3 * r["seconds"] / r["count"] if r else None


def decode_work(run: Run) -> List[tuple]:
    """(flops, bytes) of each decode block of the traced window."""
    return [run.sizes.decode_block(zip(pos, steps))
            for pos, steps in run.blocks]


def decode_least(run: Run) -> tuple:
    """(least seconds of the traced window's decode blocks, the bound that
    most of that time is under)."""
    total, by = 0.0, {"compute": 0.0, "memory": 0.0}
    for f, b in decode_work(run):
        t, bound = least_seconds(f, b, run.peak)
        total += t
        by[bound] += t
    return total, max(by, key=by.get)
