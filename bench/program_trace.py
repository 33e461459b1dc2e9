"""Run one cell as ``bench/run.py`` does, with the program's own serving
spans on: where the device's idle time sits inside the program, and what
the program's tracer costs.

    python3 bench/program_trace.py --workload <cell> --seed <n> \\
        --seconds <s> [--tracer on,off] [--out runs.jsonl]

One process makes one run of the cell per ``--tracer`` value, on the same
seed.  With ``on`` the engine gets a ``repro.obs`` tracer in annotate mode
and the window is watched for garbage collections (``host.gc``); with
``off`` it gets none, as in ``bench/run.py``.  Each run is traced by the
profiler as ``bench/run.py --trace 1`` traces it, and the trace is read
with the program's spans (``bench/harness/program.py``): the idle time by
the innermost span of either kind, the five longest idle gaps, each
program span's count and host seconds, the device idle time in admissions
per admitted request (``admission_idle_ms``) and the host time of a drain
outside its device waits (``frontier_host_ms``).  Every run also reads the
requests' stamps (``queue_p95_ms``, ``commit_wait_p95_ms``), the window's
seconds per decode block and its tokens per second.

Each run prints one JSON line; ``bench/run.py``'s own result is under
``run``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def run(cell, seed: int, seconds: float, tracer: bool, *,
        require_chip: bool = True, peak: dict = None) -> dict:
    """One run of ``cell`` through ``bench/run.py``'s ``run_cell``, with
    the program's tracer on or off; returns its readings."""
    from bench import run as bench_run
    from bench.harness import program, serve, tracing
    from repro.obs import NULL, Tracer

    tr = Tracer(annotate=True) if tracer else NULL
    kept = {}

    class Session(serve.Session):
        """The harness's session, with ``tr`` as the workspace's tracer
        and the served window kept for its requests' stamps."""

        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.ws.tracer = tr

        def serve(self, traffic, seconds, on_start=None, on_end=None):
            def closed():
                kept["closed"] = time.perf_counter()
                if on_end:
                    on_end()
            with tr.watch_gc():
                kept["served"] = super().serve(traffic, seconds, on_start,
                                               closed)
            return kept["served"]

    # the harness builds its own Session and takes no tracer
    with mock.patch.object(serve, "Session", Session):
        out = bench_run.run_cell(cell, seed, seconds, True,
                                 require_chip=require_chip, peak=peak)
    served = kept["served"]
    summ = serve.summary(served)
    blocks = summ["counters"].get("blocks_dispatched", 0)
    pt = program.load(os.path.join(bench_run.TRACE_DIR, cell.name))
    gaps = program.longest_gaps(pt)
    for start, secs, name in gaps:
        log(f"idle gap at {start:.3f} s of the window: {secs:.4f} s in "
            f"{name}")
    return {"workload": cell.name, "seed": seed, "tracer": tracer,
            "correct": out["correct"],
            "tokens_per_s": summ["tokens_per_s"],
            "ttft_p95_ms": summ["ttft_p95_ms"],
            "itl_p95_ms": summ["itl_p95_ms"],
            "window_s_per_block": served.seconds / blocks if blocks
            else None,
            "queue_p95_ms": program.queue_p95_ms(
                served.requests, served.in_window, kept["closed"]),
            "commit_wait_p95_ms": program.commit_wait_p95_ms(
                served.requests, served.in_window, kept["closed"]),
            "admitted_after_close": len(served.in_window) - len(
                program.stamped(served.requests, served.in_window,
                                 "admit_t", kept["closed"])),
            "counters": summ["counters"],
            "admission_idle_ms": program.admission_idle_ms(pt),
            "frontier_host_ms": program.frontier_host_ms(pt),
            "idle_gaps": tracing.reduce(pt.trace).get("idle_gaps", []),
            "longest_gaps": gaps, "spans": program.span_seconds(pt),
            "run": out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--tracer", default="on",
                    help="comma-separated on/off, one run each")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    modes = args.tracer.split(",")
    if not set(modes) <= {"on", "off"}:
        ap.error("--tracer takes on and off")
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run as bench_run
    from bench.harness import spec
    from repro.launch.cache import enable_compile_cache
    try:
        cell = spec.cell(args.workload)
        bench_run.devices(cell.chips, True)
    except (spec.SpecError, bench_run.NoChip) as e:
        log(str(e))
        return 3 if isinstance(e, bench_run.NoChip) else 2
    log(f"compile cache: {enable_compile_cache()}")
    for mode in modes:
        res = run(cell, args.seed, args.seconds, mode == "on")
        line = json.dumps(res)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
