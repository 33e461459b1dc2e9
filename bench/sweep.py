"""Find an open-loop cell's knee: the highest offered rate whose backlog
does not grow over a window.  Run once on the chip when a cell is defined;
the cell's rate is then written into its traffic file as a number.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 2,4,6 [--out sweep.jsonl]

One process boots the cell once and serves one window per base rate
(``rate_rps``; the mix's bursts stay as they are).  For each it prints the
offered mean rate, tokens per second, the tails, and the backlog (requests
waiting for a slot) at the start of every burst period: a rate the system
sustains drains its backlog before each next burst.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def backlog_at(backlog, times):
    """Backlog sampled last before each time in ``times``."""
    out, j, last = [], 0, 0
    for t in times:
        while j < len(backlog) and backlog[j][0] <= t:
            last = backlog[j][1]
            j += 1
        out.append(last)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        sys.path.insert(0, p)
    from bench.harness import serve, spec
    from bench.harness.traffic import Traffic, mean_rate
    from repro.launch.cache import enable_compile_cache
    import jax
    cell = spec.cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("no TPU found", file=sys.stderr)
        return 3
    enable_compile_cache()
    adapter, ref = spec.family(cell.config["model_type"])
    sess = serve.Session(cell, adapter, ref)
    sess.boot()
    sess.load(args.seed)
    every = (cell.traffic.get("burst") or {}).get("every_s") or args.seconds
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_rps=rate)
        served = sess.serve(Traffic(mix, cell.config["vocab_size"],
                                    args.seed), args.seconds)
        summ = serve.summary(served)
        starts = [every * k for k in range(1, int(args.seconds // every) + 1)]
        row = {"rate_rps": rate, "mean_rps": mean_rate(mix),
               "due": len(served.in_window),
               "tokens_per_s": summ["tokens_per_s"],
               "ttft_p50_ms": summ["ttft_p50_ms"],
               "ttft_p95_ms": summ["ttft_p95_ms"],
               "itl_p95_ms": summ["itl_p95_ms"],
               "backlog_before_bursts": backlog_at(served.backlog, starts),
               "backlog_max": max((b for _, b in served.backlog), default=0),
               "unserved": len(served.unserved),
               "counters": summ["counters"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
        sess.load(args.seed)           # empty slots for the next rate
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
