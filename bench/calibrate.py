"""The readings a cell's correctness limits are set from, and its control.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s> [--out calibrate.jsonl]

One process boots the cell once.  For each seed it makes the weights,
serves a window of the cell's own traffic and load, and draws the same
sample as ``bench/run.py``.  Then, with the engine and weights freed, it
reads the compared numbers (``kv_err``, ``logit_err``, ``off_share``, and
the widest token gap beside them) on the same served prompts and
positions for three sides:

* ``served``: what ``bench/run.py`` reads of the served path;
* ``control``: the reference put in the program's place with its weight
  matrices in float8 (e4m3), the precision below the configuration's
  bfloat16: its keys and values, its logits at the last prompt position,
  and the gap of the token it puts first at each served position;
* ``int8``: the same of the program's own int8 weight path
  (``repro.serving.quant``), a second, milder lower precision.

With ``--fault <name>`` (``bench/harness/faults.py``) the served path
is broken underneath before the cell boots, and ``served`` reads the
fault: its ``served.correct`` has to come out false.

Each side's numbers also go through ``check.verdict`` and
``check.correct`` with the cell's own limits (``<side>.correct``): sound
runs have to come out correct and the control not.  Each limit lies
between the largest ``served`` reading over a dozen seeds and the
smallest ``control`` reading.  The benchmark's own runs do not run this;
``bench/tests`` keeps it at a size a test run can hold.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


SIDES = ("served", "control", "int8")


def readings(sess, cell, seed: int, seconds: float) -> dict:
    """Serve one window on ``seed``; the readings of every side."""
    from bench.harness import check
    from bench.harness.traffic import Traffic
    sess.load(seed)
    served = sess.serve(Traffic(cell.traffic, cell.config["vocab_size"],
                                seed), seconds)
    picks = check.draw(served, sess.channel.last_ids, seed,
                       cell.shape["check"]["tokens"],
                       cell.shape["check"]["requests"])
    samples = check.collect(sess, served, picks, sess.ref.BLOCK)
    sess.release()
    int8 = sess.adapter.int8_control(sess.cfg, sess.ref, cell.config, seed,
                                     samples)
    res = check.compare(sess.ref, cell.config, seed, samples, fp8=True,
                        others={"int8": int8})
    row = {"seed": seed, "requests": len(samples),
           "unserved": len(served.unserved)}
    for side in SIDES:
        row.update({f"{side}.{k}": v for k, v in res[side].items()})
        row[f"{side}.correct"] = check.correct(check.verdict(
            served if side == "served" else None, res[side],
            cell.shape["limits"]))
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        sys.path.insert(0, p)
    import jax
    from bench.harness import serve, spec
    from bench.harness.faults import FAULTS
    if args.fault:
        FAULTS[args.fault](setattr)
    from repro.launch.cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("no TPU found", file=sys.stderr)
        return 3
    enable_compile_cache()
    cell = spec.cell(args.workload)
    adapter, ref = spec.family(cell.config["model_type"])
    sess = serve.Session(cell, adapter, ref)
    sess.boot()
    out = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            row = dict(readings(sess, cell, seed, args.seconds),
                       cell=cell.name, fault=args.fault,
                       secs=time.perf_counter() - t0)
            print(json.dumps(row), flush=True)
            if out:
                out.write(json.dumps(row) + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
