"""Run one benchmark cell once on the accelerator this process sees.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic mix
and shapes are files under ``bench/`` found by name.  The run records and
publishes the cell's prefill and decode executables, boots the verified
registry ``ReplayChannel``, makes the weights from the seed, warms up, and
serves the traffic for ``--seconds`` through ``Engine``.  Then, with the
program's state freed, the plain reference checks a seeded sample of the
served requests.  ``--trace 1`` runs the profiler over the window and
reports the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit).
The same numbers are the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def peak_bytes(devs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, peak: dict = None) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax
    import numpy as np

    from bench.harness import check, readings, serve, spec, tracing
    from bench.harness.traffic import Traffic
    from bench.harness.work import Sizes

    devs = devices(cell.chips, require_chip)
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    peak = peak if peak is not None else spec.peaks(dev["kind"])
    conf = cell.config
    adapter, ref = spec.family(conf["model_type"])
    sess = serve.Session(cell, adapter, ref, annotate=trace)
    sess.boot()
    log(f"recorded prefill {sess.record_s['prefill']:.2f} s, decode "
        f"{sess.record_s['decode']:.2f} s; booted the verified "
        f"ReplayChannel in {sess.boot_s:.2f} s")
    sess.load(seed)
    traffic = Traffic(cell.traffic, conf["vocab_size"], seed)
    marks = {}
    log_dir = os.path.join(TRACE_DIR, cell.name)

    def on_start():
        marks["setup_end"] = time.perf_counter()
        sess.channel.recording = trace
        if trace:
            shutil.rmtree(log_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(log_dir, profiler_options=opts)

    def on_end():
        sess.channel.recording = False
        if trace:
            jax.profiler.stop_trace()

    served = sess.serve(traffic, seconds, on_start, on_end)
    setup_s = marks["setup_end"] - T_START
    log(f"setup {setup_s:.2f} s")
    mem = peak_bytes(devs)
    summ = serve.summary(served)
    ch = sess.channel
    blocks = [(np.asarray(p), s) for p, s in ch.blocks]
    prefills, dispatch_s = list(ch.prefills), list(ch.dispatch_s)
    exe_names, boot_s = dict(sess.exe_names), sess.boot_s
    picks = check.draw(served, sess.channel.last_ids, seed,
                       cell.shape["check"]["tokens"],
                       cell.shape["check"]["requests"])
    samples = check.collect(sess, served, picks, ref.BLOCK)
    sess.free()
    del sess, ch

    lat = served.lateness
    log(f"window {seconds} s: {len(served.in_window)} requests due, "
        f"{summ['tokens']} tokens committed, "
        f"{len(served.compiles)} compiles in the window "
        f"{[n for n, _ in served.compiles]}")
    if lat:
        log(f"open-loop generator lateness: median "
            f"{1e3 * float(np.median(lat))} ms, max {1e3 * max(lat)} ms")
    log(f"engine counters in the window: {summ['counters']}")
    log(f"ttft p50 {summ['ttft_p50_ms']} ms, itl p50 {summ['itl_p50_ms']} "
        f"ms; peak bytes in use {mem}")

    red = {}
    if trace:
        t0 = time.perf_counter()
        red = tracing.reduce(tracing.load(log_dir))
        log(f"trace: window {red.get('window_s')} s, busy "
            f"{red.get('busy_s')} s, read in "
            f"{time.perf_counter() - t0:.2f} s; executables "
            f"{red.get('modules')}")

    t0 = time.perf_counter()
    numbers = check.compare(ref, conf, seed, samples)["served"]
    log(f"reference: {len(samples)} requests, {numbers['tokens']} served "
        f"tokens, {numbers['off']} not the reference's first choice, "
        f"widest gap {numbers['widest_gap']} (not compared), "
        f"{time.perf_counter() - t0:.2f} s")
    checks = check.verdict(served, numbers, cell.shape["limits"])

    run = readings.Run(setup_s=setup_s, boot_s=boot_s, summary=summ,
                       trace=red, exe_names=exe_names, sizes=Sizes.of(conf),
                       peak=peak, blocks=blocks, prefills=prefills,
                       dispatch_s=dispatch_s)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.metric_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if trace and blocks:
        log(f"decode blocks in the traced window: {len(blocks)}, least-time "
            f"bound: {readings.decode_least(run)[1]}")
    failed = len(served.unserved) + sum(
        served.requests[r].failed for r in served.in_window)
    dev["memory_peak_bytes"] = mem
    out = {"correct": check.correct(checks),
           "attempted": len(served.in_window), "failed": failed,
           "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = red.get("busy_s")
        dev["window_s"] = red.get("window_s")
        out["breakdown"] = {"device_ops": red.get("device_ops", []),
                            "idle_gaps": red.get("idle_gaps", [])}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log(f"no program under test next to the benchmark ({ROOT}/src)")
        return 2
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.harness import spec
    try:
        cell = spec.cell(args.workload)
        devices(cell.chips, True)       # before the compile cache is set
        from repro.launch.cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except (spec.SpecError, NoChip) as e:
        log(str(e))
        return 3 if isinstance(e, NoChip) else 2
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
