"""Bring-up check: serve qwen2.5-3b at its published widths on a TPU.

The cloud role records the prefill and decode steps, signs them and
publishes them into an in-memory registry.  The device role fetches and
verifies them, boots a ``ReplayChannel`` and serves seeded requests through
the Scheduler / StreamExecutor / CommitFrontier stack.  The same requests
are then served by live jit on the same params, and the token streams must
be identical.  Weights are random, made from a seed.

    python3 chip_smoke.py               # one chip: registry replay vs live jit
    python3 chip_smoke.py --chips 4     # replay on the default 4-device mesh
                                        # vs replay on a one-device mesh

Everything runs in this one process: a chip belongs to one process at a
time.  The last line of output is one JSON object naming the device.  The
script exits non-zero, without that line, when JAX finds no TPU or when any
phase fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import Workspace  # noqa: E402
from repro.core.channel import ExecutionChannel  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

ARCH = "qwen2.5-3b"
SHAPES = dict(cache_len=1024, block_k=8, batch=4, prefill_batch=1, seq=16)
# data-parallel serving over four chips: two decode slots per chip
MESH_SHAPES = dict(SHAPES, batch=8)
N_REQUESTS = 8
MAX_NEW = 32
SEED = 0
KEY = b"chip-smoke-signing-key"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    """A phase of the bring-up check did not hold."""


def check(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


class CompileLog:
    """Backend compiles and persistent-cache hits/misses while active."""

    def __init__(self):
        self.compiles = []            # (function name, seconds)
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event, secs, **kw):
        if event == BACKEND_COMPILE:
            self.compiles.append((kw.get("fun_name", "?"), secs))

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


class DonationProbe(ExecutionChannel):
    """Forwards to a channel and counts decode blocks whose input caches
    were consumed (donated) by the step.  It offers no grouped prefill, so
    the executor prefills one request per dispatch, as it must through a
    recorded prefill."""

    def __init__(self, inner: ExecutionChannel):
        self.inner = inner
        self.kind = inner.kind
        self.donated = 0
        self.kept = 0

    @property
    def fixed_prompt_len(self):
        return self.inner.fixed_prompt_len

    def prefill(self, params, batch):
        return self.inner.prefill(params, batch)

    def decode_block(self, params, tokens, pos, caches):
        leaves = jax.tree.leaves(caches)
        out = self.inner.decode_block(params, tokens, pos, caches)
        if all(x.is_deleted() for x in leaves):
            self.donated += 1
        else:
            self.kept += 1
        return out


def make_prompts(vocab: int, n: int, seq: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(3, vocab, seq).tolist() for _ in range(n)]


def serve(eng, prompts, max_new: int) -> list:
    rids = [eng.submit(p, max_new) for p in prompts]
    outs = eng.run()
    return [outs[r] for r in rids]


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def record_and_publish(wl, log) -> dict:
    """The cloud role: record, sign and publish prefill and decode."""
    out = {}
    for kind in ("prefill", "decode"):
        with CompileLog() as cl:
            t0 = time.perf_counter()
            rec = wl.record(kind)
            secs = time.perf_counter() - t0
        wl.publish(rec)
        compile_s = sum(s for _, s in cl.compiles)
        out[kind] = {"record_s": secs, "compile_s": compile_s,
                     "payload_bytes": len(rec.payload),
                     "cache_hits": cl.cache_hits,
                     "cache_misses": cl.cache_misses}
        log(f"recorded {kind}: {secs:.2f} s, {compile_s:.2f} s of it "
            f"compiling (compile cache {cl.cache_hits} hit / "
            f"{cl.cache_misses} miss), {len(rec.payload)} payload bytes")
    return out


def serve_replay(wl, params, prompts, warmup, log, label="replay"):
    """The device role: boot the verified registry ReplayChannel (preload
    + warm) and serve the warm-up prompts, then the checked ones.  Returns
    (streams of warm-up + checked prompts, engine)."""
    t0 = time.perf_counter()
    probe = DonationProbe(wl.channel())
    log(f"[{label}] booted ReplayChannel in "
        f"{time.perf_counter() - t0:.2f} s, peak bytes {peak_bytes()}")
    eng = wl.engine(params=params, channel=probe)
    check(eng.fixed_prompt_len == len(prompts[0]),
          f"[{label}] recorded prefill seq {eng.fixed_prompt_len} != "
          f"prompt length {len(prompts[0])}")
    # warm-up traffic first: the executor's small eager helpers (cache
    # scatter, token slicing) compile on first use, against fresh caches
    # and again against caches the decode step produced; the served window
    # below must then compile nothing
    warm = serve(eng, warmup, MAX_NEW)
    with CompileLog() as cl:
        t0 = time.perf_counter()
        streams = serve(eng, prompts, MAX_NEW)
        secs = time.perf_counter() - t0
    toks = sum(len(s) for s in streams)
    log(f"[{label}] served {len(prompts)} requests, {toks} tokens in "
        f"{secs:.2f} s; compiles while serving: {len(cl.compiles)}")
    check(not cl.compiles,
          f"[{label}] replay engine compiled while serving: {cl.compiles}")
    stats = wl.replayer_stats()
    check(stats.get("fast_hits", 0) > 0,
          f"[{label}] replayer fast path never hit: {stats}")
    check(probe.donated > 0 and probe.kept == 0,
          f"[{label}] decode caches not donated on every block "
          f"({probe.donated} donated, {probe.kept} kept)")
    log(f"[{label}] engine stats: {dict(eng.stats)}")
    log(f"[{label}] frontier: {dict(eng.frontier.stats)}")
    log(f"[{label}] replayer: {stats}")
    return warm + streams, eng


def replay_vs_live(arch: str = ARCH, *, smoke: bool = False,
                   shapes: dict = SHAPES, n_requests: int = N_REQUESTS,
                   log=print) -> dict:
    """Record -> publish -> verified registry replay, then the same
    requests through live jit on the same params object; the streams must
    be identical.  Returns what was observed."""
    ws = Workspace(registry=":memory:", key=KEY)
    wl = ws.workload(arch, smoke=smoke, **shapes)
    t0 = time.perf_counter()
    params = wl.params(SEED)
    jax.block_until_ready(params)
    log(f"params: {sum(x.size for x in jax.tree.leaves(params))} in "
        f"{time.perf_counter() - t0:.2f} s, peak bytes {peak_bytes()}")
    recorded = record_and_publish(wl, log)
    prompts = make_prompts(wl.cfg.vocab_size, n_requests, shapes["seq"],
                           SEED + 1)
    warmup = make_prompts(wl.cfg.vocab_size, shapes["batch"] + 1,
                          shapes["seq"], SEED + 2)
    replay, eng = serve_replay(wl, params, prompts, warmup, log)
    log(f"peak bytes after replay: {peak_bytes()}")
    # drop the replay engine's caches and executables before live jit
    del eng
    wl.replayers.clear()

    # live jit runs the programs replay runs: the [prefill_batch, seq]
    # prefill step, one request per dispatch.  Its grouped prefill is a
    # different program ([n, seq] with per-row lengths), and on a TPU its
    # bf16 rounding differs from the single-request one, enough to flip
    # greedy tokens a few dozen steps later
    live_wl = Workspace().workload(arch, smoke=smoke, **shapes)
    probe = DonationProbe(live_wl.channel())
    eng = live_wl.engine(params=params, channel=probe)
    t0 = time.perf_counter()
    live = serve(eng, warmup + prompts, MAX_NEW)
    log(f"[live] served {len(live)} requests in "
        f"{time.perf_counter() - t0:.2f} s (compiles included); "
        f"engine stats: {dict(eng.stats)}")
    check(probe.donated > 0 and probe.kept == 0,
          f"[live] decode caches not donated on every block "
          f"({probe.donated} donated, {probe.kept} kept)")
    del eng
    same = sum(a == b for a, b in zip(replay, live))
    log(f"replay vs live: {same}/{len(live)} streams identical")
    for i, (a, b) in enumerate(zip(replay, live)):
        if a != b:
            cut = next((j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                       min(len(a), len(b)))
            log(f"  request {i}: diverges at token {cut}: "
                f"replay {a[cut:cut + 4]} live {b[cut:cut + 4]}")
    check(same == len(live), "replay and live token streams differ")
    return {"recorded": recorded, "streams": replay,
            "tokens": sum(len(s) for s in replay)}


def one_device_mesh():
    return make_mesh((1, 1), ("data", "model"),
                     devices=jax.devices()[:1])


def mesh_vs_one_device(arch: str = ARCH, *, smoke: bool = False,
                       shapes: dict = MESH_SHAPES,
                       n_requests: int = N_REQUESTS,
                       log=print) -> dict:
    """Registry replay on the default mesh over every device (data
    parallel) against registry replay on one device; the streams must be
    equal and the multi-device caches must span every device.  The
    one-device run keeps as many slots as each device holds in the
    data-parallel run, so both run the same per-device programs (a TPU
    rounds an [8, d] matmul differently from a [2, d] one).  Both serve
    the same weights: the one-device params are moved onto the full mesh
    (one copy per device) for the second run."""
    n_dev = len(jax.devices())
    results = {}
    params = None
    for label, mesh, batch in (
            ("one-device", one_device_mesh(), shapes["batch"] // n_dev),
            (f"{n_dev}-device", None, shapes["batch"])):
        ws = Workspace(registry=":memory:", key=KEY)
        wl = ws.workload(arch, smoke=smoke, mesh=mesh,
                         **dict(shapes, batch=batch))
        params = wl.params(SEED) if params is None else \
            jax.device_put(params, wl.param_shardings())
        record_and_publish(wl, log)
        prompts = make_prompts(wl.cfg.vocab_size, n_requests, shapes["seq"],
                               SEED + 1)
        warmup = make_prompts(wl.cfg.vocab_size, shapes["batch"] + 1,
                              shapes["seq"], SEED + 2)
        streams, eng = serve_replay(wl, params, prompts, warmup, log,
                                    label=label)
        spans = {name: len(leaf.sharding.device_set) for name, leaf in (
            ("caches", jax.tree.leaves(eng.caches)[0]),
            ("tokens", eng.stream._last_block_out["tokens"]))}
        log(f"[{label}] devices spanned: {spans}")
        results[label] = (streams, spans)
        del eng, ws, wl
    (one, _), (multi, spans) = results["one-device"], \
        results[f"{n_dev}-device"]
    check(all(n == n_dev for n in spans.values()),
          f"{n_dev}-device run did not span {n_dev} devices: {spans}")
    same = sum(a == b for a, b in zip(one, multi))
    log(f"{n_dev}-device vs one-device: {same}/{len(one)} streams identical")
    check(same == len(one), "multi-device and one-device streams differ")
    return {"streams": multi, "spans": spans}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the multi-device mesh phase")
    args = ap.parse_args(argv)
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: {dev}", flush=True)
    if dev["platform"] != "tpu":
        print("no TPU found; this check runs only on the chip",
              file=sys.stderr)
        return 1
    if dev["count"] != args.chips:
        print(f"--chips {args.chips} but JAX sees {dev['count']} devices",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    cached = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    print(f"compile cache: {cache_dir} "
          f"({'has entries' if cached else 'empty'})", flush=True)
    log = lambda msg: print(msg, flush=True)  # noqa: E731
    with CompileLog() as cl:
        if args.chips == 4:
            mesh_vs_one_device(log=log)
        else:
            replay_vs_live(log=log)
    print(f"compile cache: {cl.cache_hits} hits, {cl.cache_misses} misses; "
          f"{len(cl.compiles)} backend compiles, "
          f"{sum(s for _, s in cl.compiles):.1f} s", flush=True)
    print(f"peak bytes in use: {peak_bytes()}", flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
