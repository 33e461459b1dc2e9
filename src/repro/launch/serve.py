"""Serving driver CLI — a thin shim over ``repro.api``.

Continuous batching with fused-block decode, speculative continuation,
and (optionally) execution purely from signed recordings — the paper's
in-TEE replay mode.  Recordings come from a flat directory
(``--from-recordings``) or from the content-addressed registry
(``--from-registry``), the latter with chunked/resumable fetch over an
emulated network and collaborative record-on-miss:

    python -m repro.launch.serve --arch qwen2.5-3b --smoke --requests 8
    python -m repro.launch.serve --streams qwen2.5-3b,xlstm-350m --requests 8
    python -m repro.launch.serve --from-recordings /tmp/recordings --key k
    python -m repro.launch.serve --from-registry /tmp/recordings/registry \
        --net wifi --record-on-miss --key k

This module is CLI-only: channel selection, registry boot, record-on-miss
and multi-tenant wiring all live in ``repro.api``; ``build_channel`` /
``build_engine`` / ``build_scheduler`` / ``stream_kwargs`` are kept as
thin compatibility wrappers over ``Workspace``/``Workload``.  One
deliberate tightening: passing BOTH ``registry_dir`` and
``recordings_dir`` (previously registry silently won) and a registry
without a signing key (previously failed later, at client creation) now
raise ``ValueError`` up front.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.api import Workspace, stream_kwargs
from repro.configs import get_config, smoke_shrink
from repro.core import PROFILES, NetworkEmulator
from repro.launch.cache import enable_compile_cache
from repro.models import model as M
from repro.serving.engine import Engine

__all__ = ["build_channel", "build_engine", "build_scheduler",
           "stream_kwargs", "main"]

# registry prefill recordings are fetched at this prompt length; the
# engine adapts admission via channel.fixed_prompt_len
REC_SEQ = 16


def _workspace_workload(cfg, *, cache_len, block_k, eos_id, n_slots,
                        registry_dir, key, netem):
    ws = Workspace(registry=registry_dir or None, key=key, net=netem)
    wl = ws.workload(cfg, cache_len=cache_len, block_k=block_k,
                     batch=n_slots, prefill_batch=1, seq=REC_SEQ,
                     eos_id=eos_id)
    return ws, wl


def build_channel(cfg, *, cache_len: int, block_k: int, eos_id: int = 2,
                  n_slots: int = 4, recordings_dir: str = "",
                  registry_dir: str = "", record_on_miss: bool = False,
                  key: bytes = b"", netem=None, bill_dispatches: bool = False):
    """Build the ExecutionChannel for one workload (live-jit / flat
    signed-replay / verified registry replay).  Returns
    ``(channel, registry_client_or_None)``."""
    ws, wl = _workspace_workload(cfg, cache_len=cache_len, block_k=block_k,
                                 eos_id=eos_id, n_slots=n_slots,
                                 registry_dir=registry_dir, key=key,
                                 netem=netem)
    channel = wl.channel(recordings_dir=recordings_dir,
                         record_on_miss=record_on_miss,
                         bill_dispatches=bill_dispatches)
    return channel, ws.registry_client


def build_engine(cfg, *, n_slots: int, cache_len: int, block_k: int,
                 eos_id: int, params=None, recordings_dir: str = "",
                 registry_dir: str = "", record_on_miss: bool = False,
                 key: bytes = b"", netem=None, speculate=True,
                 pipeline_depth: int = 4) -> Engine:
    """Single-workload path: one stream behind the classic Engine facade."""
    _ws, wl = _workspace_workload(cfg, cache_len=cache_len, block_k=block_k,
                                  eos_id=eos_id, n_slots=n_slots,
                                  registry_dir=registry_dir, key=key,
                                  netem=netem)
    return wl.engine(params=params, recordings_dir=recordings_dir,
                     record_on_miss=record_on_miss, speculate=speculate,
                     pipeline_depth=pipeline_depth)


def build_scheduler(archs, *, n_slots: int, cache_len: int, block_k: int,
                    eos_id: int = 2, netem=None, speculate: bool = True,
                    pipeline_depth: int = 4, smoke: bool = True,
                    max_live_slots=None, stall_limit=None, seed: int = 0):
    """Multi-workload path: one Scheduler, one stream per arch, each with
    its own live-jit channel, params, slots, and caches.  Returns
    ``(scheduler, {name: cfg})``."""
    ws = Workspace(net=netem)
    sched, wls = ws.scheduler(archs, n_slots=n_slots, cache_len=cache_len,
                              block_k=block_k, eos_id=eos_id, smoke=smoke,
                              speculate=speculate,
                              pipeline_depth=pipeline_depth,
                              max_live_slots=max_live_slots,
                              stall_limit=stall_limit, seed=seed)
    return sched, {name: wl.cfg for name, wl in wls.items()}


def _serve_multi(args, netem):
    archs = [a.strip() for a in args.streams.split(",") if a.strip()]
    sched, cfgs = build_scheduler(
        archs, n_slots=args.slots, cache_len=args.cache_len,
        block_k=args.block_k, netem=netem,
        speculate=not args.no_speculate,
        pipeline_depth=args.pipeline_depth, smoke=args.smoke)
    rng = np.random.default_rng(0)
    for name, cfg in cfgs.items():
        for _ in range(args.requests):
            plen = int(rng.integers(4, 16))
            sched.submit(name, list(rng.integers(3, cfg.vocab_size, plen)),
                         args.max_new)
    t0 = time.time()
    outs = sched.run()
    dt = time.time() - t0
    toks = sum(len(v) for per in outs.values() for v in per.values())
    print(f"served {len(cfgs)} streams x {args.requests} requests, "
          f"{toks} tokens in {dt:.2f}s ({toks/dt:.0f} tok/s)")
    for name, ex in sched.streams.items():
        print(f"  [{name}] stats: {dict(ex.stats)}")
    print("frontier:", dict(sched.frontier.stats))
    print("speculator:", dict(sched.spec.stats))
    return outs, sched


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--streams", default="",
                    help="comma-separated archs to serve CONCURRENTLY "
                         "through one Scheduler (multi-tenant mode)")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke-shrunk widths (--no-smoke: the "
                         "published widths)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--block-k", type=int, default=8)
    ap.add_argument("--no-speculate", action="store_true")
    ap.add_argument("--pipeline-depth", type=int, default=4)
    ap.add_argument("--from-recordings", default="")
    ap.add_argument("--from-registry", default="",
                    help="registry root to fetch recordings from")
    ap.add_argument("--record-on-miss", action="store_true",
                    help="on registry miss, record through the service's "
                         "single-flight lease")
    ap.add_argument("--net", default="none",
                    choices=["none"] + sorted(PROFILES),
                    help="emulated network profile for registry fetches")
    ap.add_argument("--key", default="cody-demo-key")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)

    netem = None
    if args.net != "none":
        netem = NetworkEmulator(PROFILES[args.net])

    if args.streams:
        return _serve_multi(args, netem)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_shrink(cfg)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = build_engine(cfg, n_slots=args.slots, cache_len=args.cache_len,
                       block_k=args.block_k, eos_id=2, params=params,
                       recordings_dir=args.from_recordings,
                       registry_dir=args.from_registry,
                       record_on_miss=args.record_on_miss,
                       key=args.key.encode(), netem=netem,
                       speculate=not args.no_speculate,
                       pipeline_depth=args.pipeline_depth)
    # registry boot traffic, snapshotted BEFORE the engine starts billing
    # its own commit round trips into the same emulated link
    registry_net = dict(netem.snapshot()) if netem is not None else None
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        plen = eng.fixed_prompt_len or int(rng.integers(4, 16))
        eng.submit(list(rng.integers(3, cfg.vocab_size, plen)), args.max_new)
    t0 = time.time()
    outs = eng.run()
    dt = time.time() - t0
    toks = sum(len(v) for v in outs.values())
    print(f"served {len(outs)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.0f} tok/s)")
    print("engine stats:", dict(eng.stats))
    print("speculator:", dict(eng.spec.stats))
    if eng.registry_client is not None:
        print("registry client:", dict(eng.registry_client.stats))
        if registry_net is not None:
            print("registry net (boot):", registry_net)
            print("total net (boot + serve):", netem.snapshot())
    return outs, eng


if __name__ == "__main__":
    enable_compile_cache()
    main()
