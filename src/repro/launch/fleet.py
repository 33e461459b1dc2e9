"""Fleet serving CLI — a thin shim over ``Workspace.fleet``.

Boots a pool of replay replicas (live-jit when no registry is given,
warm registry boot with ``--from-registry``), generates deterministic
open-loop traffic, serves it, and prints per-tenant latency quantiles
plus the pool/balancer accounting:

    python -m repro.launch.fleet --tenants qwen2.5-3b,xlstm-350m \
        --replicas 3 --policy least_loaded --rate 12 --horizon 2
    python -m repro.launch.fleet --from-registry /tmp/reg --key k \
        --net wifi --record-on-miss --regions 2 --policy cache_affinity
"""
from __future__ import annotations

import argparse
import json
import time

from repro.api import Workspace
from repro.core import PROFILES
from repro.fleet import POLICIES, OpenLoopTraffic, TenantMix
from repro.launch.cache import enable_compile_cache

# registry prefill recordings pin the prompt shape; live fleets may vary
REC_SEQ = 16


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", default="qwen2.5-3b",
                    help="comma-separated archs, one stream per tenant")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--policy", default="round_robin", choices=POLICIES)
    ap.add_argument("--rate", type=float, default=10.0,
                    help="per-tenant Poisson arrival rate (requests/s)")
    ap.add_argument("--horizon", type=float, default=2.0,
                    help="virtual seconds of open-loop traffic")
    ap.add_argument("--burst-x", type=float, default=4.0)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--block-k", type=int, default=4)
    ap.add_argument("--tick", type=float, default=0.02)
    ap.add_argument("--regions", type=int, default=1)
    ap.add_argument("--queue-limit", type=int, default=None)
    ap.add_argument("--autoscale", action="store_true")
    ap.add_argument("--from-registry", default="",
                    help="registry root; replicas boot warm from it")
    ap.add_argument("--record-on-miss", action="store_true")
    ap.add_argument("--net", default="wifi",
                    choices=["none"] + sorted(PROFILES))
    ap.add_argument("--key", default="cody-demo-key")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    registry = args.from_registry or None
    ws = Workspace(registry=registry,
                   key=args.key.encode() if registry else b"",
                   net=None if args.net == "none" else args.net)
    archs = [a.strip() for a in args.tenants.split(",") if a.strip()]
    wls = [ws.workload(a, cache_len=args.cache_len, block_k=args.block_k,
                       batch=args.slots, seq=REC_SEQ) for a in archs]
    pool, _ = ws.fleet(wls, replicas=args.replicas, policy=args.policy,
                       tick_s=args.tick, regions=args.regions,
                       record_on_miss=args.record_on_miss,
                       queue_limit=args.queue_limit,
                       autoscale=args.autoscale, seed=args.seed)
    for r in pool.replicas:
        print(f"replica {r.name}: region r{r.region}, "
              f"boot {r.boot_virtual_s:.3f}s virtual")

    mixes = [TenantMix(wl.cfg.name, args.rate,
                       prompt_len=REC_SEQ if registry else (4, 12),
                       max_new=(4, args.max_new),
                       vocab=min(wl.cfg.vocab_size, 256)) for wl in wls]
    traffic = OpenLoopTraffic(mixes, seed=args.seed, burst_every_s=1.0,
                              burst_len_s=0.25, burst_x=args.burst_x)
    arrivals = traffic.generate(args.horizon)
    print(f"open-loop traffic: {len(arrivals)} arrivals over "
          f"{args.horizon}s virtual ({args.policy})")
    t0 = time.time()
    outputs = pool.run(arrivals)
    dt = time.time() - t0
    toks = sum(len(v) for v in outputs.values())
    print(f"served {len(outputs)}/{len(arrivals)} requests, {toks} tokens "
          f"in {dt:.2f}s wall / {pool.clock:.2f}s virtual")
    for wl in wls:
        q = ws.metrics.quantiles("fleet_request_latency_s",
                                 pool=pool.name, tenant=wl.cfg.name)
        print(f"  [{wl.cfg.name}] latency: {q}")
    print("pool:", json.dumps(pool.stats(), indent=2))
    return outputs, pool


if __name__ == "__main__":
    enable_compile_cache()
    main()
