"""Recording-campaign CLI — a thin shim over ``Workspace.campaign``.

Fans a key's shape variants out across a device pool and publishes each
finished variant into the registry through the multi-variant lease:

    python -m repro.launch.fanout --arch qwen2.5-3b --devices 4 \
        --seqs 8,16,32,64 --registry /tmp/reg --key secret --net wifi
    python -m repro.launch.fanout --devices 4 --net wifi,cellular \
        --no-share-history     # cold-per-session baseline

Prints the per-device assignment table and the campaign accounting:
makespan vs the sum of per-record times, speculation hit rates per
device (shared history warms later devices), skips for already-published
variants.
"""
from __future__ import annotations

import argparse
import json
import os

from repro.api import Workspace
from repro.core import PROFILES
from repro.launch.cache import enable_compile_cache


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke-shrunk widths (--no-smoke: the "
                         "published widths)")
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--net", default="wifi",
                    help="comma list of link profiles, round-robin over "
                         f"devices ({'|'.join(sorted(PROFILES))})")
    ap.add_argument("--seqs", default="8,16,32,64",
                    help="prefill seq buckets to record (decode rides "
                         "along once)")
    ap.add_argument("--kinds", default="prefill,decode")
    ap.add_argument("--registry", default=None,
                    help="registry root (default: in-memory, print-only)")
    ap.add_argument("--key", default="cody-demo-key")
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--block-k", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prefill-batch", type=int, default=1)
    ap.add_argument("--jobs", type=int, default=None,
                    help="pin per-session job count (determinism across "
                         "recompiles)")
    ap.add_argument("--passes", default="all")
    ap.add_argument("--hw-class", default="edge-gpu")
    ap.add_argument("--no-share-history", action="store_true",
                    help="cold speculator per session (the serial "
                         "baseline's behavior)")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)

    registry = args.registry if args.registry else ":memory:"
    if args.registry:
        os.makedirs(args.registry, exist_ok=True)
    nets = [n.strip() for n in args.net.split(",") if n.strip()]
    ws = Workspace(registry=registry, key=args.key.encode(), net=nets[0],
                   record_passes=args.passes)
    wl = ws.workload(args.arch, smoke=args.smoke, cache_len=args.cache_len,
                     block_k=args.block_k, batch=args.batch,
                     prefill_batch=args.prefill_batch,
                     seq=int(args.seqs.split(",")[0]))
    seqs = [int(s) for s in args.seqs.split(",") if s.strip()]
    kinds = tuple(k.strip() for k in args.kinds.split(",") if k.strip())
    items = wl.variants(seqs=seqs, kinds=kinds)
    campaign = ws.campaign(items, devices=args.devices, nets=nets,
                           hw_class=args.hw_class,
                           share_history=not args.no_share_history,
                           jobs=args.jobs, name=f"fanout-{args.arch}")
    print(f"campaign: {len(items)} variants over {args.devices} devices "
          f"({'+'.join(nets)}), shared history="
          f"{not args.no_share_history}")
    campaign.run()
    s = campaign.stats()
    for d in s["per_device"]:
        spec = d["spec"]
        hr = (spec["hit"] / spec["predict"]) if spec["predict"] else 0.0
        print(f"  {d['name']}[{d['net']}]: {d['recorded']} variants, "
              f"{d['busy_virtual_s']:.2f}s busy, "
              f"{d['blocking_round_trips']} blocking RTs, "
              f"spec hit {hr:.0%}")
    print(f"makespan {s['virtual_time_s']:.2f}s virtual vs "
          f"{s['sum_record_virtual_s']:.2f}s summed record time "
          f"({s['recorded']} recorded, "
          f"{s['skipped_published']} already published, "
          f"{s['publishes']} published)")
    print("campaign:", json.dumps(s, indent=2))
    return campaign


if __name__ == "__main__":
    enable_compile_cache()
    main()
