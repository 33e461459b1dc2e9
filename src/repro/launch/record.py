"""The CODY "cloud dryrun service" CLI — a thin shim over ``repro.api``.

    python -m repro.launch.record --arch qwen2.5-3b --smoke \
        --kinds prefill,decode --out /tmp/recordings --key secret \
        --net wifi --passes all

Each record runs as a distributed ``RecordingSession`` (device proxy +
cloud dryrun over the ``--net`` emulated link) with the paper's record
optimizations selected by ``--passes``, and prints the session report:
virtual record time, blocking/async round trips, wire bytes, per-pass
accounting.  Recordings are identified by ``registry.key_for`` — the
same key the serve CLI fetches by — and written both as a flat
``.codyrec`` file (legacy/offline path) and into the content-addressed
registry at ``--registry`` (delta-published).

This module is CLI-only: all lifecycle logic lives in ``repro.api``
(``Workspace``/``Workload``); ``build_step`` / ``static_meta_for`` /
``recording_name`` / ``format_session_report`` are re-exported here for
backward compatibility.
"""
from __future__ import annotations

import argparse
import os

from repro.api import (Workspace, build_step, format_session_report,
                       recording_name, static_meta_for)
from repro.core import PROFILES
from repro.launch.cache import enable_compile_cache

__all__ = ["build_step", "static_meta_for", "recording_name",
           "format_session_report", "main"]


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke-shrunk widths (--no-smoke: the "
                         "published widths)")
    ap.add_argument("--kinds", default="prefill,decode")
    ap.add_argument("--out", default="/tmp/recordings")
    ap.add_argument("--registry", default=None,
                    help="registry root (default: <out>/registry)")
    ap.add_argument("--no-registry", action="store_true",
                    help="skip registry publishing (flat files only)")
    ap.add_argument("--key", default="cody-demo-key")
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--block-k", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4,
                    help="decode batch = number of serving slots (match "
                         "serve --slots)")
    ap.add_argument("--prefill-batch", type=int, default=1,
                    help="prefill batch (default 1: the engine admits "
                         "prompts per request, so serve fetches batch-1 "
                         "prefill recordings)")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--net", default="local", choices=sorted(PROFILES),
                    help="emulated device<->cloud link the recording "
                         "session runs over")
    ap.add_argument("--passes", default="all",
                    help="comma list of record-session optimization passes "
                         "(deferral,speculation,metasync) | all | none")
    ap.add_argument("--devices", type=int, default=1,
                    help="> 1 fans the kinds out across a device pool "
                         "(campaign API) instead of recording serially")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)

    registry = None
    if not args.no_registry:
        registry = args.registry or os.path.join(args.out, "registry")
    ws = Workspace(registry=registry, key=args.key.encode(), net=args.net,
                   record_passes=args.passes)
    wl = ws.workload(args.arch, smoke=args.smoke, cache_len=args.cache_len,
                     block_k=args.block_k, batch=args.batch,
                     prefill_batch=args.prefill_batch, seq=args.seq)
    os.makedirs(args.out, exist_ok=True)
    kinds = [k for k in args.kinds.split(",") if k.strip()]
    if args.devices > 1:
        # fan the kinds out across a device pool; each finished variant
        # publishes through the campaign's multi-variant lease
        campaign = ws.campaign([(wl, k) for k in kinds],
                               devices=args.devices,
                               name=f"record-{args.arch}")
        recs = campaign.run()
        for kind in kinds:
            rec = recs.get(wl.key(kind))
            if rec is None:
                print(f"skipped {kind}: already published / leased")
                continue
            path = os.path.join(args.out, recording_name(args.arch, kind))
            rec.save(path, ws.key)
            print(f"recorded {kind}: {path} "
                  f"({len(rec.payload)/1e3:.1f} kB executable)")
            print("  " + format_session_report(
                rec.manifest["record_session"]))
        s = campaign.stats()
        print(f"campaign[{s['devices']} devices]: "
              f"{s['virtual_time_s']:.2f}s virtual makespan vs "
              f"{s['sum_record_virtual_s']:.2f}s summed, "
              f"{s['publishes']} published")
        return
    for kind in kinds:
        # one two-party session per recording: fresh device proxy, fresh
        # speculation history, per-recording report
        rec = wl.record(kind)
        path = os.path.join(args.out, recording_name(args.arch, kind))
        rec.save(path, ws.key)
        line = (f"recorded {kind}: {path} "
                f"({len(rec.payload)/1e3:.1f} kB executable, "
                f"{rec.manifest['record_wall_s']:.1f}s record time)")
        if registry is not None:
            pub = wl.publish(rec)
            line += (f"; published {pub['key']} v{pub['version']} "
                     f"({pub['wire_bytes']/1e3:.1f} kB wire, "
                     f"{pub['chunks_new']} new / "
                     f"{pub['chunks_reused']} reused chunks)")
        print(line)
        print("  " + format_session_report(rec.manifest["record_session"]))


if __name__ == "__main__":
    enable_compile_cache()
    main()
