"""End-to-end training driver (runs on this host's devices).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Wires together: data pipeline -> recorded train step (CODY recorder: the
step is lowered+compiled once, AOT) -> AdamW -> async checkpoints ->
elastic restore (resume on a different device count just works).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke_shrink
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.runtime.checkpoint import CheckpointStore
from repro.runtime.elastic import reshard_state
from repro.sharding import rules_for
from repro.training import steps as ST
from repro.training.grad_compress import make_ef_int8_transform
from repro.training.optimizer import AdamWConfig, init_opt_state


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke-shrunk widths (--no-smoke: the "
                         "published widths)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_shrink(cfg)
    mesh = make_host_mesh(model=1)
    rules = rules_for("train", mesh.axis_names)

    opt = AdamWConfig(lr=args.lr, warmup_steps=10, decay_steps=args.steps)
    gt = make_ef_int8_transform() if args.grad_compress else None
    train_step = ST.make_train_step(cfg, rules, opt, remat=args.remat,
                                    grad_transform=gt)

    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    state = init_opt_state(params)
    store = CheckpointStore(args.ckpt_dir) if args.ckpt_dir else None
    data = SyntheticLM(cfg.vocab_size, args.batch, args.seq)
    start_step = 0
    if store and args.resume and store.latest_step() is not None:
        state_np, manifest = store.restore(state)
        state = reshard_state(state_np, ST.train_state_axes(cfg), mesh)
        data.restore(manifest["extra"])
        start_step = manifest["step"]
        print(f"resumed from step {start_step} on {len(jax.devices())} devices")

    with jax.set_mesh(mesh):
        jitted = jax.jit(train_step, donate_argnums=(0,))
        loader = Prefetcher(data)
        t0 = time.time()
        for step in range(start_step, args.steps):
            batch = {k: jnp.asarray(v) for k, v in loader.next_batch().items()}
            state, metrics = jitted(state, batch)
            if (step + 1) % args.log_every == 0:
                m = {k: float(v) for k, v in metrics.items()}
                print(f"step {step+1:5d} loss {m['loss']:.4f} "
                      f"gnorm {m['grad_norm']:.3f} lr {m['lr']:.2e} "
                      f"({(time.time()-t0)/args.log_every*1000:.0f} ms/step)")
                t0 = time.time()
            if store and (step + 1) % args.ckpt_every == 0:
                store.async_save(state, step + 1, extra_meta=data.meta())
        if store:
            store.wait()
            store.save(state, args.steps, extra_meta=data.meta())
        loader.close()
    final = float(metrics["loss"])
    print(f"done: final loss {final:.4f}")
    return final


if __name__ == "__main__":
    enable_compile_cache()
    main()
