"""Kernel micro-bench: numerics of each Pallas kernel vs its oracle, plus
the wall time of the jnp reference path.  ``repro.kernels.ops`` picks the
mode from the backend: on the CPU the kernels run in interpret mode (so
only their numerics mean anything here), on a TPU they are compiled."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _time(fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def main(quick: bool = False):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    rows = []

    B, S, H, Hkv, hd = 1, 512, 8, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, hd)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd)).astype(jnp.bfloat16)
    jref = jax.jit(lambda q, k, v: ref.flash_attention(q, k, v))
    got = ops.flash_attention(q, k, v, blk_q=128, blk_k=128)
    err = float(np.max(np.abs(np.asarray(got, np.float32) -
                              np.asarray(jref(q, k, v), np.float32))))
    rows.append({"name": "flash_attention_512", "us_per_call":
                 round(_time(jref, q, k, v), 1), "derived": f"maxerr={err:.4f}"})

    qd = jax.random.normal(ks[0], (4, H, hd)).astype(jnp.bfloat16)
    kc = jax.random.normal(ks[1], (4, 1024, Hkv, hd)).astype(jnp.bfloat16)
    vc = jax.random.normal(ks[2], (4, 1024, Hkv, hd)).astype(jnp.bfloat16)
    lens = jnp.array([1024, 700, 64, 1], jnp.int32)
    jref2 = jax.jit(lambda q, k, v, l: ref.decode_attention(q, k, v, l))
    err = float(np.max(np.abs(
        np.asarray(ops.decode_attention(qd, kc, vc, lens), np.float32) -
        np.asarray(jref2(qd, kc, vc, lens), np.float32))))
    rows.append({"name": "decode_attention_1k", "us_per_call":
                 round(_time(jref2, qd, kc, vc, lens), 1),
                 "derived": f"maxerr={err:.4f}"})

    x = jax.random.normal(ks[0], (2048, 1024)).astype(jnp.bfloat16)
    sc = jnp.ones((1024,))
    jref3 = jax.jit(lambda x, s: ref.rmsnorm(x, s))
    err = float(np.max(np.abs(np.asarray(ops.rmsnorm(x, sc), np.float32) -
                              np.asarray(jref3(x, sc), np.float32))))
    rows.append({"name": "rmsnorm_2048x1024", "us_per_call":
                 round(_time(jref3, x, sc), 1), "derived": f"maxerr={err:.4f}"})

    xe = jax.random.normal(ks[1], (8, 128, 256)).astype(jnp.bfloat16) * 0.06
    we = jax.random.normal(ks[2], (8, 256, 512)).astype(jnp.bfloat16)
    jref4 = jax.jit(ref.moe_gmm)
    err = float(np.max(np.abs(np.asarray(ops.moe_gmm(xe, we), np.float32) -
                              np.asarray(jref4(xe, we), np.float32))))
    rows.append({"name": "moe_gmm_8x128x256x512", "us_per_call":
                 round(_time(jref4, xe, we), 1), "derived": f"maxerr={err:.4f}"})
    return rows


if __name__ == "__main__":
    for r in main():
        print(r)
