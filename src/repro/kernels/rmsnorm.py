"""Fused RMSNorm (Pallas TPU): one pass, fp32 accumulation in-register.

Grid over row blocks; each block loads [blk, D] once from HBM, computes
mean-square + rsqrt + scale fused, writes once — 2x fewer HBM touches than
the unfused (square->mean->rsqrt->mul) chain when XLA fails to fuse across
the reduce.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, s_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) *
                  s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x, scale, *, eps: float = 1e-5, blk: int = 256,
            interpret: bool):
    """x [..., D]; scale [D]."""
    orig_shape = x.shape
    D = x.shape[-1]
    xf = x.reshape(-1, D)
    R = xf.shape[0]
    blk = min(blk, R)
    pad = (-R) % blk
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(xf.shape[0] // blk,),
        in_specs=[pl.BlockSpec((blk, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((blk, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=interpret,
    )(xf, scale)
    return out[:R].reshape(orig_shape)
