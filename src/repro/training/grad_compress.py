"""Int8 error-feedback gradient compression (distributed-optimization trick).

Two pieces:

1. ``make_ef_int8_transform`` — a ``grad_transform`` hook for train_step:
   grads are quantized to int8 (per-leaf max scaling) with the residual
   carried in an error-feedback buffer (Karimireddy et al. style), so the
   *update math* matches what a compressed-collective deployment computes.

2. ``compressed_psum`` — a shard_map collective that actually moves int8 on
   the wire for the DP all-reduce: quantize -> all_to_all (scatter chunks)
   -> local fp32 sum -> requantize -> all_gather.  Wire bytes per device:
   2 x S x (n-1)/n x 1B  vs  2 x S x (n-1)/n x 4B for fp32 ring AR (4x
   reduction; 2x vs bf16).  Benchmarked in benchmarks/grad_compress.py via
   the HLO analyzer.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _quant(x, axis=None):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant(q, scale):
    return q.astype(jnp.float32) * scale


def make_ef_int8_transform():
    """grad_transform(grads, state) -> (decompressed_grads, state') with an
    error-feedback buffer stored in state['ef']."""

    def transform(grads, state):
        ef = state.get("ef")
        if ef is None:
            ef = jax.tree.map(lambda g: jnp.zeros_like(g, jnp.float32), grads)

        def one(g, e):
            v = g.astype(jnp.float32) + e
            q, s = _quant(v)
            d = _dequant(q, s)
            return d.astype(g.dtype), v - d

        flat_g, td = jax.tree.flatten(grads)
        flat_e = jax.tree.leaves(ef)
        out = [one(g, e) for g, e in zip(flat_g, flat_e)]
        new_g = jax.tree.unflatten(td, [o[0] for o in out])
        new_e = jax.tree.unflatten(td, [o[1] for o in out])
        state = dict(state)
        state["ef"] = new_e
        return new_g, state

    return transform


def compressed_psum(x, mesh, axis: str = "data"):
    """int8-on-the-wire all-reduce over `axis` (reduce-scatter + all-gather
    in int8 with fp32 local accumulation)."""
    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]

    def inner(xs):
        # xs: local shard [*dims]; reduce over `axis` peers
        flat = xs.reshape(-1)
        pad = (-flat.shape[0]) % n
        flat = jnp.pad(flat, (0, pad))
        chunks = flat.reshape(n, -1)
        q, s = _quant(chunks)
        # scatter: chunk i goes to rank i (int8 wire)
        qt = jax.lax.all_to_all(q, axis, 0, 0)               # [n, chunk]
        st = jax.lax.all_gather(s, axis)                     # scales
        partial_sum = jnp.sum(_dequant(qt, st[:, None]), axis=0)
        q2, s2 = _quant(partial_sum)
        gathered = jax.lax.all_gather(q2, axis)              # [n, chunk] int8
        s2g = jax.lax.all_gather(s2, axis)
        full = _dequant(gathered, s2g[:, None]).reshape(-1)
        full = full[:xs.size] if pad == 0 else full[:-pad] if pad else full
        return full[:xs.size].reshape(xs.shape)

    spec = P(*[None] * x.ndim)
    return jax.shard_map(inner, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)(x)
