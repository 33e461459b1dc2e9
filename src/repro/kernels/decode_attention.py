"""Single-token GQA decode attention vs a long KV cache (Pallas TPU).

Grid (B, Hkv, nW): W (cache) blocks iterate innermost, carrying online
softmax state in VMEM scratch.  The per-row lengths are scalar-prefetched
into SMEM, so blocks past a row's length skip their FLOPs.  The q tile is
[G, hd] (all G query heads of one KV group), so the MXU contraction is
[G,hd]x[hd,blk] — for G>=8 this keeps the MXU busy even at batch 1, which
is the long-context decode cell's regime.  VMEM: one [blk_w, hd] K tile +
V tile + [G, blk_w] scores.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale, blk_w, n_w):
    b = pl.program_id(0)
    iw = pl.program_id(2)

    @pl.when(iw == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    length = len_ref[b]
    base = iw * blk_w

    @pl.when(base < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # [G, hd]
        k = k_ref[0, 0].astype(jnp.float32)          # [blk_w, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        slot = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(slot < length, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, -1)
        acc_sc[...] = acc_sc[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(iw == n_w - 1)
    def _fini():
        denom = jnp.maximum(l_sc[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_sc[...] / denom).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, scale=None,
                     blk_w=256, interpret: bool):
    """q [B,H,hd]; caches [B,W,Hkv,hd]; lengths [B] -> [B,H,hd]."""
    B, H, hd = q.shape
    W, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    scale = scale if scale is not None else hd ** -0.5
    blk_w = min(blk_w, W)
    assert W % blk_w == 0
    n_w = W // blk_w
    qg = q.reshape(B, Hkv, G, hd)
    kt = k_cache.transpose(0, 2, 1, 3)               # [B,Hkv,W,hd]
    vt = v_cache.transpose(0, 2, 1, 3)

    kernel = functools.partial(_kernel, scale=scale, blk_w=blk_w, n_w=n_w)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, n_w),
            in_specs=[
                pl.BlockSpec((1, 1, G, hd),
                             lambda b, h, iw, lens: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, blk_w, hd),
                             lambda b, h, iw, lens: (b, h, iw, 0)),
                pl.BlockSpec((1, 1, blk_w, hd),
                             lambda b, h, iw, lens: (b, h, iw, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd),
                                   lambda b, h, iw, lens: (b, h, 0, 0)),
            scratch_shapes=[pltpu.VMEM((G,), jnp.float32),
                            pltpu.VMEM((G,), jnp.float32),
                            pltpu.VMEM((G, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, kt, vt)
    return out.reshape(B, H, hd)
