"""Flash attention (causal / sliding-window, GQA) as a Pallas TPU kernel.

Grid (B*H, nQ, nK) — the innermost K dimension iterates sequentially on
TPU, carrying the online-softmax state (m, l, acc) in VMEM scratch.  Block
shapes are MXU-aligned (multiples of 128 on the contracting/lane dims);
the q block + one k/v block + accumulator bound the VMEM working set to
~(3*blk*hd + blk_q*blk_k)*4 bytes, independent of sequence length.

GQA: the kernel grid runs over Q heads; the k/v index_map folds the head
down to its KV group (h -> h // G), so no repeated KV is materialized.
SWA: fully-masked K blocks are skipped via ``pl.when`` on the block index
(the compiler still schedules them, but no FLOPs/VMEM traffic happen on
TPU for predicated-off bodies).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale, causal, window, blk_q, blk_k, n_k, q_offset):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_start = iq * blk_q + q_offset
    k_start = ik * blk_k
    # block-level relevance (causal lower-left + SWA band)
    relevant = True
    if causal:
        relevant = jnp.logical_and(
            k_start <= q_start + blk_q - 1, True)
    if window:
        relevant = jnp.logical_and(
            relevant, k_start + blk_k - 1 >= q_start - window + 1)

    @pl.when(relevant)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # [blk_q, hd]
        k = k_ref[0].astype(jnp.float32)            # [blk_k, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [blk_q, blk_k]
        pq = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        pk = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= pq >= pk
        if window:
            mask &= pq - pk < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, -1)
        acc_sc[...] = acc_sc[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(ik == n_k - 1)
    def _fini():
        denom = jnp.maximum(l_sc[...], 1e-30)[:, None]
        o_ref[0] = (acc_sc[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, scale=None,
                    blk_q=128, blk_k=128, interpret: bool):
    """q [B,Sq,H,hd]; k,v [B,Sk,Hkv,hd] -> [B,Sq,H,hd]."""
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = scale if scale is not None else hd ** -0.5
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Sk)
    assert Sq % blk_q == 0 and Sk % blk_k == 0, (Sq, blk_q, Sk, blk_k)
    n_q, n_k = Sq // blk_q, Sk // blk_k
    q_offset = Sk - Sq  # align sequence ends

    # layout: heads become the leading grid axis
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, hd)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        blk_q=blk_q, blk_k=blk_k, n_k=n_k, q_offset=q_offset)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, blk_k, hd),
                         lambda bh, iq, ik, G=G: (bh // G, ik, 0)),
            pl.BlockSpec((1, blk_k, hd),
                         lambda bh, iq, ik, G=G: (bh // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)

