"""Grouped expert matmul (Pallas TPU): x [E,C,D] @ w [E,D,F] -> [E,C,F].

Grid (E, nC, nF, nD) with the D (contraction) axis innermost, accumulating
in a VMEM fp32 scratch tile — the MoE hot loop after dispatch.  Block
shapes default to MXU-native 128x128 tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, acc_sc, *, n_d):
    idd = pl.program_id(3)

    @pl.when(idd == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    acc_sc[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(idd == n_d - 1)
    def _fini():
        o_ref[0] = acc_sc[...].astype(o_ref.dtype)


def moe_gmm(x, w, *, blk_c=128, blk_f=128, blk_d=128,
            interpret: bool):
    E, C, D = x.shape
    F = w.shape[-1]
    blk_c, blk_f, blk_d = min(blk_c, C), min(blk_f, F), min(blk_d, D)
    assert C % blk_c == 0 and F % blk_f == 0 and D % blk_d == 0
    grid = (E, C // blk_c, F // blk_f, D // blk_d)
    return pl.pallas_call(
        functools.partial(_kernel, n_d=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_c, blk_d), lambda e, c, f, d: (e, c, d)),
            pl.BlockSpec((1, blk_d, blk_f), lambda e, c, f, d: (e, d, f)),
        ],
        out_specs=pl.BlockSpec((1, blk_c, blk_f), lambda e, c, f, d: (e, c, f)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((blk_c, blk_f), jnp.float32)],
        interpret=interpret,
    )(x, w)
