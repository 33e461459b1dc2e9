"""mLSTM chunkwise kernel (Pallas TPU): matrix-memory linear attention with
per-head scalar decay, numerator+denominator carried across chunks in VMEM
scratch (grid (B, nh, nc), nc sequential).

Every in-kernel value is a 2-D tile ([Q, dh], [Q, Q], [dh, dh]), so each
contraction is one MXU matmul.  The wrapper moves heads to the front,
passes the forget-gate cumsum as a column and a row and the input gate as
a row, and precomputes the weights to the chunk's end (Mosaic cannot
broadcast one element across both tile axes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mamba_scan import _NN, _NT, _T, _dot


def _kernel(q_ref, k_ref, v_ref, fcol_ref, frow_ref, irow_ref, wgt_ref,
            cd_ref, y_ref, h_sc, n_sc):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_sc[...] = jnp.zeros_like(h_sc)
        n_sc[...] = jnp.zeros_like(n_sc)

    q = q_ref[0, 0, 0].astype(jnp.float32)      # [Q, dh]
    k = k_ref[0, 0, 0].astype(jnp.float32)
    v = v_ref[0, 0, 0].astype(jnp.float32)
    fcol, frow = fcol_ref[0, 0, 0], frow_ref[0, 0, 0]   # [Q,1], [1,Q]
    irow = irow_ref[0, 0, 0]                    # [1,Q]
    wgt = wgt_ref[0, 0, 0]                      # [Q,1] weight to chunk end
    cd = cd_ref[0, 0, 0]                        # [1,dh] whole-chunk decay
    Q = q.shape[0]

    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lmat = jnp.where(ii >= jj, jnp.exp(fcol - frow + irow), 0.0)     # [Q,Q]
    y_diag = _dot(_dot(q, k, _NT) * lmat, v, _NN)                     # [Q,dh]
    n_diag = _dot(lmat, k, _NN)

    h_prev, n_prev = h_sc[...], n_sc[...]       # [dh,dh], [1,dh]
    iw = jnp.exp(fcol)                                                # [Q,1]
    y_off = _dot(q, h_prev, _NN) * iw
    n_off = jnp.sum(q * n_prev, axis=1, keepdims=True) * iw
    n = jnp.sum(q * n_diag, axis=1, keepdims=True) + n_off            # [Q,1]
    y = (y_diag + y_off) / jnp.maximum(jnp.abs(n), 1.0)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    kbar = k * wgt
    h_sc[...] = h_prev * cd + _dot(kbar, v, _T)
    n_sc[...] = n_prev * cd + jnp.sum(kbar, axis=0, keepdims=True)


def mlstm_chunk_scan(q, k, v, cumf, li, *, interpret: bool):
    """Chunked views: q,k,v [B,nc,Q,nh,dh]; cumf,li [B,nc,Q,nh]
    -> y [B,nc,Q,nh,dh] (fp32)."""
    B, nc, Q, nh, dh = q.shape
    heads_first = lambda a: a.transpose(0, 3, 1, 2, 4)  # noqa: E731
    ft = cumf.astype(jnp.float32).transpose(0, 3, 1, 2)  # [B,nh,nc,Q]
    it = li.astype(jnp.float32).transpose(0, 3, 1, 2)
    last = ft[..., -1:]
    cd = jnp.broadcast_to(jnp.exp(last)[..., None], (B, nh, nc, 1, dh))
    idx = lambda b, h, c: (b, h, c, 0, 0)                # noqa: E731
    tile = pl.BlockSpec((1, 1, 1, Q, dh), idx)
    col = pl.BlockSpec((1, 1, 1, Q, 1), idx)
    row = pl.BlockSpec((1, 1, 1, 1, Q), idx)
    lanes = pl.BlockSpec((1, 1, 1, 1, dh), idx)
    y = pl.pallas_call(
        _kernel,
        grid=(B, nh, nc),
        in_specs=[tile, tile, tile, col, row, row, col, lanes],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((B, nh, nc, Q, dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32),
                        pltpu.VMEM((1, dh), jnp.float32)],
        interpret=interpret,
    )(heads_first(q), heads_first(k), heads_first(v), ft[..., None],
      ft[..., None, :], it[..., None, :],
      jnp.exp(last - ft + it)[..., None], cd)
    return y.transpose(0, 2, 3, 1, 4)
