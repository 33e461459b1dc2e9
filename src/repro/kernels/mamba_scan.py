"""Mamba2 SSD intra-chunk kernel (Pallas TPU).

Grid (B, nh, nc): one program handles one head of one [Q, ...] chunk —
computes the intra-chunk (masked decay) contribution, the off-diagonal
term from the carried state, and the new chunk state.  The state is
carried across the sequentially-iterated nc grid axis in VMEM scratch
(same pattern the flash kernel uses for online softmax), so the HBM
traffic is one read of x/B/C/decay and one write of y + final state.

Every in-kernel value is a 2-D tile ([Q, P], [Q, N], [Q, Q], [N, P]), so
each contraction is one MXU matmul.  The wrapper moves heads to the front,
passes the chunk-local log-decay both as a column and as a row, and
precomputes the decays to the chunk's end (Mosaic cannot broadcast one
element across both tile axes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_T = (((0,), (0,)), ((), ()))      # contract the leading (Q) dims
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_NN = (((1,), (0,)), ((), ()))     # a @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _kernel(x_ref, b_ref, c_ref, ccol_ref, crow_ref, rem_ref, decay_ref,
            y_ref, st_ref, h_sc, *, n_c):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_sc[...] = jnp.zeros_like(h_sc)

    x = x_ref[0, 0, 0].astype(jnp.float32)       # [Q, P]
    Bm = b_ref[0, 0].astype(jnp.float32)         # [Q, N]
    Cm = c_ref[0, 0].astype(jnp.float32)         # [Q, N]
    ccol = ccol_ref[0, 0, 0]                     # [Q, 1] log-decay cumsum
    crow = crow_ref[0, 0, 0]                     # [1, Q]
    rem = rem_ref[0, 0, 0]                       # [Q, 1] decay to chunk end
    decay = decay_ref[0, 0, 0]                   # [1, P] whole-chunk decay
    Q = x.shape[0]

    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lmat = jnp.where(ii >= jj, jnp.exp(ccol - crow), 0.0)             # [Q,Q]
    y_diag = _dot(_dot(Cm, Bm, _NT) * lmat, x, _NN)                   # [Q,P]

    h_prev = h_sc[...]                                                # [N,P]
    y_off = _dot(Cm, h_prev, _NN) * jnp.exp(ccol)
    y_ref[0, 0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    new_h = h_prev * decay + _dot(Bm * rem, x, _T)
    h_sc[...] = new_h

    @pl.when(ic == n_c - 1)
    def _fini():
        st_ref[0, 0] = new_h.astype(st_ref.dtype)


def mamba_chunk_scan_chunked(xbar, B_c, C_c, cum, *, interpret: bool):
    """Chunked views: xbar [B,nc,Q,nh,P]; B_c,C_c [B,nc,Q,N];
    cum [B,nc,Q,nh] (log-decay cumsum, reset per chunk)
    -> (y [B,nc,Q,nh,P], final_state [B,nh,P,N])."""
    B, nc, Q, nh, P = xbar.shape
    N = B_c.shape[-1]
    xt = xbar.transpose(0, 3, 1, 2, 4)                 # [B,nh,nc,Q,P]
    ct = cum.astype(jnp.float32).transpose(0, 3, 1, 2)  # [B,nh,nc,Q]
    last = ct[..., -1:]
    decay = jnp.broadcast_to(jnp.exp(last)[..., None], (B, nh, nc, 1, P))
    head_chunk = lambda b, h, c: (b, h, c, 0, 0)       # noqa: E731
    chunk = lambda b, h, c: (b, c, 0, 0)               # noqa: E731
    y, st = pl.pallas_call(
        functools.partial(_kernel, n_c=nc),
        grid=(B, nh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), head_chunk),
            pl.BlockSpec((1, 1, Q, N), chunk),
            pl.BlockSpec((1, 1, Q, N), chunk),
            pl.BlockSpec((1, 1, 1, Q, 1), head_chunk),
            pl.BlockSpec((1, 1, 1, 1, Q), head_chunk),
            pl.BlockSpec((1, 1, 1, Q, 1), head_chunk),
            pl.BlockSpec((1, 1, 1, 1, P), head_chunk),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), head_chunk),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nh, nc, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xt, B_c, C_c, ct[..., None], ct[..., None, :],
      jnp.exp(last - ct)[..., None], decay)
    return y.transpose(0, 2, 3, 1, 4), st.transpose(0, 1, 3, 2)
