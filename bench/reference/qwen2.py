"""Plain float32 reference of the Qwen2 decoder, and its seeded weights.

Written from the published description (Qwen2 technical report,
arXiv:2407.10671, and the family's ``config.json`` keys), importing nothing
of the program under test:

* token embedding; ``num_hidden_layers`` pre-norm blocks; final RMSNorm;
  a head that is the embedding's transpose when ``tie_word_embeddings``;
* RMSNorm: ``x / sqrt(mean(x^2) + rms_norm_eps) * scale``;
* attention: grouped-query (``num_key_value_heads`` K/V heads shared by
  ``num_attention_heads / num_key_value_heads`` query heads), with biases
  on the Q, K and V projections and none on the output projection; RoPE
  on Q and K with base ``rope_theta``, rotating the two halves of each
  head (``x1 cos - x2 sin, x2 cos + x1 sin``); causal softmax scaled by
  ``head_dim ** -0.5``;
* MLP: SwiGLU, ``down(silu(gate(x)) * up(x))``.

RMSNorm's eps is the one the program runs where the configuration lists
it under ``departures`` (the program fixes it), else the published one.

Everything is computed in float32 at the highest matmul precision, one
layer at a time, with attention in blocks of queries and the MLP in blocks
of rows, so that a long sequence of a wide model fits beside the weights.

``weights`` makes the weights from a seed in the configuration's own
layout, in the type they are served in (bfloat16 matrices and biases,
float32 norm scales), on the device in one jitted call.  The benchmark
hands the same arrays, re-laid out, to the program; the reference makes
them again from the seed rather than take them from the program.

``forward(..., fp8=True)`` is the correctness check's control: the same
reference with every weight matrix rounded to float8 (e4m3, one scale per
output channel), the next precision below the bfloat16 it is served in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256            # queries per attention block, rows per MLP block


def _dims(c):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return (c["num_hidden_layers"], d, c["intermediate_size"], h,
            c["num_key_value_heads"], c.get("head_dim") or d // h,
            c["vocab_size"])


def shapes(c) -> dict:
    """Name -> (shape, dtype, std, mean) of every weight."""
    L, D, F, H, Hkv, hd, V = _dims(c)
    mat = jnp.dtype(c["torch_dtype"])
    s = {
        "embed": ((V, D), mat, D ** -0.5, 0.0),
        "final_norm": ((D,), jnp.float32, 0.1, 1.0),
        "ln1": ((L, D), jnp.float32, 0.1, 1.0),
        "ln2": ((L, D), jnp.float32, 0.1, 1.0),
        "wq": ((L, D, H * hd), mat, D ** -0.5, 0.0),
        "bq": ((L, H * hd), mat, 0.5, 0.0),
        "wk": ((L, D, Hkv * hd), mat, D ** -0.5, 0.0),
        "bk": ((L, Hkv * hd), mat, 0.5, 0.0),
        "wv": ((L, D, Hkv * hd), mat, D ** -0.5, 0.0),
        "bv": ((L, Hkv * hd), mat, 0.5, 0.0),
        "wo": ((L, H * hd, D), mat, (H * hd) ** -0.5, 0.0),
        "w_gate": ((L, D, F), mat, D ** -0.5, 0.0),
        "w_up": ((L, D, F), mat, D ** -0.5, 0.0),
        "w_down": ((L, F, D), mat, F ** -0.5, 0.0),
    }
    if not c["tie_word_embeddings"]:
        s["lm_head"] = ((D, V), mat, D ** -0.5, 0.0)
    return s


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    seed &= 2 ** 64 - 1
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def draw(c, key) -> dict:
    """Every weight from ``key`` (traceable: call it inside a jit)."""
    out = {}
    for i, (name, (shape, dt, std, mean)) in enumerate(
            sorted(shapes(c).items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = (x * std + mean).astype(dt)
    return out


def weights(c, seed: int) -> dict:
    """Every weight, made from ``seed`` on the device in one call."""
    return jax.jit(functools.partial(draw, c))(seed_key(seed))


# ------------------------------------------------------------- forward --
F8 = jnp.float8_e4m3fn
F8_MAX = float(jnp.finfo(F8).max)


def _mat(w, axis: int, fp8: bool):
    """A weight matrix in float32; with ``fp8``, first rounded to e4m3 with
    one scale per output channel (``axis`` is the one reduced over)."""
    w = w.astype(jnp.float32)
    if not fp8:
        return w
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(F8).astype(jnp.float32) * s


def eps_of(c) -> float:
    """RMSNorm's eps as the program runs it."""
    d = (c.get("departures") or {}).get("rms_norm_eps")
    return float(d["program"] if d else c["rms_norm_eps"])


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x [S, n, hd] at positions 0..S-1."""
    S, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / hd)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(fn, x, size):
    """fn over row blocks of x [S, ...] (S a multiple of size)."""
    S = x.shape[0]
    xs = x.reshape((S // size, size) + x.shape[1:])
    return jax.lax.map(fn, xs).reshape(S, -1)


@functools.partial(jax.jit, static_argnames=("dims", "eps", "theta", "fp8"))
def _layer(h, w, *, dims, eps, theta, fp8=False):
    L_, D, F, H, Hkv, hd, V = dims
    f32 = lambda a: a.astype(jnp.float32)
    w = dict(w, **{n: _mat(w[n], 0, fp8) for n in
                   ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")})
    S = h.shape[0]
    x = _rms(h, f32(w["ln1"]), eps)
    q = (x @ f32(w["wq"]) + f32(w["bq"])).reshape(S, H, hd)
    k = (x @ f32(w["wk"]) + f32(w["bk"])).reshape(S, Hkv, hd)
    v = (x @ f32(w["wv"]) + f32(w["bv"])).reshape(S, Hkv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    g = H // Hkv
    qg = q.reshape(S, Hkv, g, hd) * hd ** -0.5
    qb = BLOCK

    def attend(args):
        qc, start = args                      # qc [qb, Hkv, g, hd]
        s = jnp.einsum("qkgd,tkd->kgqt", qc, k)
        qpos = start + jnp.arange(qb)
        mask = qpos[:, None] >= jnp.arange(S)[None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)
    starts = jnp.arange(S // qb) * qb
    o = jax.lax.map(attend, (qg.reshape(S // qb, qb, Hkv, g, hd), starts))
    o = o.reshape(S, H * hd)
    h = h + o @ f32(w["wo"])
    x = _rms(h, f32(w["ln2"]), eps)

    def mlp(xr):
        return (jax.nn.silu(xr @ f32(w["w_gate"])) * (xr @ f32(w["w_up"]))) \
            @ f32(w["w_down"])
    return h + _blocks(mlp, x, BLOCK), k.reshape(S, -1), v.reshape(S, -1)


@functools.partial(jax.jit, static_argnames=("eps", "tied", "fp8"))
def _head(h, norm, head, *, eps, tied, fp8):
    x = _rms(h, norm, eps)
    return x @ (_mat(head, 1, fp8).T if tied else _mat(head, 0, fp8))


@functools.partial(jax.jit, static_argnames=("fp8",))
def _embed(table, tokens, *, fp8):
    return jnp.take(_mat(table, 1, fp8), tokens, axis=0)


def forward(c, w: dict, tokens, fp8: bool = False) -> tuple:
    """Float32 logits [S, V] at every position of ``tokens`` [S], and each
    layer's keys (after RoPE) and values [L, S, kv heads x head size]; S a
    multiple of ``BLOCK`` (pad at the end: attention is causal).  With
    ``fp8``, the control: every weight matrix rounded to e4m3 first."""
    dims = _dims(c)
    eps, theta = eps_of(c), float(c["rope_theta"])
    layer_names = ("ln1", "ln2", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
                   "w_gate", "w_up", "w_down")
    with jax.default_matmul_precision("highest"):
        h = _embed(w["embed"], jnp.asarray(tokens), fp8=fp8)
        ks, vs = [], []
        for i in range(dims[0]):
            h, k, v = _layer(h, {n: w[n][i] for n in layer_names},
                             dims=dims, eps=eps, theta=theta, fp8=fp8)
            ks.append(k)
            vs.append(v)
        tied = bool(c["tie_word_embeddings"])
        head = w["embed"] if tied else w["lm_head"]
        return (_head(h, w["final_norm"], head, eps=eps, tied=tied,
                      fp8=fp8), jnp.stack(ks),
                jnp.stack(vs))
