"""Core layers: schemas (shape+logical-axes), norms, RoPE, attention, MLP.

Params are described by ``ParamSpec`` schemas so the same definition serves
three consumers: real init (tests/examples), abstract init (dry-run
ShapeDtypeStructs), and sharding resolution (logical axes -> PartitionSpec).

Attention is computed in query chunks (flash-style memory footprint in pure
JAX; the Pallas kernel in repro.kernels is a drop-in for real TPUs).  SWA
slices only the needed KV window per query chunk, so 500k-token sequences
never materialize quadratic scores.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding import constrain


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    scale: float = 0.02          # init std; 0.0 -> zeros; -1.0 -> ones
    dtype: Optional[str] = None  # None -> model dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def materialize(schema, key, default_dtype):
    leaves, treedef = jax.tree.flatten(
        schema, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, sp in zip(keys, leaves):
        dt = jnp.dtype(sp.dtype or default_dtype)
        if sp.scale == 0.0:
            out.append(jnp.zeros(sp.shape, dt))
        elif sp.scale == -1.0:
            out.append(jnp.ones(sp.shape, dt))
        else:
            out.append((jax.random.normal(k, sp.shape, jnp.float32) * sp.scale).astype(dt))
    return jax.tree.unflatten(treedef, out)


def abstract(schema, default_dtype):
    return jax.tree.map(
        lambda sp: jax.ShapeDtypeStruct(sp.shape, jnp.dtype(sp.dtype or default_dtype)),
        schema, is_leaf=lambda x: isinstance(x, ParamSpec))


def axes_tree(schema):
    return jax.tree.map(lambda sp: sp.axes, schema,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------- norms ----
def norm_schema(d, kind="rmsnorm"):
    s = {"scale": ParamSpec((d,), ("norm",), -1.0, "float32")}
    if kind == "layernorm":
        s["bias"] = ParamSpec((d,), ("norm",), 0.0, "float32")
    return s


def apply_norm(p, x, kind="rmsnorm", eps=1e-5):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        xf = xf - xf.mean(-1, keepdims=True)
    var = (xf * xf).mean(-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    if "bias" in p:
        y = y + p["bias"]
    return y.astype(x.dtype)


# ----------------------------------------------------------------- rope ----
def rope(x, pos, theta):
    """x: [..., S, H, hd]; pos: broadcastable to [..., S]."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freq          # [..., S, half]
    cos = jnp.cos(ang)[..., None, :]                          # [..., S, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ------------------------------------------------------------ attention ----
def _masked_softmax(scores, mask):
    scores = jnp.where(mask, scores, -1e30)
    m = jnp.max(scores, -1, keepdims=True)
    e = jnp.exp(scores - jax.lax.stop_gradient(m))
    denom = jnp.sum(e, -1, keepdims=True)
    return e / jnp.maximum(denom, 1e-30)


def chunked_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                      chunk=1024, rules=None):
    """q [B,Sq,H,hd]; k,v [B,Sk,Hkv,hd]; positions: q at q_offset+i, k at j.

    Scans over query chunks; with SWA only the [start-W, end) KV slice is
    touched per chunk, keeping both memory and FLOPs sub-quadratic.
    """
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]
    scale = hd ** -0.5
    if Hkv != H:  # GQA: repeat KV so the head dim shards cleanly over TP
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    if rules is not None:
        hax = ("batch", None, "heads", "head_dim")
        q, k, v = (constrain(t, hax, rules) for t in (q, k, v))
    chunk = min(chunk, Sq)
    pad = (-Sq) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = q.shape[1] // chunk
    qs = q.reshape(B, nc, chunk, H, hd).transpose(1, 0, 2, 3, 4)

    kv_span = min(Sk, (window + chunk) if window else Sk)

    def body(c, qc):
        q_start = c * chunk + q_offset
        if window:
            start = jnp.clip(q_start + chunk - kv_span, 0, max(Sk - kv_span, 0))
        else:
            start = 0
        kc = jax.lax.dynamic_slice_in_dim(k, start, kv_span, 1)
        vc = jax.lax.dynamic_slice_in_dim(v, start, kv_span, 1)
        pq = q_start + jnp.arange(chunk)
        pk = start + jnp.arange(kv_span)
        mask = jnp.ones((chunk, kv_span), bool)
        if causal:
            mask &= pq[:, None] >= pk[None, :]
        if window:
            mask &= pq[:, None] - pk[None, :] < window
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc,
                       preferred_element_type=jnp.float32) * scale
        a = _masked_softmax(s, mask[None, None])
        o = jnp.einsum("bhqk,bkhd->bqhd", a.astype(vc.dtype), vc,
                       preferred_element_type=jnp.float32)
        return c + 1, o.astype(q.dtype)

    _, outs = jax.lax.scan(body, jnp.int32(0), qs)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nc * chunk, H, hd_v)
    return out[:, :Sq]


def decode_attention(q, k_cache, v_cache, pos, *, window=0,
                     k_scale=None, v_scale=None):
    """q [B,1,H,hd]; caches [B,W,Hkv,hd]; pos [B] current absolute position.

    Ring cache for SWA (slot = p % W); dense cache otherwise (slot = p).
    GQA handled by grouping q as [B,Hkv,G,hd] against the Hkv-cache — the
    cache stays SP-sharded on W (kv_seq), so the group reshape is benign.
    """
    B, _, H, hd = q.shape
    W, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    scale = hd ** -0.5
    slots = jnp.arange(W)
    if window:
        slot_pos = pos[:, None] - ((pos[:, None] - slots[None]) % W)
    else:
        slot_pos = jnp.broadcast_to(slots[None], (B, W))
    valid = (slot_pos >= 0) & (slot_pos <= pos[:, None])
    if window:
        valid &= pos[:, None] - slot_pos < window
    qg = q.reshape(B, Hkv, G, hd)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    if k_scale is not None:  # int8 cache: apply per-(token,head) scales
        s = s * k_scale[..., 0].transpose(0, 2, 1)[:, :, None, :]
    a = _masked_softmax(s, valid[:, None, None])
    if v_scale is not None:
        a = a * v_scale[..., 0].transpose(0, 2, 1)[:, :, None, :]
        o = jnp.einsum("bhgk,bkhd->bhgd", a, v_cache.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    else:
        o = jnp.einsum("bhgk,bkhd->bhgd", a.astype(v_cache.dtype), v_cache,
                       preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def gqa_schema(cfg):
    D, H, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd()
    s = {
        "wq": ParamSpec((D, H, hd), ("fsdp", "heads", "head_dim"), D ** -0.5),
        "wk": ParamSpec((D, Hkv, hd), ("fsdp", "kv_heads", "head_dim"), D ** -0.5),
        "wv": ParamSpec((D, Hkv, hd), ("fsdp", "kv_heads", "head_dim"), D ** -0.5),
        "wo": ParamSpec((H, hd, D), ("heads", "head_dim", "fsdp"), (H * hd) ** -0.5),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((H, hd), ("heads", "head_dim"), 0.0)
        s["bk"] = ParamSpec((Hkv, hd), ("kv_heads", "head_dim"), 0.0)
        s["bv"] = ParamSpec((Hkv, hd), ("kv_heads", "head_dim"), 0.0)
    return s


def gqa_qkv(p, x, cfg, pos):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.rope_theta:
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    return q, k, v


def gqa_attention(p, x, cfg, *, rules=None, cross_kv=None, causal=True):
    """Full-sequence (train / prefill) GQA or cross attention."""
    B, S, _ = x.shape
    pos = jnp.arange(S)[None]
    if cross_kv is not None:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if "bq" in p:
            q = q + p["bq"]
        k, v = cross_kv
        causal = False
    else:
        q, k, v = gqa_qkv(p, x, cfg, pos)
    o = chunked_attention(q, k, v, causal=causal,
                          window=cfg.sliding_window, rules=rules)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), (k, v)


def kv_quantize(t):
    """t [..., Hkv, hd] -> (int8, f32 scale [..., Hkv, 1])."""
    f = t.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(f), axis=-1, keepdims=True), 1e-6) / 127.0
    return jnp.clip(jnp.round(f / s), -127, 127).astype(jnp.int8), s


def layer_slice(t, layer):
    """One layer of a stage's stacked cache leaf; ``t`` itself when
    ``layer`` is None (an unstacked, one-block stage)."""
    if layer is None:
        return t
    return jax.lax.dynamic_index_in_dim(t, layer, keepdims=False)


def _write_rows(cache, rows, layer, slot):
    """Set row ``[layer, b, slot[b]]`` (``[b, slot[b]]`` when ``layer`` is
    None) of each cache leaf named in ``rows`` [B, ...]."""
    bidx = jnp.arange(slot.shape[0])
    at = (bidx, slot) if layer is None else (layer, bidx, slot)
    return {n: cache[n].at[at].set(r) for n, r in rows.items()}


def gqa_decode(p, x, cfg, cache, pos, layer=None):
    """x [B,1,D]; cache dict {'k','v'[, 'k_s','v_s']} -> (out, new cache).

    With ``layer`` the leaves are the stage's whole stack [n,B,W,...],
    carried through the decode layer scan: this layer's new K/V row is
    written in place at ``[layer, b, slot_b]`` (the ring slot ``pos % W``
    under a sliding window) and attention reads ``cache[layer]``. Nothing
    else of the cache is copied. Without ``layer`` the leaves are one
    layer's [B,W,...].
    """
    q, k, v = gqa_qkv(p, x, cfg, pos[:, None])
    W = cache["k"].shape[-3]
    slot = (pos % W) if cfg.sliding_window else pos
    if cfg.kv_quant:
        kq, ks = kv_quantize(k[:, 0])
        vq, vs = kv_quantize(v[:, 0])
        rows = {"k": kq, "k_s": ks, "v": vq, "v_s": vs}
    else:
        rows = {"k": k[:, 0], "v": v[:, 0]}
    cache = _write_rows(cache, rows, layer, slot)
    c = {n: layer_slice(t, layer) for n, t in cache.items()}
    o = decode_attention(q, c["k"], c["v"], pos, window=cfg.sliding_window,
                         k_scale=c.get("k_s"), v_scale=c.get("v_s"))
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), cache


# ------------------------------------------------------------------ MLA ----
def mla_schema(cfg):
    D, H = cfg.d_model, cfg.num_heads
    m = cfg.mla
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq": ParamSpec((D, H, qk), ("fsdp", "heads", "head_dim"), D ** -0.5),
        "w_dkv": ParamSpec((D, m.kv_lora_rank + m.qk_rope_head_dim),
                           ("fsdp", "kv_lora"), D ** -0.5),
        "kv_norm": norm_schema(m.kv_lora_rank),
        "w_uk": ParamSpec((m.kv_lora_rank, H, m.qk_nope_head_dim),
                          ("kv_lora", "heads", "head_dim"), m.kv_lora_rank ** -0.5),
        "w_uv": ParamSpec((m.kv_lora_rank, H, m.v_head_dim),
                          ("kv_lora", "heads", "head_dim"), m.kv_lora_rank ** -0.5),
        "wo": ParamSpec((H, m.v_head_dim, D), ("heads", "head_dim", "fsdp"),
                        (H * m.v_head_dim) ** -0.5),
    }


def _mla_latent(p, x, cfg, pos):
    m = cfg.mla
    ckr = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    c_kv, k_rope = ckr[..., :m.kv_lora_rank], ckr[..., m.kv_lora_rank:]
    c_kv = apply_norm(p["kv_norm"], c_kv)
    k_rope = rope(k_rope[:, :, None, :], pos, cfg.rope_theta)[:, :, 0]
    return c_kv, k_rope


def mla_attention(p, x, cfg, *, rules=None):
    """Prefill/train: decompress latent to per-head K/V, chunked attention."""
    B, S, _ = x.shape
    m = cfg.mla
    pos = jnp.arange(S)[None]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = rope(q_rope, pos, cfg.rope_theta)
    c_kv, k_rope = _mla_latent(p, x, cfg, pos)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uv"])
    H = cfg.num_heads
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None], (B, S, H, m.qk_rope_head_dim))], -1)
    qf = jnp.concatenate([q_nope, q_rope], -1)
    o = chunked_attention(qf, k, v, causal=True, rules=rules)
    # pad v-dim back: o has head_dim qk? no — v head dim = m.v_head_dim
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), (c_kv, k_rope)


def mla_decode(p, x, cfg, cache, pos, layer=None):
    """Absorbed-matrices decode: scores/combine in the 512-d latent space.

    cache {'c','kr'} -> (out, new cache), stacked or not as in
    ``gqa_decode``: with ``layer`` one latent row is written in place into
    the carried stack and attention reads ``cache[layer]``.
    """
    m = cfg.mla
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = rope(q_rope, pos[:, None], cfg.rope_theta)
    c_kv, k_rope = _mla_latent(p, x, cfg, pos[:, None])
    cache = _write_rows(cache, {"c": c_kv[:, 0], "kr": k_rope[:, 0]},
                        layer, pos)
    cache_c = layer_slice(cache["c"], layer)
    cache_kr = layer_slice(cache["kr"], layer)
    # absorb W_uk into q:   q_lat [B,H,R]
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], p["w_uk"])
    s = jnp.einsum("bhr,bsr->bhs", q_lat, cache_c,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bhk,bsk->bhs", q_rope[:, 0], cache_kr,
                       preferred_element_type=jnp.float32)
    s = s * ((m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5)
    valid = jnp.arange(cache_c.shape[1])[None] <= pos[:, None]
    a = _masked_softmax(s, valid[:, None])
    ctx = jnp.einsum("bhs,bsr->bhr", a.astype(cache_c.dtype), cache_c,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    o = jnp.einsum("bhr,rhk->bhk", ctx, p["w_uv"])
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None]
    return out, cache


# ------------------------------------------------------------------ MLP ----
def mlp_schema(cfg, d_ff=None):
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    s = {"w2": ParamSpec((F, D), ("ffn", "fsdp"), F ** -0.5)}
    if cfg.act == "silu":
        s["w1"] = ParamSpec((D, F), ("fsdp", "ffn"), D ** -0.5)
        s["w3"] = ParamSpec((D, F), ("fsdp", "ffn"), D ** -0.5)
    else:
        s["w1"] = ParamSpec((D, F), ("fsdp", "ffn"), D ** -0.5)
        if cfg.mlp_bias:
            s["b1"] = ParamSpec((F,), ("ffn",), 0.0)
            s["b2"] = ParamSpec((D,), ("norm",), 0.0)
    return s


def apply_mlp(p, x, cfg, rules=None):
    cst = (lambda t: constrain(t, ("batch", None, "ffn"), rules)) \
        if (rules is not None and x.ndim == 3) else (lambda t: t)
    if "w3" in p:
        h = cst(jax.nn.silu(x @ p["w1"])) * cst(x @ p["w3"])
    else:
        h = x @ p["w1"]
        if "b1" in p:
            h = h + p["b1"]
        h = cst(jax.nn.gelu(h))
    y = h @ p["w2"]
    if "b2" in p:
        y = y + p["b2"]
    return y
