"""Stage-structured unified model.

A model is a list of *stages*; each stage is ``lax.scan`` over ``n``
identical blocks (params stacked on a leading axis).  Compile time is O(1)
in depth; the roofline analyzer multiplies while-body costs by the scan trip
count read from HLO ``known_trip_count``.

Supported block kinds: dense (GQA/SWA, optional parallel-block), moe,
mla_dense / mla_moe (deepseek), enc / dec (whisper), mamba (mamba2),
zamba_group (6 mamba + shared attention block), xlstm_group (5 mLSTM +
1 sLSTM).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models import xlstm as XL
from repro.models.config import ModelConfig
from repro.models.layers import ParamSpec
from repro.sharding import constrain


def _maybe_dequant(p):
    """Transparently dequantize int8 serving weights ({'q','s'} leaves) —
    inside the layer-scan body, so only one layer's weights materialize in
    bf16 at a time (repro.serving.quant)."""
    from repro.serving.quant import dequantize
    has_q = any(isinstance(x, dict) and set(x) == {"q", "s"}
                for x in jax.tree.leaves(
                    p, is_leaf=lambda x: isinstance(x, dict) and
                    set(x) == {"q", "s"}))
    return dequantize(p) if has_q else p


@dataclasses.dataclass(frozen=True)
class StageDef:
    kind: str
    n: int


def stack_schema(schema, n: int):
    if n == 1:
        return schema
    return jax.tree.map(
        lambda sp: ParamSpec((n,) + sp.shape, ("stack",) + sp.axes, sp.scale,
                             sp.dtype),
        schema, is_leaf=lambda x: isinstance(x, ParamSpec))


# --------------------------------------------------------------- stages ----
def build_stages(cfg: ModelConfig) -> List[StageDef]:
    if cfg.family == "moe" and cfg.attention == "mla":
        return [StageDef("mla_dense", 1), StageDef("mla_moe", cfg.num_layers - 1)]
    if cfg.family == "moe":
        return [StageDef("moe", cfg.num_layers)]
    if cfg.family == "audio":
        return [StageDef("enc", cfg.encdec.num_encoder_layers),
                StageDef("dec", cfg.num_layers)]
    if cfg.family == "ssm":  # xlstm: groups of 6 (sLSTM at in-group index 3)
        assert cfg.num_layers % 6 == 0
        return [StageDef("xlstm_group", cfg.num_layers // 6)]
    if cfg.family == "hybrid":  # zamba2: groups of (shared_every mamba + shared attn)
        g = cfg.shared_every
        return [StageDef("zamba_group", cfg.num_layers // g)]
    return [StageDef("dense", cfg.num_layers)]


def _moe_shard_mode(cfg) -> str:
    return "expert" if cfg.moe and cfg.moe.num_experts >= 16 else "ffn"


def _block_schema(cfg: ModelConfig, kind: str):
    D = cfg.d_model
    nrm = lambda: L.norm_schema(D, cfg.norm)
    if kind == "dense":
        s = {"ln1": nrm(), "attn": L.gqa_schema(cfg)}
        if cfg.parallel_block:
            s["mlp"] = L.mlp_schema(cfg)
        else:
            s["ln2"] = nrm()
            s["mlp"] = L.mlp_schema(cfg)
        return s
    if kind == "moe":
        return {"ln1": nrm(), "attn": L.gqa_schema(cfg), "ln2": nrm(),
                "moe": MOE.moe_schema(cfg, _moe_shard_mode(cfg))}
    if kind == "mla_dense":
        return {"ln1": nrm(), "attn": L.mla_schema(cfg), "ln2": nrm(),
                "mlp": L.mlp_schema(cfg, cfg.dense_first_layer_d_ff or cfg.d_ff)}
    if kind == "mla_moe":
        return {"ln1": nrm(), "attn": L.mla_schema(cfg), "ln2": nrm(),
                "moe": MOE.moe_schema(cfg, _moe_shard_mode(cfg))}
    if kind == "enc":
        return {"ln1": nrm(), "attn": L.gqa_schema(cfg), "ln2": nrm(),
                "mlp": L.mlp_schema(cfg)}
    if kind == "dec":
        return {"ln1": nrm(), "attn": L.gqa_schema(cfg),
                "lnx": nrm(), "xattn": L.gqa_schema(cfg),
                "ln2": nrm(), "mlp": L.mlp_schema(cfg)}
    if kind == "mamba":
        return {"ln1": nrm(), "mamba": SSM.mamba2_schema(cfg)}
    if kind == "zamba_group":
        return {"mambas": stack_schema(
            {"ln1": nrm(), "mamba": SSM.mamba2_schema(cfg)}, cfg.shared_every)}
    if kind == "xlstm_group":
        return {"m": stack_schema(
            {"ln1": nrm(), "cell": XL.mlstm_schema(cfg)}, 5),
            "s": {"ln1": nrm(), "cell": XL.slstm_schema(cfg)}}
    raise ValueError(kind)


def model_schema(cfg: ModelConfig):
    D, V = cfg.d_model, cfg.vocab_size
    s: Dict[str, Any] = {
        "embed": ParamSpec((V, D), ("vocab", "fsdp"), D ** -0.5),
        "final_norm": L.norm_schema(D, cfg.norm),
        "stages": [stack_schema(_block_schema(cfg, st.kind), st.n)
                   for st in build_stages(cfg)],
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = ParamSpec((D, V), ("fsdp", "vocab"), D ** -0.5)
    if cfg.family == "hybrid":  # zamba2 shared attention block (applied per group)
        s["shared"] = {"ln1": L.norm_schema(D, cfg.norm),
                       "attn": L.gqa_schema(cfg), "ln2": L.norm_schema(D, cfg.norm),
                       "mlp": L.mlp_schema(cfg)}
    if cfg.family == "audio":
        s["enc_pos"] = ParamSpec((cfg.encdec.encoder_seq, D), ("seq", "fsdp"), 0.02)
        s["dec_pos"] = ParamSpec((cfg.max_seq, D), ("seq", "fsdp"), 0.02)
    if cfg.family == "vlm":
        s["img_proj"] = ParamSpec((D, D), ("fsdp", None), D ** -0.5)
    return s


def init_params(cfg: ModelConfig, key):
    return L.materialize(model_schema(cfg), key, cfg.dtype)


def abstract_params(cfg: ModelConfig):
    return L.abstract(model_schema(cfg), cfg.dtype)


def param_axes(cfg: ModelConfig):
    return L.axes_tree(model_schema(cfg))


# -------------------------------------------------------------- forward ----
def _block_forward(kind, p, h, cfg, rules, shared=None, enc_out=None):
    """Full-sequence forward for one block. Returns (h, aux_loss, cache_out)."""
    p = _maybe_dequant(p)
    aux = 0.0
    cache_out = ()
    if kind in ("dense", "moe", "enc", "mla_dense", "mla_moe"):
        hn = L.apply_norm(p["ln1"], h, cfg.norm)
        if kind in ("mla_dense", "mla_moe"):
            a, (c_kv, k_rope) = L.mla_attention(p["attn"], hn, cfg, rules=rules)
            cache_out = {"c": c_kv, "kr": k_rope}
        else:
            a, (k, v) = L.gqa_attention(p["attn"], hn, cfg, rules=rules,
                                        causal=(kind != "enc"))
            cache_out = {"k": k, "v": v}
        if cfg.parallel_block:
            m = L.apply_mlp(p["mlp"], hn, cfg, rules)
            h = h + a + m
        else:
            h = h + a
            hn2 = L.apply_norm(p["ln2"], h, cfg.norm)
            if kind in ("moe", "mla_moe"):
                m, aux = MOE.apply_moe(p["moe"], hn2, cfg, rules=rules,
                                       group_size=getattr(cfg, "_moe_group", 0))
            else:
                m = L.apply_mlp(p["mlp"], hn2, cfg, rules)
            h = h + m
    elif kind == "dec":
        hn = L.apply_norm(p["ln1"], h, cfg.norm)
        a, (k, v) = L.gqa_attention(p["attn"], hn, cfg, rules=rules)
        h = h + a
        hx = L.apply_norm(p["lnx"], h, cfg.norm)
        xk = jnp.einsum("bsd,dhk->bshk", enc_out, p["xattn"]["wk"])
        xv = jnp.einsum("bsd,dhk->bshk", enc_out, p["xattn"]["wv"])
        a, _ = L.gqa_attention(p["xattn"], hx, cfg, rules=rules, cross_kv=(xk, xv))
        h = h + a
        hn2 = L.apply_norm(p["ln2"], h, cfg.norm)
        h = h + L.apply_mlp(p["mlp"], hn2, cfg, rules)
        cache_out = {"k": k, "v": v, "xk": xk, "xv": xv}
    elif kind == "mamba":
        hn = L.apply_norm(p["ln1"], h, cfg.norm)
        y, cache_out = SSM.mamba2_forward(p["mamba"], hn, cfg, rules)
        h = h + y
    elif kind == "zamba_group":
        m_states = []
        for i in range(cfg.shared_every):
            pm = jax.tree.map(lambda t: t[i], p["mambas"])
            hn = L.apply_norm(pm["ln1"], h, cfg.norm)
            y, stt = SSM.mamba2_forward(pm["mamba"], hn, cfg, rules)
            m_states.append(stt)
            h = h + y
        hn = L.apply_norm(shared["ln1"], h, cfg.norm)
        a, (k, v) = L.gqa_attention(shared["attn"], hn, cfg, rules=rules)
        h = h + a
        hn = L.apply_norm(shared["ln2"], h, cfg.norm)
        h = h + L.apply_mlp(shared["mlp"], hn, cfg, rules)
        cache_out = {"mamba": jax.tree.map(lambda *xs: jnp.stack(xs), *m_states),
                     "attn": {"k": k, "v": v}}
    elif kind == "xlstm_group":
        order = [0, 1, 2, None, 3, 4]  # None -> sLSTM (in-group index 3)
        m_states, s_state = [], None
        for idx in order:
            if idx is None:
                hn = L.apply_norm(p["s"]["ln1"], h, cfg.norm)
                y, s_state = XL.slstm_forward(p["s"]["cell"], hn, cfg, rules)
            else:
                pm = jax.tree.map(lambda t: t[idx], p["m"])
                hn = L.apply_norm(pm["ln1"], h, cfg.norm)
                y, m_states_i = XL.mlstm_forward(pm["cell"], hn, cfg, rules)
                m_states.append({"C": m_states_i[0], "n": m_states_i[1]})
            h = h + y
        hc, cc, nc_, mc = s_state
        cache_out = {"m": jax.tree.map(lambda *xs: jnp.stack(xs), *m_states),
                     "s": {"h": hc, "c": cc, "n": nc_, "m": mc}}
    else:
        raise ValueError(kind)
    h = constrain(h, ("batch", "seq", None), rules) if rules else h
    return h, aux, cache_out


def forward(params, cfg: ModelConfig, batch: Dict[str, Any], rules=None,
            remat: str = "none", collect_cache: bool = False):
    """Full-sequence forward -> (logits [B,S,V], aux_loss[, kv_stacks]).

    batch: tokens [B,S]; audio adds frames [B,enc_S,D]; vlm adds image
    embeds [B,n_img,D] prepended to the text sequence.  With
    ``collect_cache`` the per-block K/V (or final SSM states) are returned
    for prefill-cache assembly (see ``assemble_caches``).
    """
    tokens = batch["tokens"]
    params = {k: (_maybe_dequant(v) if k != "stages" else v)
              for k, v in params.items()}
    h = jnp.take(params["embed"], tokens, axis=0)
    h = constrain(h, ("batch", "seq", None), rules) if rules else h
    n_img = 0
    if cfg.family == "vlm" and "image_embeds" in batch:
        img = batch["image_embeds"].astype(h.dtype) @ params["img_proj"]
        h = jnp.concatenate([img, h], axis=1)
        n_img = img.shape[1]
    enc_out = None
    if cfg.family == "audio":
        h_dec = h + params["dec_pos"][:h.shape[1]].astype(h.dtype)
        enc_out = batch["frames"].astype(h.dtype) + \
            params["enc_pos"].astype(h.dtype)
        h = enc_out  # first stage is the encoder

    stages = build_stages(cfg)
    aux_total = 0.0
    kv_stacks = []
    for st, sp in zip(stages, params["stages"]):
        if cfg.family == "audio" and st.kind == "dec":
            enc_out, h = h, h_dec  # encoder output feeds decoder cross-attn

        def body(carry, pl, _kind=st.kind):
            hh, aux = carry
            hh, a, kv = _block_forward(_kind, pl, hh, cfg, rules,
                                       shared=params.get("shared"),
                                       enc_out=enc_out)
            return (hh, aux + a), (kv if collect_cache else ())
        if remat != "none":
            body = jax.checkpoint(
                body, policy=_remat_policy(remat), static_argnums=())
        if st.n == 1:
            (h, aux_total), kvs = body((h, aux_total), sp)
        else:
            (h, aux_total), kvs = jax.lax.scan(body, (h, aux_total), sp)
        kv_stacks.append(kvs)

    h = L.apply_norm(params["final_norm"], h, cfg.norm)
    if n_img:
        h = h[:, n_img:]
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", h, head) * cfg.logit_scale
    if rules is not None:
        logits = constrain(logits, ("batch", "seq", "vocab"), rules)
    if collect_cache:
        return logits, aux_total, kv_stacks
    return logits, aux_total


def _remat_policy(name: str):
    pol = jax.checkpoint_policies
    return {"full": pol.nothing_saveable,
            "dots": pol.dots_with_no_batch_dims_saveable,
            "minimal": pol.everything_saveable}[name]


# --------------------------------------------------------------- decode ----
def init_cache(cfg: ModelConfig, batch: int, cache_len: int, enc_S: int = 0):
    """Cache pytree per stage (leading stage-scan axis when n>1)."""
    dt = jnp.dtype(cfg.dtype)
    Hkv, hd = cfg.num_kv_heads, cfg.hd()
    W = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len

    def kv(n, w=None):
        w = w or W
        shape = (batch, w, Hkv, hd) if n == 1 else (n, batch, w, Hkv, hd)
        if cfg.kv_quant:
            sshape = shape[:-1] + (1,)
            return {"k": jnp.zeros(shape, jnp.int8),
                    "k_s": jnp.zeros(sshape, jnp.float32),
                    "v": jnp.zeros(shape, jnp.int8),
                    "v_s": jnp.zeros(sshape, jnp.float32)}
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    caches = []
    for st in build_stages(cfg):
        if st.kind in ("dense", "moe", "enc"):
            caches.append(kv(st.n))
        elif st.kind in ("mla_dense", "mla_moe"):
            m = cfg.mla
            shp = lambda d: ((batch, cache_len, d) if st.n == 1 else
                             (st.n, batch, cache_len, d))
            caches.append({"c": jnp.zeros(shp(m.kv_lora_rank), dt),
                           "kr": jnp.zeros(shp(m.qk_rope_head_dim), dt)})
        elif st.kind == "dec":
            c = kv(st.n)
            xshape = (st.n, batch, enc_S, Hkv, hd)
            c["xk"] = jnp.zeros(xshape, dt)
            c["xv"] = jnp.zeros(xshape, dt)
            caches.append(c)
        elif st.kind == "mamba":
            caches.append(_stack_state(SSM.mamba2_init_state(cfg, batch, dt), st.n))
        elif st.kind == "zamba_group":
            caches.append({
                "mamba": _stack_state(_stack_state(
                    SSM.mamba2_init_state(cfg, batch, dt), cfg.shared_every), st.n),
                "attn": kv(st.n, w=cache_len)})
        elif st.kind == "xlstm_group":
            caches.append({
                "m": _stack_state(_stack_state(XL.mlstm_init_state(cfg, batch), 5), st.n),
                "s": _stack_state(XL.slstm_init_state(cfg, batch), st.n)})
        else:
            raise ValueError(st.kind)
    return caches


def _stack_state(state, n):
    if n == 1:
        return state
    return jax.tree.map(lambda t: jnp.broadcast_to(t[None], (n,) + t.shape), state)


def cache_axes(cfg: ModelConfig):
    """Logical axes mirroring ``init_cache`` (serve: kv_seq -> SP over tp)."""
    kv = ("batch", "kv_seq", "kv_heads", "head_dim")
    st = lambda n, ax: ax if n == 1 else ("stack",) + ax
    kv_entry = lambda n: (
        {"k": st(n, kv), "k_s": st(n, kv), "v": st(n, kv),
         "v_s": st(n, kv)} if cfg.kv_quant else
        {"k": st(n, kv), "v": st(n, kv)})
    mamba_ax = lambda pre: {"ssm": pre + ("batch", "ssm_heads", None, None),
                            "conv": {"x": pre + ("batch", None, "ssm_inner"),
                                     "bc": pre + ("batch", None, None)}}
    axes = []
    for s in build_stages(cfg):
        pre = () if s.n == 1 else ("stack",)
        if s.kind in ("dense", "moe", "enc"):
            axes.append(kv_entry(s.n))
        elif s.kind in ("mla_dense", "mla_moe"):
            axes.append({"c": st(s.n, ("batch", "kv_seq", "kv_lora")),
                         "kr": st(s.n, ("batch", "kv_seq", None))})
        elif s.kind == "dec":
            axes.append(dict(kv_entry(s.n),
                             xk=st(s.n, kv), xv=st(s.n, kv)))
        elif s.kind == "mamba":
            axes.append(mamba_ax(pre))
        elif s.kind == "zamba_group":
            axes.append({"mamba": mamba_ax(pre + (None,)),
                         "attn": kv_entry(s.n)})
        elif s.kind == "xlstm_group":
            axes.append({"m": {"C": pre + (None, "batch", "ssm_heads", None, None),
                               "n": pre + (None, "batch", "ssm_heads", None)},
                         "s": {k: pre + ("batch", "ssm_heads", None)
                               for k in ("h", "c", "n", "m")}})
    return axes


def _block_decode(kind, p, h, cache, pos, cfg, shared=None, rules=None,
                  layer=None):
    """Single-token decode for one block. h [B,1,D].

    ``cache`` is the block's cache with its K/V leaves (``_carried``) as
    the stage's whole stack when ``layer`` is given, and its recurrent
    state as this block's slice; returns (h, cache) of the same layout.
    """
    p = _maybe_dequant(p)
    if kind in ("dense", "moe", "enc"):
        hn = L.apply_norm(p["ln1"], h, cfg.norm)
        a, cache = L.gqa_decode(p["attn"], hn, cfg, cache, pos, layer)
        if cfg.parallel_block:
            h = h + a + L.apply_mlp(p["mlp"], hn, cfg, rules)
        else:
            h = h + a
            hn2 = L.apply_norm(p["ln2"], h, cfg.norm)
            if kind == "moe":
                m, _ = MOE.apply_moe(p["moe"], hn2, cfg)
            else:
                m = L.apply_mlp(p["mlp"], hn2, cfg, rules)
            h = h + m
    elif kind in ("mla_dense", "mla_moe"):
        hn = L.apply_norm(p["ln1"], h, cfg.norm)
        a, cache = L.mla_decode(p["attn"], hn, cfg, cache, pos, layer)
        h = h + a
        hn2 = L.apply_norm(p["ln2"], h, cfg.norm)
        if kind == "mla_moe":
            m, _ = MOE.apply_moe(p["moe"], hn2, cfg)
        else:
            m = L.apply_mlp(p["mlp"], hn2, cfg, rules)
        h = h + m
    elif kind == "dec":
        hn = L.apply_norm(p["ln1"], h, cfg.norm)
        kv_in = {k: cache[k] for k in cache if not k.startswith("x")}
        a, kv_out = L.gqa_decode(p["attn"], hn, cfg, kv_in, pos, layer)
        h = h + a
        hx = L.apply_norm(p["lnx"], h, cfg.norm)
        q = jnp.einsum("bsd,dhk->bshk", hx, p["xattn"]["wq"])
        xk = L.layer_slice(cache["xk"], layer)
        xv = L.layer_slice(cache["xv"], layer)
        o = L.decode_attention(q, xk, xv, jnp.full_like(pos, xk.shape[1] - 1))
        h = h + jnp.einsum("bshk,hkd->bsd", o, p["xattn"]["wo"])
        hn2 = L.apply_norm(p["ln2"], h, cfg.norm)
        h = h + L.apply_mlp(p["mlp"], hn2, cfg, rules)
        cache = dict(cache, **kv_out)
    elif kind == "mamba":
        hn = L.apply_norm(p["ln1"], h, cfg.norm)
        y, cache = SSM.mamba2_decode(p["mamba"], hn, cfg, cache)
        h = h + y
    elif kind == "zamba_group":
        new_m = []
        for i in range(cfg.shared_every):
            pm = jax.tree.map(lambda t: t[i], p["mambas"])
            ci = jax.tree.map(lambda t: t[i], cache["mamba"])
            hn = L.apply_norm(pm["ln1"], h, cfg.norm)
            y, ci = SSM.mamba2_decode(pm["mamba"], hn, cfg, ci)
            h = h + y
            new_m.append(ci)
        hn = L.apply_norm(shared["ln1"], h, cfg.norm)
        a, attn_cache = L.gqa_decode(shared["attn"], hn, cfg,
                                     cache["attn"], pos, layer)
        h = h + a
        hn = L.apply_norm(shared["ln2"], h, cfg.norm)
        h = h + L.apply_mlp(shared["mlp"], hn, cfg, rules)
        cache = {"mamba": jax.tree.map(lambda *xs: jnp.stack(xs), *new_m),
                 "attn": attn_cache}
    elif kind == "xlstm_group":
        order = [0, 1, 2, None, 3, 4]
        new_m = []
        s_state = cache["s"]
        for idx in order:
            if idx is None:
                hn = L.apply_norm(p["s"]["ln1"], h, cfg.norm)
                y, s_state = XL.slstm_decode(p["s"]["cell"], hn, cfg, s_state)
            else:
                pm = jax.tree.map(lambda t: t[idx], p["m"])
                ci = jax.tree.map(lambda t: t[idx], cache["m"])
                hn = L.apply_norm(pm["ln1"], h, cfg.norm)
                y, ci = XL.mlstm_decode(pm["cell"], hn, cfg, ci)
                new_m.append(ci)
            h = h + y
        cache = {"m": jax.tree.map(lambda *xs: jnp.stack(xs), *new_m),
                 "s": s_state}
    else:
        raise ValueError(kind)
    return h, cache


def _carried(kind, cache):
    """Keys of a stage's cache that the decode layer scan carries whole:
    those indexed by position, where a step writes one row per layer.
    Recurrent state (mamba, xLSTM, zamba's mamba part) is rewritten whole
    each step, so it stays per-layer ``xs``/``ys``."""
    if kind in ("mamba", "xlstm_group"):
        return ()
    if kind == "zamba_group":
        return ("attn",)
    return tuple(cache)


def decode_step(params, cfg: ModelConfig, tokens, pos, caches, rules=None):
    """tokens [B], pos [B] -> (logits [B,V], new caches).

    The cache layout is ``init_cache``'s. Per stage the layer scan carries
    the stacked K/V leaves (``_carried``: dense, moe, enc, dec, mla_* and
    zamba's shared attention) whole; each layer writes its new row at
    ``[layer, b, slot_b]`` in place and attends over ``cache[layer]``, so
    a step writes one row per layer and no layer slice is re-stacked.
    Params and the layer index are the scan's ``xs``; recurrent state goes
    in as ``xs`` and comes out as ``ys``.
    """
    params = {k: (_maybe_dequant(v) if k != "stages" else v)
              for k, v in params.items()}
    h = jnp.take(params["embed"], tokens[:, None], axis=0)
    if cfg.family == "audio":
        h = h + params["dec_pos"][pos][:, None].astype(h.dtype)
    stages = build_stages(cfg)
    new_caches = []
    for st, sp, cache in zip(stages, params["stages"], caches):
        if cfg.family == "audio" and st.kind == "enc":
            new_caches.append(cache)  # encoder is inactive during decode
            continue
        keys = _carried(st.kind, cache)
        kv = {k: cache[k] for k in keys}
        state = {k: t for k, t in cache.items() if k not in keys}

        def body(carry, xs, _kind=st.kind):
            hh, kv = carry
            pl, layer, state = xs
            hh, c = _block_decode(_kind, pl, hh, {**kv, **state}, pos, cfg,
                                  shared=params.get("shared"), rules=rules,
                                  layer=layer)
            return (hh, {k: c[k] for k in kv}), {k: c[k] for k in state}
        if st.n == 1:
            (h, kv), state = body((h, kv), (sp, None, state))
        else:
            (h, kv), state = jax.lax.scan(
                body, (h, kv), (sp, jnp.arange(st.n), state))
        new_caches.append({**kv, **state})
    h = L.apply_norm(params["final_norm"], h, cfg.norm)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (h[:, 0] @ head) * cfg.logit_scale
    if rules is not None:
        logits = constrain(logits, ("batch", "vocab"), rules)
    return logits, new_caches


def _pad_kv(kv, cache_len, window):
    """kv [..., S, H, hd] -> cache [..., W, H, hd] (ring layout for SWA)."""
    S = kv.shape[-3]
    if window and S >= window:
        tail = kv[..., S - window:, :, :]
        return jnp.roll(tail, S % window, axis=-3)
    W = min(cache_len, window) if window else cache_len
    pad = [(0, 0)] * kv.ndim
    pad[-3] = (0, W - S)
    return jnp.pad(kv, pad)


def assemble_caches(cfg: ModelConfig, kv_stacks, cache_len: int, seq_len: int):
    """Turn ``forward(collect_cache=True)`` outputs into decode caches."""
    W = cfg.sliding_window

    def kv_assemble(k, v):
        if cfg.kv_quant:
            from repro.models.layers import kv_quantize
            kq, ks = kv_quantize(k)
            vq, vs = kv_quantize(v)
            return {"k": _pad_kv(kq, cache_len, W),
                    "k_s": _pad_kv(ks, cache_len, W),
                    "v": _pad_kv(vq, cache_len, W),
                    "v_s": _pad_kv(vs, cache_len, W)}
        return {"k": _pad_kv(k, cache_len, W), "v": _pad_kv(v, cache_len, W)}

    caches = []
    for st, kvs in zip(build_stages(cfg), kv_stacks):
        if st.kind in ("dense", "moe", "enc"):
            caches.append(kv_assemble(kvs["k"], kvs["v"]))
        elif st.kind in ("mla_dense", "mla_moe"):
            caches.append({
                "c": _pad_kv(kvs["c"][..., None], cache_len, 0)[..., 0],
                "kr": _pad_kv(kvs["kr"][..., None], cache_len, 0)[..., 0]})
        elif st.kind == "dec":
            caches.append(dict(kv_assemble(kvs["k"], kvs["v"]),
                               xk=kvs["xk"], xv=kvs["xv"]))
        elif st.kind == "zamba_group":
            caches.append({"mamba": kvs["mamba"],
                           "attn": kv_assemble(kvs["attn"]["k"],
                                               kvs["attn"]["v"])})
        else:  # mamba / xlstm_group: states pass through unchanged
            caches.append(kvs)
    return caches


def prefill(params, cfg: ModelConfig, batch, cache_len: int, rules=None):
    """Full-sequence forward + populated decode caches.

    Returns (logits [B,S,V], caches).  This is what ``prefill_*`` dry-run
    cells lower and what the serving engine records (the paper's per-layer
    "recording" granularity corresponds to per-stage executables; we record
    at step granularity: prefill / decode)."""
    out = forward(params, cfg, batch, rules=rules, collect_cache=True)
    logits, _aux, kv_stacks = out
    S = batch["tokens"].shape[1]
    if cfg.family == "vlm" and "image_embeds" in batch:
        S += batch["image_embeds"].shape[1]   # image prefix lives in cache
    caches = assemble_caches(cfg, kv_stacks, max(cache_len, S), S)
    return logits, caches


__all__ = ["ModelConfig", "StageDef", "build_stages", "model_schema",
           "init_params", "abstract_params", "param_axes", "forward",
           "decode_step", "init_cache", "prefill", "assemble_caches"]
