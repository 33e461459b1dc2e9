"""The Qwen2 family on the program under test.

``program_config`` turns a configuration file into the program's
``ModelConfig``; ``program_params`` lays the reference's seeded weights
out as the program's parameter tree (a reshape, no arithmetic), and
checks the tree against the program's own abstract parameters.
``served_kv`` reads one slot's keys and values out of the engine's
caches.  ``int8_control`` reads the program's own int8 weight path
(``repro.serving.quant``) switched on, over whole sequences: a second,
milder lower precision beside the check's float8 control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.models.config import ModelConfig
from repro.serving.quant import quantize_params


def program_config(c: dict) -> ModelConfig:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return ModelConfig(
        name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
        d_model=d, num_heads=h, num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or d // h, d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], max_seq=c["max_position_embeddings"],
        attention="gqa", rope_theta=float(c["rope_theta"]), qkv_bias=True,
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["torch_dtype"])


def _layout(cfg: ModelConfig, w: dict) -> dict:
    L, D, H, Hkv, hd = (cfg.num_layers, cfg.d_model, cfg.num_heads,
                        cfg.num_kv_heads, cfg.hd())
    block = {
        "ln1": {"scale": w["ln1"]},
        "attn": {"wq": w["wq"].reshape(L, D, H, hd),
                 "wk": w["wk"].reshape(L, D, Hkv, hd),
                 "wv": w["wv"].reshape(L, D, Hkv, hd),
                 "wo": w["wo"].reshape(L, H, hd, D),
                 "bq": w["bq"].reshape(L, H, hd),
                 "bk": w["bk"].reshape(L, Hkv, hd),
                 "bv": w["bv"].reshape(L, Hkv, hd)},
        "ln2": {"scale": w["ln2"]},
        "mlp": {"w1": w["w_gate"], "w3": w["w_up"], "w2": w["w_down"]},
    }
    if L == 1:
        block = jax.tree.map(lambda a: a[0], block)
    p = {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
         "stages": [block]}
    if "lm_head" in w:
        p["lm_head"] = w["lm_head"]
    return p


def program_params(cfg: ModelConfig, ref, c: dict, seed: int,
                   shardings) -> dict:
    """The program's parameter tree, made on the device in one call."""
    def make(key):
        return _layout(cfg, ref.draw(c, key))
    key = ref.seed_key(seed)
    want, got = M.abstract_params(cfg), jax.eval_shape(make, key)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError("reference weights do not lay out as the "
                         f"program's parameters of {cfg.name}")
    return jax.jit(make, out_shardings=shardings)(key)


def served_kv(caches, slot: int, n: int) -> tuple:
    """Keys and values of positions [0, n) in ``slot`` of the program's
    slot caches, as float32 [L, n, kv heads x head size]."""
    kv = caches[0]
    out = []
    for name in ("k", "v"):
        x = kv[name]
        x = x[:, slot, :n] if x.ndim == 5 else x[None, slot, :n]
        x = np.asarray(x, np.float32)
        out.append(x.reshape(x.shape[0], n, -1))
    return tuple(out)


def int8_control(cfg: ModelConfig, ref, c: dict, seed: int, samples):
    """The program's forward with its int8 weights on, over
    each sample's tokens.  Per sample: the greedy choice at every
    position, the logits at the last prompt position, and the keys and
    values of the sample's positions (float32, as ``served_kv``)."""
    qparams = jax.jit(lambda key: quantize_params(
        _layout(cfg, ref.draw(c, key))))(ref.seed_key(seed))

    @jax.jit
    def fwd(p, t):
        logits, _aux, kvs = M.forward(p, cfg, {"tokens": t[None]},
                                      collect_cache=True)
        return jnp.argmax(logits[0], -1).astype(jnp.int32), logits[0], \
            kvs[0]
    out = []
    for s in samples:
        choice, logits, kv = fwd(qparams, jnp.asarray(s.tokens))
        kv = [np.asarray(kv[name], np.float32) for name in ("k", "v")]
        kv = [x.reshape((1,) * (5 - x.ndim) + x.shape)[:, 0, :s.n]
              for x in kv]
        out.append((np.asarray(choice), np.asarray(
            logits[s.prompt_len - 1], np.float32),
            tuple(x.reshape(x.shape[0], s.n, -1) for x in kv)))
    return out
