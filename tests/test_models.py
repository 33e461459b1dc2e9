"""Per-arch smoke tests: reduced same-family configs, one forward/train step
on CPU asserting output shapes + no NaNs; prefill+decode == full forward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, smoke_shrink
from repro.models import model as M
from repro.training import steps as ST
from repro.training.optimizer import AdamWConfig, init_opt_state

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=32, with_labels=True, key=jax.random.PRNGKey(1)):
    b = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if with_labels:
        b["labels"] = jnp.roll(b["tokens"], -1, axis=1)
    if cfg.family == "audio":
        b["frames"] = jax.random.normal(
            key, (B, cfg.encdec.encoder_seq, cfg.d_model)).astype(jnp.bfloat16)
    if cfg.family == "vlm":
        b["image_embeds"] = jax.random.normal(
            key, (B, cfg.vlm.num_image_tokens, cfg.d_model)).astype(jnp.bfloat16)
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_smoke(arch):
    cfg = smoke_shrink(get_config(arch))
    params = M.init_params(cfg, KEY)
    batch = _batch(cfg)
    logits, aux = M.forward(params, cfg, batch)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = smoke_shrink(get_config(arch))
    params = M.init_params(cfg, KEY)
    state = init_opt_state(params)
    step = jax.jit(ST.make_train_step(
        cfg, None, AdamWConfig(warmup_steps=1, decay_steps=10), remat="none"))
    state, metrics = step(state, _batch(cfg))
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(state["step"]) == 1


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = smoke_shrink(get_config(arch))
    if cfg.moe is not None:  # disable capacity drops for exactness
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=100.0))
    params = M.init_params(cfg, KEY)
    B, S = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S + 1), 0,
                              cfg.vocab_size)
    full = _batch(cfg, with_labels=False)
    full["tokens"] = toks
    pre = dict(full)
    pre["tokens"] = toks[:, :S]
    logits_full, _ = M.forward(params, cfg, full)
    ref = logits_full[:, S].astype(jnp.float32)
    _, caches = M.prefill(params, cfg, pre, cache_len=64)
    n_img = cfg.vlm.num_image_tokens if cfg.family == "vlm" else 0
    pos = jnp.full((B,), S + n_img, jnp.int32)
    got, _ = M.decode_step(params, cfg, toks[:, S], pos, caches)
    got = got.astype(jnp.float32)
    rel = float(jnp.max(jnp.abs(ref - got))) / (float(jnp.max(jnp.abs(ref))) + 1e-9)
    assert rel < 0.05, f"{arch}: prefill+decode diverges from forward ({rel})"


def _slot_caches(cfg, per_slot):
    """Concatenate one-slot caches along each leaf's batch axis."""
    return jax.tree.map(
        lambda ax, *ts: jnp.concatenate(ts, axis=ax.index("batch")),
        M.cache_axes(cfg), *per_slot, is_leaf=lambda x: isinstance(x, tuple))


@pytest.mark.parametrize("arch,kv_quant", [
    ("qwen2.5-3b", False), ("starcoder2-7b", False), ("mixtral-8x22b", False),
    ("qwen2-72b", True), ("deepseek-v2-lite-16b", False),
    ("whisper-large-v3", False)])
def test_fused_decode_writes_only_its_rows(arch, kv_quant):
    """One fused block of K steps over slots at mixed positions (under SWA
    one slot crosses the window inside the block, one has wrapped): every
    cache row but the written [layer, slot, pos..pos+K-1] rows is
    bit-identical to its input, and the written rows match what prefill of
    the extended sequence puts there."""
    cfg = smoke_shrink(get_config(arch))
    if cfg.moe is not None:  # disable capacity drops for exactness
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=100.0))
    cfg = dataclasses.replace(cfg, kv_quant=kv_quant)
    params = M.init_params(cfg, KEY)
    K, cache_len, lens = 4, 64, (13, 30, 45)
    toks = jax.random.randint(jax.random.PRNGKey(7),
                              (len(lens), max(lens) + 1), 0, cfg.vocab_size)
    jit_prefill = jax.jit(M.prefill, static_argnums=(1, 3))

    def prefill(b, seq):
        batch = {"tokens": seq[None]}
        if cfg.family == "audio":
            batch["frames"] = jax.random.normal(
                jax.random.PRNGKey(10 + b),
                (1, cfg.encdec.encoder_seq, cfg.d_model)).astype(jnp.bfloat16)
        return jit_prefill(params, cfg, batch, cache_len)[1]

    caches = _slot_caches(cfg, [prefill(b, toks[b, :n])
                                for b, n in enumerate(lens)])
    first = jnp.array([toks[b, n] for b, n in enumerate(lens)])
    pos = jnp.array(lens, jnp.int32)
    fused = jax.jit(ST.make_fused_decode_step(cfg, None, k=K, eos_id=-1))
    out, new = fused(params, first, pos, caches)
    fed = jnp.concatenate([first[:, None], out["tokens"][:, :K - 1]], 1)
    refs = [prefill(b, jnp.concatenate([toks[b, :n], fed[b]]))
            for b, n in enumerate(lens)]

    kinds = [st.kind for st in M.build_stages(cfg)]
    flat = jax.tree_util.tree_flatten_with_path(
        M.cache_axes(cfg), is_leaf=lambda x: isinstance(x, tuple))[0]
    for (path, ax), old, got, *ref in zip(
            flat, *(jax.tree.leaves(t) for t in (caches, new, *refs))):
        old, got = np.asarray(old), np.asarray(got)
        name = jax.tree_util.keystr(path)
        if kinds[path[0].idx] == "enc" or "'x" in name:
            assert old.tobytes() == got.tobytes(), name  # read-only
            continue
        b_ax, s_ax = ax.index("batch"), ax.index("kv_seq")
        W = old.shape[s_ax]
        written = np.zeros(old.shape, bool)
        for b, n in enumerate(lens):
            slots = [p % W if cfg.sliding_window else p
                     for p in range(n, n + K)]
            at = [slice(None)] * old.ndim
            at[b_ax], at[s_ax] = b, slots
            written[tuple(at)] = True
            want = np.take(np.asarray(ref[b]), 0, axis=b_ax).astype(np.float32)
            have = np.take(got, b, axis=b_ax).astype(np.float32)
            want, have = (np.take(t, slots, axis=s_ax - 1)
                          for t in (want, have))
            rel = np.abs(have - want).max() / (np.abs(want).max() + 1e-9)
            assert rel < 0.05, (name, b, rel)
        assert old[~written].tobytes() == got[~written].tobytes(), name


def test_param_counts_match_analytic():
    """Analytic param_count ~ actual materialized count (within 5%)."""
    for arch in ("qwen2.5-3b", "mixtral-8x22b", "xlstm-350m"):
        cfg = smoke_shrink(get_config(arch))
        params = M.init_params(cfg, KEY)
        actual = sum(x.size for x in jax.tree.leaves(params))
        approx = cfg.param_count()
        assert abs(actual - approx) / actual < 0.25, (arch, actual, approx)


def test_full_configs_are_exact():
    """Full configs match the assignment table (spot checks)."""
    q = get_config("qwen2-72b")
    assert (q.num_layers, q.d_model, q.num_heads, q.num_kv_heads,
            q.d_ff, q.vocab_size) == (80, 8192, 64, 8, 29568, 152064)
    m = get_config("mixtral-8x22b")
    assert (m.num_layers, m.moe.num_experts, m.moe.top_k) == (56, 8, 2)
    d = get_config("deepseek-v2-lite-16b")
    assert (d.mla.kv_lora_rank, d.moe.num_experts, d.moe.top_k) == (512, 64, 6)
    x = get_config("xlstm-350m")
    assert x.xlstm.slstm_at == (3, 9, 15, 21)
    z = get_config("zamba2-1.2b")
    assert z.ssm.state_dim == 64 and z.shared_every == 6


def test_swa_ring_cache_decode():
    """SWA decode with ring cache matches full-attention-with-window ref."""
    cfg = smoke_shrink(get_config("starcoder2-7b"))
    assert cfg.sliding_window == 32
    params = M.init_params(cfg, KEY)
    S = 48  # > window: ring wraps
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, S + 1), 0,
                              cfg.vocab_size)
    logits_full, _ = M.forward(params, cfg, {"tokens": toks})
    ref = logits_full[:, S].astype(jnp.float32)
    _, caches = M.prefill(params, cfg, {"tokens": toks[:, :S]}, cache_len=64)
    got, _ = M.decode_step(params, cfg, toks[:, S],
                           jnp.array([S], jnp.int32), caches)
    rel = float(jnp.max(jnp.abs(ref - got.astype(jnp.float32)))) / \
        (float(jnp.max(jnp.abs(ref))) + 1e-9)
    assert rel < 0.05


def test_kv_quant_decode_close():
    """int8 KV cache (per-token/head scales) stays within 10% of bf16."""
    import dataclasses as dc
    cfg = smoke_shrink(get_config("qwen2-72b"))
    cfgq = dc.replace(cfg, kv_quant=True)
    params = M.init_params(cfg, KEY)
    B, S = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, S + 1), 0,
                              cfg.vocab_size)
    ref_logits, _ = M.forward(params, cfg, {"tokens": toks})
    ref = ref_logits[:, S].astype(jnp.float32)
    _, caches = M.prefill(params, cfgq, {"tokens": toks[:, :S]}, 64)
    got, _ = M.decode_step(params, cfgq, toks[:, S],
                           jnp.full((B,), S, jnp.int32), caches)
    rel = float(jnp.max(jnp.abs(ref - got.astype(jnp.float32)))) / \
        (float(jnp.max(jnp.abs(ref))) + 1e-9)
    assert rel < 0.1, rel


def test_int8_weight_quant_decode_close():
    """int8 weight quantization (per-channel scales) within 15%."""
    from repro.serving.quant import quantize_params
    cfg = smoke_shrink(get_config("qwen2.5-3b"))
    params = M.init_params(cfg, KEY)
    pq = quantize_params(params)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(6), (B, S), 0,
                              cfg.vocab_size)
    ref, _ = M.forward(params, cfg, {"tokens": toks})
    got, _ = M.forward(pq, cfg, {"tokens": toks})
    ref = ref.astype(jnp.float32)
    got = got.astype(jnp.float32)
    rel = float(jnp.max(jnp.abs(ref - got))) / (float(jnp.max(jnp.abs(ref))) + 1e-9)
    assert rel < 0.15, rel
