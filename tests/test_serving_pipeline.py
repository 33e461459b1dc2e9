"""Async decode pipeline: frontier-only host syncs, speculation rollback
(forced EOS mid-pipeline), batched prefill equivalence, and the replayer's
argument validation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_shrink
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.serving.engine import Engine, cache_batch_axes_for
from repro.sharding import rules_for
from repro.training import steps as ST

BLOCK_K = 4
CACHE_LEN = 96


def _cfg(arch="cody-mnist"):
    return smoke_shrink(get_config(arch))


def _make_engine(cfg, params, *, speculate, depth, decode_wrap=None,
                 batched=True, n_slots=2, netem=None, tracer=None):
    rules = rules_for("serve", make_host_mesh(model=1).axis_names)
    prefill = jax.jit(ST.make_prefill_step(cfg, rules, CACHE_LEN))
    batched_prefill = jax.jit(
        ST.make_batched_prefill_step(cfg, rules, CACHE_LEN)) \
        if batched else None
    decode = jax.jit(
        ST.make_fused_decode_step(cfg, rules, k=BLOCK_K, eos_id=2),
        donate_argnums=(3,))
    if decode_wrap is not None:
        decode = decode_wrap(decode)
    return Engine(params, prefill, decode, n_slots=n_slots,
                  cache_len=CACHE_LEN, block_k=BLOCK_K, eos_id=2,
                  init_caches_fn=lambda: M.init_cache(cfg, n_slots,
                                                      CACHE_LEN),
                  cache_batch_axes=cache_batch_axes_for(cfg), netem=netem,
                  speculate=speculate, pipeline_depth=depth,
                  batched_prefill_fn=batched_prefill, tracer=tracer)


def _submit_workload(eng, cfg, n=5, max_new=14, seed=7):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        plen = int(rng.integers(4, 12))
        eng.submit(list(rng.integers(3, cfg.vocab_size, plen)), max_new)


@pytest.mark.parametrize("arch", ["cody-mnist", "qwen2.5-3b"])
def test_pipeline_bit_exact_vs_sync(arch):
    """Acceptance: speculative pipelined and synchronous modes produce
    identical token streams after validate()."""
    cfg = _cfg(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng_sync = _make_engine(cfg, params, speculate=False, depth=1)
    _submit_workload(eng_sync, cfg)
    outs_sync = eng_sync.run()
    eng_spec = _make_engine(cfg, params, speculate=True, depth=4)
    _submit_workload(eng_spec, cfg)
    outs_spec = eng_spec.run()
    assert outs_sync == outs_spec
    assert eng_spec.stats["spec_blocks"] > 0
    assert eng_spec.stats["host_syncs"] < eng_sync.stats["host_syncs"]
    # every request validated to its full tail at the final frontier
    for req in eng_spec.requests.values():
        assert req.done and req.committed == len(req.generated)


def _forced_eos_wrap(trigger_pos, eos_id=2):
    """Wrap a fused decode fn so slot 0 emits EOS once its input position
    reaches ``trigger_pos``.  Pure function of the block inputs => fires at
    the same logical block in speculative, synchronous, and re-executed
    runs; stays device-side (no host sync in the wrapper)."""
    def wrap(base):
        def fn(params, toks, pos, caches):
            out, caches = base(params, toks, pos, caches)
            trig = pos[0] >= trigger_pos
            tokens = out["tokens"].at[0, -1].set(
                jnp.where(trig, eos_id, out["tokens"][0, -1]))
            done = out["done"].at[0].set(out["done"][0] | trig)
            return {"tokens": tokens, "pos": out["pos"], "done": done}, \
                caches
        return fn
    return wrap


def test_forced_eos_mispredict_rolls_back_to_sync_stream():
    """Satellite: inject a forced EOS mid-pipeline; the mispredict path
    must roll back and still produce the synchronous token stream."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    # prompts are 6 tokens; trigger deep enough that the EOS lands inside a
    # speculative pipeline window (after warm-up sync blocks)
    wrap = _forced_eos_wrap(trigger_pos=6 + 4 * BLOCK_K)
    runs = {}
    for mode, (spec, depth) in {"sync": (False, 1),
                                "spec": (True, 4)}.items():
        eng = _make_engine(cfg, params, speculate=spec, depth=depth,
                           decode_wrap=wrap)
        rng = np.random.default_rng(3)
        for _ in range(4):
            eng.submit(list(rng.integers(3, cfg.vocab_size, 6)), 28)
        runs[mode] = (eng.run(), eng)
    outs_sync, _ = runs["sync"]
    outs_spec, eng_spec = runs["spec"]
    assert eng_spec.stats["mispredicts"] >= 1
    assert outs_sync == outs_spec          # token-for-token, incl. tails
    # the forced EOS really ended a request early
    assert any(r.generated[-1] == 2 and len(r.generated) < 28
               for r in eng_spec.requests.values())


def test_mid_pipeline_admission_is_sound():
    """Regression: submitting a request while speculative blocks are in
    flight must drain the frontier before admission — the device chain
    re-seed reads host metastate, which is stale mid-pipeline."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(3, cfg.vocab_size, 7)) for _ in range(3)]
    outs = {}
    for mode, (spec, depth) in {"sync": (False, 1),
                                "spec": (True, 4)}.items():
        eng = _make_engine(cfg, params, speculate=spec, depth=depth,
                           n_slots=4)
        for p in prompts[:2]:
            eng.submit(p, 24)
        for _ in range(6):          # deep enough that blocks are in flight
            eng.step_block()
        eng.submit(prompts[2], 24)  # mid-pipeline admission
        outs[mode] = eng.run()
    assert outs["sync"] == outs["spec"]


def test_deeper_pipeline_fewer_host_syncs():
    """Acceptance: host-sync count drops ~1/validate_every with depth."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    outs, syncs = {}, {}
    for depth in (1, 4):
        eng = _make_engine(cfg, params, speculate=True, depth=depth)
        _submit_workload(eng, cfg, n=4, max_new=16)
        outs[depth] = eng.run()
        syncs[depth] = eng.stats["host_syncs"]
    assert outs[1] == outs[4]
    assert syncs[4] < syncs[1]


def test_batched_prefill_matches_per_request():
    """Grouped right-padded admission must not change any token: compare
    against the exact-shape per-request path on mixed prompt lengths."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    outs = {}
    for batched in (False, True):
        eng = _make_engine(cfg, params, speculate=False, depth=1,
                           batched=batched, n_slots=3)
        _submit_workload(eng, cfg, n=6, max_new=10, seed=11)
        outs[batched] = eng.run()
        if batched:
            # 3 slots admitted as a group -> fewer dispatches than requests
            assert eng.stats["prefill_dispatches"] < 6
    assert outs[False] == outs[True]


@pytest.mark.parametrize("max_new", [14, 3], ids=["long", "short"])
def test_request_stamps_are_ordered(max_new):
    """Every request carries submit <= admit <= prefilled <= first commit;
    it retires after its prefill, and after its first commit unless it
    ended inside the drain that made that commit (``Request``)."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = _make_engine(cfg, params, speculate=True, depth=2)
    _submit_workload(eng, cfg, n=5, max_new=max_new)
    eng.run()
    for r in eng.requests.values():
        assert r.done and not r.failed
        assert 0 < r.submit_t <= r.admit_t <= r.prefilled_t <= r.first_t
        assert r.prefilled_t <= r.finish_t
        if max_new > 2 * BLOCK_K + 1:      # outlives its first drain
            assert r.first_t <= r.finish_t


@pytest.mark.parametrize("batched", [True, False],
                         ids=["batched", "per_request"])
def test_admission_spans_and_counters(batched):
    """``admission_drains`` counts the drains admission forced, which are
    the ``frontier.drain`` spans inside ``executor.admit``; each prefill
    dispatch is a ``prefill.dispatch`` span inside an admission, and
    ``host_syncs`` counts only the frontier's drains and sync blocks.
    Spans nest as documented."""
    from repro.obs import Tracer
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    tr = Tracer(annotate=True)
    eng = _make_engine(cfg, params, speculate=True, depth=4, n_slots=3,
                       batched=batched, tracer=tr)
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(3, cfg.vocab_size, 7)) for _ in range(6)]
    for p in prompts[:2]:
        eng.submit(p, 24)
    for _ in range(6):              # blocks in flight, then admissions
        eng.step_block()
    for p in prompts[2:]:
        eng.submit(p, 24)
    eng.run()
    st = eng.stats
    spans = tr.spans("serve.stream0")
    by = lambda name: [s for s in spans if s["name"] == name]
    within = lambda c, p: (p["ts"] <= c["ts"]
                           and c["ts"] + c["dur"] <= p["ts"] + p["dur"])
    inside = lambda name, parents: [c for c in by(name)
                                    if any(within(c, p) for p in parents)]
    admits = by("executor.admit")
    assert sum(a["args"]["n"] for a in admits) == st["admitted"] == 6
    assert st["admission_drains"] >= 1
    assert len(inside("frontier.drain", admits)) == st["admission_drains"]
    assert st["prefill_dispatches"] == len(by("prefill.dispatch"))
    assert len(inside("prefill.dispatch", admits)) \
        == st["prefill_dispatches"]
    assert len(inside("executor.scatter", by("prefill.dispatch"))) \
        == len(by("executor.scatter")) == st["prefill_dispatches"]
    drains = by("frontier.drain")
    assert len(drains) == st["host_syncs"] - st["sync_blocks"]
    waits = inside("frontier.wait", drains)
    assert len(waits) == len(by("frontier.wait")) \
        == len(inside("frontier.apply", drains)) \
        == st["validated_blocks"] + st["mispredicts"]
    assert len(inside("frontier.commit", drains)) == len(drains)
    names = {e["name"] for e in tr.events}
    assert not names & {"host_sync", "request.done"}


def test_replayer_validates_args_and_dispatches_on_avals():
    """Satellite: execute() rejects wrong shapes/dtypes with a clear error
    (not an XLA crash) and dispatches between same-name recordings on the
    argument avals."""
    from repro.core.recorder import record
    from repro.core.replay import ReplayArgumentError, Replayer

    key = b"k"
    fn = lambda x: x * 2.0
    rp = Replayer(key=key)
    for n in (4, 8):
        rec = record("double", fn,
                     (jax.ShapeDtypeStruct((n,), jnp.float32),))
        rec.sign_with(key)
        rp.load(rec.to_bytes(), name="double")
    # aval dispatch: both shapes execute through one logical name
    np.testing.assert_allclose(
        np.asarray(rp.execute("double", jnp.ones(4, jnp.float32))), 2.0)
    np.testing.assert_allclose(
        np.asarray(rp.execute("double", jnp.ones(8, jnp.float32))), 2.0)
    with pytest.raises(ReplayArgumentError) as ei:
        rp.execute("double", jnp.ones(5, jnp.float32))
    assert "float32[5]" in str(ei.value) and "recorded" in str(ei.value)
    with pytest.raises(ReplayArgumentError):
        rp.execute("double", jnp.ones(4, jnp.int32))   # dtype mismatch
    # warm path executes each variant once without error
    before = rp.stats["executions"]
    rp.warm("double")
    assert rp.stats["executions"] == before + 2


def test_replayer_dispatches_on_dtype_when_shapes_collide():
    """Satellite: two recordings of one workload sharing a SHAPE but
    differing in dtype must occupy distinct executable-cache entries —
    the aval signature includes the dtype, so dispatch picks the right
    executable and the error message names the near-miss."""
    from repro.core.recorder import record
    from repro.core.replay import ReplayArgumentError, Replayer

    key = b"k"
    rp = Replayer(key=key)
    for dt, scale in ((jnp.float32, 2.0), (jnp.int32, 3)):
        rec = record("scale", lambda x, scale=scale: x * scale,
                     (jax.ShapeDtypeStruct((4,), dt),))
        rec.sign_with(key)
        rp.load(rec.to_bytes(), name="scale")
    # same shape, different dtype -> different executable, right result
    np.testing.assert_allclose(
        np.asarray(rp.execute("scale", jnp.ones(4, jnp.float32))), 2.0)
    np.testing.assert_array_equal(
        np.asarray(rp.execute("scale", jnp.ones(4, jnp.int32))), 3)
    # a third dtype misses BOTH variants; the message points at the dtype
    # (the first differing leaf), not the shape
    with pytest.raises(ReplayArgumentError) as ei:
        rp.execute("scale", jnp.ones(4, jnp.float16))
    msg = str(ei.value)
    assert "float16[4]" in msg                      # what the caller sent
    assert "recorded" in msg and "first mismatch at leaf 0" in msg
    assert "float32[4]" in msg and "int32[4]" in msg  # both near-misses
