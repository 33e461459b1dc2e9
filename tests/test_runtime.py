"""Fault tolerance: checkpoint/restore, elastic re-mesh, straggler monitor,
gradient compression, data pipeline determinism, serving engine e2e."""
import os
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_shrink
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.models import model as M
from repro.runtime.checkpoint import CheckpointStore
from repro.runtime.straggler import DispatchMonitor
from repro.training.grad_compress import make_ef_int8_transform
from repro.training.optimizer import AdamWConfig, init_opt_state
from repro.training import steps as ST

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ------------------------------------------------------------ checkpoint ----
def test_checkpoint_roundtrip_and_dedup():
    cfg = smoke_shrink(get_config("qwen2.5-3b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    state = init_opt_state(params)
    with tempfile.TemporaryDirectory() as d:
        store = CheckpointStore(d)
        store.save(state, step=1, extra_meta={"cursor_step": 5})
        w1 = store.stats["chunks_written"]
        # unchanged state re-saved: all chunks dedup
        store.save(state, step=2)
        assert store.stats["chunks_written"] == w1
        assert store.stats["chunks_deduped"] >= w1
        restored, manifest = store.restore(state)
        assert manifest["extra"].get("cursor_step", 5) == 5 or \
            manifest["step"] == 2
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # gc keeps the latest
        store.gc(keep_last=1)
        assert store.latest_step() == 2
        store.restore(state, step=2)


def test_checkpoint_async_save():
    cfg = smoke_shrink(get_config("xlstm-350m"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        store = CheckpointStore(d)
        t = store.async_save({"params": params}, step=3)
        store.wait()
        assert store.latest_step() == 3


def test_train_resume_equals_continuous():
    """Fault-tolerance invariant: crash+restore at step k gives the same
    final state as an uninterrupted run (data cursor included)."""
    cfg = smoke_shrink(get_config("qwen2.5-3b"), num_layers=1, d_model=32,
                       d_ff=64, vocab_size=64)
    opt = AdamWConfig(warmup_steps=2, decay_steps=8)
    step_fn = jax.jit(ST.make_train_step(cfg, None, opt, remat="none"))

    def run(n_steps, state=None, data=None):
        data = data or SyntheticLM(cfg.vocab_size, 2, 16)
        if state is None:
            state = init_opt_state(M.init_params(cfg, jax.random.PRNGKey(0)))
        for _ in range(n_steps):
            batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
            state, metrics = step_fn(state, batch)
        return state, data, metrics

    # continuous 6 steps
    s_cont, _, m_cont = run(6)
    # 3 steps -> checkpoint -> restore -> 3 more
    s3, data3, _ = run(3)
    with tempfile.TemporaryDirectory() as d:
        store = CheckpointStore(d)
        store.save(s3, step=3, extra_meta=data3.meta())
        restored, manifest = store.restore(s3)
        data_r = SyntheticLM(cfg.vocab_size, 2, 16)
        data_r.restore(manifest["extra"])
        s_res, _, m_res = run(3, state=jax.tree.map(jnp.asarray, restored),
                              data=data_r)
    for a, b in zip(jax.tree.leaves(s_cont), jax.tree.leaves(s_res)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


def test_elastic_restore_subprocess():
    """Save on 1 device, restore + keep training on 8 devices (new mesh)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, sys.argv[1])
from repro.configs import get_config, smoke_shrink
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.runtime.checkpoint import CheckpointStore
from repro.runtime.elastic import make_elastic_mesh, reshard_state
from repro.training import steps as ST
from repro.training.optimizer import AdamWConfig
from repro.sharding import rules_for
cfg = smoke_shrink(get_config("qwen2.5-3b"), num_layers=1, d_model=32,
                   d_ff=64, vocab_size=64)
store = CheckpointStore(sys.argv[2])
state_np, manifest = store.restore(ST.abstract_train_state(cfg))
mesh = make_elastic_mesh(prefer_model=2)   # 4x2 mesh on 8 devices
state = reshard_state(state_np, ST.train_state_axes(cfg), mesh)
rules = rules_for("train", mesh.axis_names)
step_fn = ST.make_train_step(cfg, rules, AdamWConfig(warmup_steps=1, decay_steps=4))
batch = {"tokens": jnp.ones((4, 16), jnp.int32),
         "labels": jnp.ones((4, 16), jnp.int32)}
with jax.set_mesh(mesh):
    state, metrics = jax.jit(step_fn, donate_argnums=(0,))(state, batch)
assert np.isfinite(float(metrics["loss"]))
print("ELASTIC_OK", float(metrics["loss"]))
"""
    cfg = smoke_shrink(get_config("qwen2.5-3b"), num_layers=1, d_model=32,
                       d_ff=64, vocab_size=64)
    state = init_opt_state(M.init_params(cfg, jax.random.PRNGKey(0)))
    with tempfile.TemporaryDirectory() as d:
        CheckpointStore(d).save(state, step=1)
        out = subprocess.run([sys.executable, "-c", code, SRC, d],
                             capture_output=True, text=True, timeout=300)
        assert "ELASTIC_OK" in out.stdout, out.stderr[-2000:]


# ------------------------------------------------------------- straggler ----
def test_straggler_monitor_flags_outliers():
    mon = DispatchMonitor(factor=3.0, min_samples=3)
    for _ in range(10):
        assert not mon.observe("s0", 0.010)
    assert mon.observe("s0", 0.500)          # 50x the EWMA
    assert mon.flagged["s0"] == 1
    backup_called = []
    mon2 = DispatchMonitor(factor=2.0, min_samples=1)
    mon2.observe("s1", 0.001)
    mon2.observe("s1", 0.001)
    out = mon2.timed("s1", lambda: time.sleep(0.05) or "slow",
                     backup=lambda: backup_called.append(1) or "backup")
    assert out == "backup" and backup_called


# ------------------------------------------------------- grad compression ----
def test_ef_int8_grad_transform_preserves_training():
    """Error feedback: compressed updates accumulate the quantization
    residual, so the averaged update converges to the true gradient."""
    tf = make_ef_int8_transform()
    g = {"w": jnp.full((128,), 0.001, jnp.float32)}
    state = {}
    total = jnp.zeros((128,))
    for _ in range(64):
        dg, state = tf(g, state)
        total = total + dg["w"]
    np.testing.assert_allclose(total / 64, g["w"], rtol=0.05)


def test_compressed_psum_subprocess():
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, sys.argv[1])
from repro.training.grad_compress import compressed_psum
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
x = jnp.linspace(-1.0, 1.0, 4096).reshape(64, 64)
with jax.set_mesh(mesh):
    got = compressed_psum(x, mesh, "data")
want = x * 8
err = float(jnp.max(jnp.abs(got - want))) / float(jnp.max(jnp.abs(want)))
assert err < 0.03, err
print("PSUM_OK", err)
"""
    out = subprocess.run([sys.executable, "-c", code, SRC],
                         capture_output=True, text=True, timeout=300)
    assert "PSUM_OK" in out.stdout, out.stderr[-2000:]


# ---------------------------------------------------------------- data ----
def test_data_cursor_determinism():
    d1 = SyntheticLM(100, 2, 8, seed=3)
    b1 = [d1.next_batch() for _ in range(3)]
    d2 = SyntheticLM(100, 2, 8, seed=3)
    d2.restore({"cursor_step": 1, "cursor_seed": 3})
    b2 = d2.next_batch()
    np.testing.assert_array_equal(b1[1]["tokens"], b2["tokens"])


def test_prefetcher_steal():
    d = SyntheticLM(100, 2, 8)
    pf = Prefetcher(d, depth=2)
    b = pf.next_batch()
    assert b["tokens"].shape == (2, 8)
    time.sleep(0.05)
    stolen = pf.steal()
    assert stolen is None or stolen["tokens"].shape == (2, 8)
    pf.close()


# ------------------------------------------------------------- serving ----
def test_engine_speculative_matches_sequential():
    """Speculative continuation must produce exactly the tokens the
    non-speculative engine produces (rollback correctness end-to-end)."""
    from repro.launch.serve import main as serve_main
    outs_spec, eng_spec = serve_main(["--arch", "qwen2.5-3b", "--requests",
                                      "5", "--max-new", "12"])
    outs_sync, eng_sync = serve_main(["--arch", "qwen2.5-3b", "--requests",
                                      "5", "--max-new", "12",
                                      "--no-speculate"])
    assert outs_spec == outs_sync
    assert eng_spec.stats["spec_blocks"] >= 0  # speculation may engage
    for r in outs_spec.values():
        assert 0 < len(r) <= 12
