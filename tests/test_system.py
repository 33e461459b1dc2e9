"""End-to-end system tests: the paper's full story on this machine —
record in the 'cloud' role, replay in the 'TEE' role, serve from
recordings, plus a miniature multi-device dry-run (subprocess)."""
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_shrink
from repro.models import model as M

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_record_then_replay_inference_end_to_end():
    """Record prefill+decode for a model, replay on NEW inputs, and check
    the replayed tokens equal direct jit execution (the paper's replay
    correctness: same stimuli -> same compute on new data)."""
    from repro.launch.record import main as record_main
    from repro.core.replay import Replayer
    from repro.training import steps as ST
    from repro.sharding import rules_for
    from repro.launch.mesh import make_host_mesh

    cfg = smoke_shrink(get_config("qwen2.5-3b"))
    with tempfile.TemporaryDirectory() as d:
        record_main(["--arch", "qwen2.5-3b", "--out", d, "--key", "k1",
                     "--cache-len", "64", "--block-k", "4",
                     "--batch", "2", "--prefill-batch", "2", "--seq", "16"])
        rp = Replayer(key=b"k1")
        pre = rp.load(os.path.join(d, "qwen2.5-3b_prefill.codyrec"))
        dec = rp.load(os.path.join(d, "qwen2.5-3b_decode.codyrec"))

        params = M.init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                  cfg.vocab_size)
        out_r, caches_r = rp.execute(pre, params, {"tokens": toks})

        mesh = make_host_mesh(model=1)
        rules = rules_for("serve", mesh.axis_names)
        prefill = jax.jit(ST.make_prefill_step(cfg, rules, cache_len=64))
        out_j, caches_j = prefill(params, {"tokens": toks})
        np.testing.assert_array_equal(np.asarray(out_r["next_tokens"]),
                                      np.asarray(out_j["next_tokens"]))

        fused = jax.jit(ST.make_fused_decode_step(cfg, rules, k=4),
                        donate_argnums=(3,))
        pos = jnp.full((2,), 16, jnp.int32)
        blk_r, _ = rp.execute(dec, params, out_r["next_tokens"], pos, caches_r)
        blk_j, _ = fused(params, out_j["next_tokens"], pos, caches_j)
        np.testing.assert_array_equal(np.asarray(blk_r["tokens"]),
                                      np.asarray(blk_j["tokens"]))
        assert rp.stats["executions"] == 2


def test_serve_from_recordings_only():
    """The engine in TEE mode: executes via the Replayer, never touching
    live jit compilation for the decode path."""
    from repro.launch.record import main as record_main
    from repro.launch.serve import build_engine

    cfg = smoke_shrink(get_config("qwen2.5-3b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        record_main(["--arch", "qwen2.5-3b", "--out", d, "--key", "k2",
                     "--cache-len", "64", "--block-k", "4",
                     "--batch", "1", "--seq", "8"])
        eng = build_engine(cfg, n_slots=1, cache_len=64, block_k=4,
                           eos_id=2, params=params, recordings_dir=d,
                           key=b"k2")
        eng.submit([5, 6, 7, 8, 9, 10, 11, 12], max_new=8)
        outs = eng.run()
        assert len(outs[0]) <= 8 and len(outs[0]) > 0


def test_train_loss_decreases():
    from repro.launch.train import main as train_main
    final = train_main(["--arch", "qwen2.5-3b", "--steps", "30",
                        "--batch", "4", "--seq", "32", "--lr", "1e-2",
                        "--log-every", "30"])
    # synthetic uniform tokens: loss should move toward ln(vocab)=5.5 from
    # the random-init value and stay finite
    assert np.isfinite(final) and final < 8.0


def test_grad_compression_trains():
    from repro.launch.train import main as train_main
    final = train_main(["--arch", "qwen2.5-3b", "--steps", "10",
                        "--batch", "2", "--seq", "16", "--grad-compress",
                        "--log-every", "10"])
    assert np.isfinite(final)


@pytest.mark.slow
def test_dryrun_mini_multidevice():
    """Miniature dry-run: 8 fake devices, 4x2 mesh, two archs — proves
    lower+compile+analyze works under SPMD in a fresh process."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from repro.configs import get_config, smoke_shrink, input_specs
from repro.launch.mesh import make_mesh
from repro.sharding import rules_for, shardings_for
from repro.models import model as M
from repro.training import steps as ST
from repro.analysis.hlo import analyze
mesh = make_mesh((4, 2), ("data", "model"))
for arch in ("qwen2.5-3b", "zamba2-1.2b"):
    cfg = smoke_shrink(get_config(arch), vocab_size=512)
    rules = rules_for("train", mesh.axis_names)
    fn = ST.make_train_step(cfg, rules)
    state = ST.abstract_train_state(cfg)
    batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
    st_sh = shardings_for(ST.train_state_axes(cfg), state, mesh, rules)
    with jax.set_mesh(mesh):
        c = jax.jit(fn, in_shardings=(st_sh, None),
                    donate_argnums=(0,)).lower(state, batch).compile()
    cost = analyze(c.as_text(), 8)
    assert cost["flops"] > 0
    print("MINI_OK", arch, int(cost["flops"]))
"""
    out = subprocess.run([sys.executable, "-c", code, SRC],
                         capture_output=True, text=True, timeout=560)
    assert out.stdout.count("MINI_OK") == 2, out.stderr[-3000:]
