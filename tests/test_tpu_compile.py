"""The chip's compiler, without the chip: every Pallas kernel and the
full-width decode step compile for a described TPU v5e, and the body of
``chip_smoke.py`` serves identical streams through registry replay and
live jit on the CPU.

The topology is described inside fixtures only (never at import), so
every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler library.
"""
import dataclasses
import functools
import importlib.util
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.api import Workspace
from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_chunk_scan_chunked
from repro.kernels.mlstm import mlstm_chunk_scan
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.rmsnorm import rmsnorm
from repro.models.ssm import mamba2_dims
from repro.models.xlstm import mlstm_dims

REPO = os.path.join(os.path.dirname(__file__), "..")
V5E_HBM_BYTES = 16 * 2 ** 30
SMOKE_SHAPES = dict(cache_len=64, block_k=4, batch=4, prefill_batch=1, seq=8)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _compile_kernel(kernel, one_chip, *shapes):
    """AOT-compile ``kernel`` (interpret=False) for one described chip;
    ``shapes`` are (shape, dtype) pairs."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(functools.partial(kernel, interpret=False)) \
        .lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _kernel_cases():
    """(kernel, [(shape, dtype)...]) at the widths of a published config."""
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    qwen = get_config("qwen2.5-3b")
    H, Hkv, hd, D = qwen.num_heads, qwen.num_kv_heads, qwen.hd(), qwen.d_model
    S, W, B = 1024, 1024, 4
    mix = get_config("mixtral-8x22b")
    E, F = mix.moe.num_experts, mix.moe.expert_d_ff
    zamba = get_config("zamba2-1.2b")
    _d_in, nh, P, N = mamba2_dims(zamba)
    Q = zamba.ssm.chunk
    xl = get_config("xlstm-350m")
    _d_in, mh, dh = mlstm_dims(xl)
    C = xl.xlstm.chunk
    return {
        "flash_attention": (flash_attention, [
            ((1, S, H, hd), bf16), ((1, S, Hkv, hd), bf16),
            ((1, S, Hkv, hd), bf16)]),
        "decode_attention": (decode_attention, [
            ((B, H, hd), bf16), ((B, W, Hkv, hd), bf16),
            ((B, W, Hkv, hd), bf16), ((B,), i32)]),
        "rmsnorm": (rmsnorm, [((64, D), bf16), ((D,), f32)]),
        "moe_gmm": (moe_gmm, [((E, 256, mix.d_model), bf16),
                              ((E, mix.d_model, F), bf16)]),
        "mamba_scan": (mamba_chunk_scan_chunked, [
            ((1, 4, Q, nh, P), f32), ((1, 4, Q, N), f32),
            ((1, 4, Q, N), f32), ((1, 4, Q, nh), f32)]),
        "mlstm": (mlstm_chunk_scan, [((1, 4, C, mh, dh), f32)] * 3
                  + [((1, 4, C, mh), f32)] * 2),
    }


@pytest.mark.parametrize("name", ["flash_attention", "decode_attention",
                                  "rmsnorm", "moe_gmm", "mamba_scan",
                                  "mlstm"])
def test_kernel_compiles_for_v5e(one_chip, name):
    kernel, shapes = _kernel_cases()[name]
    _compile_kernel(kernel, one_chip, *shapes)


def _compile_qwen_decode(topo, layers, batch, cache_len=1024):
    """qwen2.5-3b's fused decode step at published widths, depth cut to
    ``layers``, compiled for one v5e with the placement the Workload
    records it under. Returns (compiled, bytes of one stacked K/V cache
    leaf, that leaf's HLO shape)."""
    cfg = dataclasses.replace(get_config("qwen2.5-3b"), num_layers=layers)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    wl = Workspace().workload(cfg, mesh=mesh, cache_len=cache_len,
                              block_k=8, batch=batch)
    fn, specs, donate = wl.step("decode")
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=wl.in_shardings("decode"),
                           donate_argnums=donate).lower(*specs).compile()
    shape = (layers, batch, cache_len, cfg.num_kv_heads, cfg.hd())
    leaf = f"bf16[{','.join(map(str, shape))}]"
    return compiled, int(np.prod(shape)) * 2, leaf


def _whole_leaf_copies(compiled, leaf):
    """HLO copies (sync or async) whose result is a whole stacked leaf."""
    return [ln for ln in compiled.as_text().splitlines()
            if re.search(r"\bcopy(-start)?\(", ln)
            and leaf in re.split(r"\bcopy(-start)?\(", ln)[0]]


def test_qwen_decode_step_compiles_and_fits_one_chip(topo):
    """qwen2.5-3b's fused decode step at published widths (depth cut to 2
    layers) compiles for one v5e with the placement the Workload records
    it under, its memory fits the chip's HBM, and it writes the donated
    caches in place: no whole stacked cache leaf is copied, and its
    temporaries are smaller than one leaf."""
    compiled, leaf_bytes, leaf = _compile_qwen_decode(topo, 2, 4)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.alias_size_in_bytes > 0          # the caches are donated
    assert 0 < total < V5E_HBM_BYTES
    assert mem.temp_size_in_bytes < leaf_bytes
    assert _whole_leaf_copies(compiled, leaf) == []


def test_qwen_decode_step_carries_the_cache_at_32_slots(topo):
    """At 4 layers x 32 slots x 1024 the copies the layer scan would make of
    a re-stacked cache no longer fit in on-chip memory and would show as
    temporaries of 1.5 leaves; the carried cache needs well under one."""
    compiled, leaf_bytes, leaf = _compile_qwen_decode(topo, 4, 32)
    assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes / 4
    assert _whole_leaf_copies(compiled, leaf) == []


def test_chip_smoke_serving_body_replay_equals_live(chip_smoke):
    """chip_smoke's serving body (record -> publish -> verified registry
    replay, then live jit on the same params) at smoke widths: the check
    raises unless both paths emit identical streams, the replay engine
    compiles nothing while serving, and the decode caches are donated."""
    logs = []
    out = chip_smoke.replay_vs_live(smoke=True, shapes=SMOKE_SHAPES,
                                    n_requests=4, log=logs.append)
    assert len(out["streams"]) == 4 + SMOKE_SHAPES["batch"] + 1
    assert out["tokens"] == sum(len(s) for s in out["streams"]) > 0
    assert set(out["recorded"]) == {"prefill", "decode"}
    assert any("streams identical" in line for line in logs)


def test_chip_smoke_refuses_a_host_without_tpu(chip_smoke, capsys):
    """The bring-up check has no CPU branch: on this host it exits
    non-zero before any phase and prints no result line."""
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_mesh_phase_on_four_virtual_devices():
    """The ``--chips 4`` body on four virtual CPU devices (two slots per
    device, as on the chips): registry replay on the default
    data-parallel mesh equals replay on one device, and the caches and
    tokens span all four devices."""
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {os.path.abspath(REPO)!r})\n"
        "import chip_smoke as cs\n"
        f"r = cs.mesh_vs_one_device(smoke=True, "
        f"shapes={dict(SMOKE_SHAPES, batch=8)!r}, "
        "n_requests=4, log=lambda m: None)\n"
        "print('RESULT' + json.dumps(r['spans']))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT")]
    assert json.loads(line[0][len("RESULT"):]) == {"caches": 4, "tokens": 4}
