"""Sharding resolution + HLO analyzer correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.analysis.hlo import _shape_bytes, _wire_bytes, analyze
from repro.sharding import rules_for, spec


# ------------------------------------------------------------- sharding ----
def test_rules_modes():
    train = rules_for("train", ("pod", "data", "model"))
    assert train["batch"] == ("pod", "data")
    assert train["seq"] == "model"          # Megatron SP
    assert train["fsdp"] == ("pod", "data")
    serve = rules_for("serve", ("data", "model"))
    assert serve["fsdp"] is None            # no weight gathers at decode
    assert serve["kv_seq"] == "model"       # SP cache


@settings(max_examples=60, deadline=None)
@given(st.integers(1, 512), st.integers(1, 512))
def test_spec_divisibility_fallback(d0, d1):
    """Any shape resolves to a legal spec: dims not divisible by the mesh
    axis product fall back to replication."""
    rules = rules_for("train", ("data", "model"))
    mesh_shape = {"data": 16, "model": 16}
    s = spec(("batch", "ffn"), rules, (d0, d1), mesh_shape)
    for dim, part in zip((d0, d1), s):
        if part is not None:
            n = np.prod([mesh_shape[a] for a in
                         (part if isinstance(part, tuple) else (part,))])
            assert dim % n == 0


def test_spec_dedup_physical_axes():
    rules = rules_for("serve", ("data", "model"))
    s = spec(("batch", "kv_seq", "kv_heads", "head_dim"), rules,
             (128, 4096, 8, 128), {"data": 16, "model": 16})
    flat = [a for a in s if a is not None]
    assert len(set(map(str, flat))) == len(flat)  # no axis used twice


# ------------------------------------------------------------- analyzer ----
def test_shape_bytes():
    assert _shape_bytes("bf16[4,32]{1,0}") == 256
    assert _shape_bytes("f32[]") == 4
    assert _shape_bytes("(s32[], bf16[4,32]{1,0})") == 4 + 256
    assert _shape_bytes("pred[10]") == 10


def test_wire_bytes_formulas():
    assert _wire_bytes("all-gather", 0, 1024, 4) == 768     # S(n-1)/n
    assert _wire_bytes("all-reduce", 1024, 1024, 4) == 1536  # 2S(n-1)/n
    assert _wire_bytes("reduce-scatter", 1024, 256, 4) == 768
    assert _wire_bytes("collective-permute", 0, 512, 4) == 512


def test_analyzer_scan_equals_unrolled_flops():
    """Trip-count correction: scan flops == unrolled flops == analytic."""
    L, D, B = 5, 64, 32

    def layer(h, w):
        return jnp.dot(h, w), ()

    def f_scan(ws, x):
        h, _ = jax.lax.scan(layer, x, ws)
        return h.sum()

    def f_unroll(ws, x):
        h = x
        for i in range(L):
            h, _ = layer(h, ws[i])
        return h.sum()

    ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    x = jax.ShapeDtypeStruct((B, D), jnp.float32)
    a_s = analyze(jax.jit(f_scan).lower(ws, x).compile().as_text())
    a_u = analyze(jax.jit(f_unroll).lower(ws, x).compile().as_text())
    analytic = L * 2 * B * D * D
    assert abs(a_s["flops"] - analytic) / analytic < 0.05
    assert abs(a_u["flops"] - analytic) / analytic < 0.05


def test_analyzer_trip_count_from_condition():
    """Post-SPMD dumps lack backend_config — trip count comes from the loop
    condition constant."""
    text = """
HloModule test

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[4] get-tuple-element(%p), index=1
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  %w = f32[4,4] constant({...})
  %y = f32[4] dot(%x, %w), lhs_contracting_dims={0}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[4]) tuple(%ni, %y)
}

%cond (p: (s32[], f32[4])) -> pred[] {
  %p = (s32[], f32[4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(9)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4] parameter(0)
  %zero = s32[] constant(0)
  %t0 = (s32[], f32[4]) tuple(%zero, %x)
  %wl = (s32[], f32[4]) while(%t0), condition=%cond, body=%body
  ROOT %out = f32[4] get-tuple-element(%wl), index=1
}
"""
    a = analyze(text, mode="spmd")
    assert a["flops"] == 9 * 2 * 4 * 4  # 9 trips x dot(4x4)


def test_analyzer_collectives_in_loops_multiply():
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device (covered by subprocess test)")
