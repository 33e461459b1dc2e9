"""The trace reduction on synthetic traces: busy union, idle share, device
time per executable, and idle gaps attributed to host spans."""
import pytest

from bench.harness import tracing
from bench.harness.tracing import Trace


def _trace():
    dev = "/device:TPU:0"
    ops = [(1.0, 2.0, "fusion.1"), (1.5, 2.5, "fusion.2"),  # overlap
           (3.0, 3.5, "fusion.1"), (9.0, 9.5, "fusion.3"),
           (0.0, 0.5, "fusion.9"),                          # before window
           (2.9, 3.6, "while.3")]                           # holds fusion.1
    modules = [(1.0, 2.5, "jit_fused(12)"), (3.0, 3.5, "jit_prefill_step(7)"),
               (9.0, 9.5, "jit_fused(12)"), (0.0, 0.5, "jit_fused(12)")]
    spans = [(0.9, 10.0, "window"), (0.9, 2.6, "step_block"),
             (2.6, 2.9, "validate"), (3.2, 6.0, "step_block"),
             (3.3, 5.0, "prefill")]
    return Trace({dev: ops}, {dev: modules}, spans)


def test_union_merges_overlaps():
    assert tracing.union([(3, 4, "a"), (1, 2, "b"), (1.5, 3.5, "c")]) == \
        [(1, 4)]
    assert tracing.union([(1, 2), (3, 4)]) == [(1, 2), (3, 4)]


def test_gaps_complement_busy_inside_window():
    merged = tracing.union([(1, 2), (3, 4)])
    assert tracing.gaps(merged, 0.5, 5) == [(0.5, 1), (2, 3), (4, 5)]
    assert tracing.gaps(merged, 1, 4) == [(2, 3)]


def test_reduce_busy_idle_and_executables():
    red = tracing.reduce(_trace())
    assert red["window_s"] == pytest.approx(9.1)
    # union inside [0.9, 10]: [1, 2.5] + [2.9, 3.6] + [9, 9.5]
    assert red["busy_s"] == pytest.approx(2.7)
    mods = red["modules"]
    assert mods["jit_fused"] == {"count": 2, "seconds": pytest.approx(2.0)}
    assert mods["jit_prefill_step"]["count"] == 1
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(1.5)
    assert "fusion.9" not in ops and "while.3" not in ops


def test_idle_gaps_go_to_the_innermost_open_span():
    idle = dict(tracing.reduce(_trace())["idle_gaps"])
    # gaps: [0.9,1] step_block, [2.5,2.9] mid 2.7 validate,
    # [3.6,9] mid 6.3 outside any span -> host, [9.5,10] host
    assert idle["step_block"] == pytest.approx(0.1)
    assert idle["validate"] == pytest.approx(0.4)
    assert idle["host"] == pytest.approx(5.9)
    assert sum(idle.values()) == pytest.approx(9.1 - 2.7)
    red = tracing.reduce(Trace({"/device:TPU:0": [(3.4, 3.45, "x")]},
                               {}, [(3.0, 4.0, "window"),
                                    (3.0, 4.0, "step_block"),
                                    (3.1, 3.3, "prefill")]))
    # gaps [3, 3.4] (mid 3.2, inside prefill) and [3.45, 4] (step_block)
    idle = dict(red["idle_gaps"])
    assert idle["prefill"] == pytest.approx(0.4)
    assert idle["step_block"] == pytest.approx(0.55)


def test_no_device_events_reduce_to_nothing():
    assert tracing.reduce(Trace({}, {}, [(0, 1, "window")])) == {}


def test_load_reads_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("step_block"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tracing.load(str(tmp_path))
    names = {n for _, _, n in t.spans}
    assert {"window", "step_block"} <= names
    assert tracing.module_name("jit_fused(123)") == "jit_fused"
