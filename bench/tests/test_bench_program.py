"""The program's serving spans in a trace: idle gaps attributed to the
innermost span of either kind, the readings made from the spans and from
the requests' stamps, and a traced run of a toy cell on the CPU with the
program's tracer on and off."""
import types

import pytest

from bench.harness import program, spec, tracing
from bench.harness.program import ProgramTrace
from bench.harness.tracing import Trace
from bench.tests import tiny


def _program_trace():
    dev = "/device:TPU:0"
    ops = [(1.0, 2.0, "fusion.1"), (3.0, 3.5, "fusion.2"),
           (4.0, 6.0, "fusion.1")]
    harness = [(0.5, 7.0, "window"), (2.0, 3.9, "step_block"),
               (2.8, 3.5, "prefill"), (6.0, 6.8, "validate")]
    prog = [(2.05, 3.85, "executor.admit", {"rid": 0, "n": 2}),
            (2.1, 2.8, "frontier.drain", {"blocks": 2}),
            (2.1, 2.4, "frontier.wait", {"block": 0}),
            (2.4, 2.7, "frontier.apply", {"block": 0}),
            (2.7, 2.8, "frontier.commit", {"requests": 4}),
            (2.8, 3.5, "prefill.dispatch", {"rid": 0}),
            (3.3, 3.5, "executor.scatter", {"n": 1}),
            (6.05, 6.75, "frontier.drain", {"blocks": 1}),
            (6.05, 6.1, "frontier.wait", {"block": 0}),
            (6.1, 6.6, "frontier.apply", {"block": 0}),
            (6.6, 6.7, "frontier.commit", {"requests": 4})]
    trace = Trace({dev: ops}, {}, harness + [p[:3] for p in prog])
    return ProgramTrace(trace, prog)


def test_idle_goes_to_the_innermost_program_span():
    pt = _program_trace()
    red = tracing.reduce(pt.trace)
    idle = dict(red["idle_gaps"])
    # gaps [0.5, 1] host; [2, 3] mid 2.5 in frontier.apply (inside drain,
    # admit, step_block); [3.5, 4] mid 3.75 in executor.admit (prefill
    # ended); [6, 7] mid 6.5 in frontier.apply (inside validate)
    assert idle == pytest.approx({"host": 0.5, "frontier.apply": 2.0,
                                  "executor.admit": 0.5})
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert program.longest_gaps(pt) == pytest.approx([
        (1.5, 1.0, "frontier.apply"), (5.5, 1.0, "frontier.apply"),
        (0.0, 0.5, "host"), (3.0, 0.5, "executor.admit")])
    # without program spans the same trace reduces as it always did
    plain = Trace(pt.trace.ops, {}, [s for s in pt.trace.spans
                                     if s[2] in tracing.SPANS])
    assert dict(tracing.reduce(plain)["idle_gaps"]) == pytest.approx(
        {"host": 0.5, "step_block": 1.5, "validate": 1.0})


def test_admission_idle_and_frontier_host_readings():
    pt = _program_trace()
    # idle inside the admission [2.05, 3.85]: [2.05, 3] + [3.5, 3.85]
    # = 1.3 s over the 2 requests it admitted
    assert program.admission_idle_ms(pt) == pytest.approx(650.0)
    # drains outside their waits: 0.7 - 0.3 and 0.7 - 0.05
    assert program.frontier_host_ms(pt) == pytest.approx(525.0)
    spans = program.span_seconds(pt)
    assert spans["frontier.drain"]["count"] == 2
    assert spans["frontier.wait"]["seconds"] == pytest.approx(0.35)
    assert program.overlap([(0, 2), (3, 5)], [(1, 4)]) == pytest.approx(2)


def test_readings_are_none_without_program_spans_or_stamps():
    pt = _program_trace()
    bare = ProgramTrace(Trace(pt.trace.ops, {}, pt.trace.spans[:4]), [])
    assert program.admission_idle_ms(bare) is None
    assert program.frontier_host_ms(bare) is None
    assert program.span_seconds(bare) == {}
    no_device = ProgramTrace(Trace({}, {}, pt.trace.spans), pt.program)
    assert program.admission_idle_ms(no_device) is None
    assert program.longest_gaps(no_device) == []
    old = {0: types.SimpleNamespace(submit_t=1.0, finish_t=2.0)}
    assert program.queue_p95_ms(old, [0], 9.0) is None
    assert program.commit_wait_p95_ms(old, [0], 9.0) is None
    new = {i: types.SimpleNamespace(submit_t=0.0, admit_t=0.01 * i,
                                    prefilled_t=1.0, first_t=1.0 + 0.1 * i)
           for i in range(1, 21)}
    new[21] = types.SimpleNamespace(submit_t=0.0, admit_t=0.0,
                                    prefilled_t=0.0, first_t=0.0)
    assert program.queue_p95_ms(new, list(new), 9.0) == pytest.approx(190.5)
    assert program.commit_wait_p95_ms(new, list(new), 9.0) == \
        pytest.approx(1905.0)
    # stamps after the window closed are left out (first_t 2.1 .. 3.0):
    # the p95 of 0.1 .. 1.0 s
    assert program.commit_wait_p95_ms(new, list(new), 2.05) == \
        pytest.approx(955.0)


@pytest.mark.parametrize("counters,expected", [
    ({"admission_drains": 12, "blocks_dispatched": 48}, 0.25),
    ({"admission_drains": 0, "blocks_dispatched": 48}, 0.0),
    ({"admission_drains": 3, "blocks_dispatched": 0}, None),
    ({"host_syncs": 12, "blocks_dispatched": 48}, None)],
    ids=["drains", "none_forced", "no_blocks", "not_counted"])
def test_admission_drains_per_block_reading(counters, expected):
    """Drains per block from the window's counters; nothing where the
    program does not count them or dispatched no block."""
    read = spec.metric_reader("executor.admission_drains_per_block")
    run = types.SimpleNamespace(summary={"counters": counters})
    assert read(run) == expected


@pytest.mark.parametrize("tracer", [True, False], ids=["on", "off"])
def test_traced_cpu_run_reads_the_program_spans(tracer):
    from bench import program_trace
    cell = tiny.cell()
    cell.name = f"tiny.program_{int(tracer)}"    # a trace directory of its own
    res = program_trace.run(cell, 2 ** 32 + 9, 1.5, tracer,
                            require_chip=False, peak=tiny.PEAK)
    assert res["run"]["correct"] is True
    # the stamps are read whether or not the tracer is on
    assert res["queue_p95_ms"] >= 0 and res["commit_wait_p95_ms"] > 0
    assert res["admitted_after_close"] >= 0
    assert res["window_s_per_block"] > 0
    assert res["counters"]["prefill_dispatches"] > 0
    # no device plane on the CPU: nothing to attribute idle time from
    assert res["admission_idle_ms"] is None and res["longest_gaps"] == []
    if tracer:
        assert res["frontier_host_ms"] > 0
        assert {"executor.admit", "prefill.dispatch", "executor.scatter",
                "frontier.drain", "frontier.wait", "frontier.apply",
                "frontier.commit", "decode.block"} <= set(res["spans"])
    else:
        assert res["frontier_host_ms"] is None and res["spans"] == {}
