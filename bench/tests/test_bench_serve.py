"""The harness's loop serves a toy Qwen2 through the registry's verified
ReplayChannel on the CPU, with nothing compiled in the window, and a run
reports the cell's metrics and checks."""
import importlib.util
import os

import pytest

from bench.harness import serve, spec
from bench.harness.traffic import Traffic
from bench.tests import tiny


def _run_module():
    s = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(spec.BENCH_DIR, "run.py"))
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("closed", [False, True], ids=["open", "closed"])
def test_loop_serves_through_registry_replay(closed):
    cell = tiny.cell(closed=closed)
    adapter, ref = spec.family("qwen2")
    sess = serve.Session(cell, adapter, ref)
    sess.boot()
    assert sess.channel.inner.kind == "signed-replay"
    sess.load(2 ** 33 + 1)
    served = sess.serve(Traffic(cell.traffic, 256, 5), 1.5)
    summ = serve.summary(served)
    assert served.compiles == []
    assert served.in_window and not served.unserved
    assert summ["tokens"] > 0 and summ["ttft_p95_ms"] > 0
    assert summ["counters"]["blocks_dispatched"] > 0
    assert sess.wl.replayer_stats()["fast_hits"] > 0
    done = [r for r in served.in_window if served.requests[r].done]
    assert done and all(len(served.requests[r].generated)
                        == served.requests[r].max_new or
                        served.requests[r].generated[-1] == 2 for r in done)


@pytest.mark.parametrize("trace,untied", [(0, False), (1, True)],
                         ids=["end_to_end", "per_layer_untied"])
def test_run_reports_metrics_and_checks(trace, untied):
    cell = tiny.cell(untied=untied)
    out = _run_module().run_cell(cell, 77, 1.5, bool(trace),
                                 require_chip=False, peak=tiny.PEAK)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    for name in ("kv_err", "logit_err"):
        assert out["checks"][name]["value"] <= tiny.SHAPE["limits"][name]
    names = set(out["metrics"])
    if trace:
        # host-side readers read on the CPU; device readers find no trace
        assert {"boot_s", "replay.dispatch_us",
                "executor.host_syncs_per_block"} <= names
        assert "device.idle_share" not in names
        assert "breakdown" in out
    else:
        assert names == {"setup_s", "tokens_per_s", "ttft_p95_ms",
                         "itl_p95_ms"}
